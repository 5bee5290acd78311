"""Device time per admission, in ms: every executable in the window other
than the decode step (the prefill at its bucket, the slot insert and the
engine's tiny input conversions), over the number of admissions."""
import trace_reduce as T


def read(run):
    if not getattr(run, "admissions", 0):
        return None
    hit = T.executed(run.reduced, run.windows, run.steps)
    if hit is None:
        return None
    total = sum(v[0] for v in T.module_counts(run.reduced,
                                              run.windows).values())
    return (total - hit[0]) / run.admissions / 1e6
