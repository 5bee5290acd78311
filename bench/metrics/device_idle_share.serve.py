"""Share of the serving window in which no operation ran on the device:
1 minus the union of the device's operation intervals, over the summed
stream spans of the traced run (averaged over the chips used)."""
import trace_reduce as T


def read(run):
    if getattr(run, "steps", None) is None:
        return None
    window = T.window_ns(run.windows)
    busy = T.busy_ns(run.reduced, run.windows)
    return None if window <= 0 else 100.0 * (1.0 - busy / window)
