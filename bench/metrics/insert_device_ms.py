"""Device time per execution of the slot insert, in ms: the executables
named ``jit_serve_insert`` (line ``XLA Modules``) in the window. Silent
where no executable has that name (an older commit named it otherwise)."""
import trace_reduce as T

NAME = "jit_serve_insert"


def read(run):
    if getattr(run, "reduced", None) is None:
        return None
    hits = [v for name, v in T.module_counts(run.reduced,
                                             run.windows).items()
            if name.split("(")[0] == NAME]
    runs = sum(v[1] for v in hits)
    return sum(v[0] for v in hits) / runs / 1e6 if runs else None
