"""Share of its roofline that the decode step reaches, in %: the least time
of all decode steps in the window (per step the larger of its required
operations over peak FLOP/s and its required bytes over peak bandwidth,
from the configuration's counts module ``bench/counts/<reference>.py``)
over their device time. Which bound holds is ``run.decode_bound``."""
import trace_reduce as T


def read(run):
    if not getattr(run, "steps", 0):
        return None
    hit = T.executed(run.reduced, run.windows, run.steps)
    if hit is None or hit[0] <= 0:
        return None
    # the mean execution over every step the engine made
    return 100.0 * run.decode_least_s / (hit[0] / hit[1] * run.steps / 1e9)
