"""Device time of the decode executable per execution, in ms. The decode
executable is the costliest one that ran as many times as the engine
called its model's decode step inside the window, as far as the profiler
recorded them (``trace_reduce.executed``); silent where no one did."""
import trace_reduce as T


def read(run):
    if not getattr(run, "steps", 0):
        return None
    hit = T.executed(run.reduced, run.windows, run.steps)
    return None if hit is None else hit[0] / hit[1] / 1e6
