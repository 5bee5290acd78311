"""Host time per decode step, in ms: the mean, over the decode steps in the
window, of the program's ``serve.step`` span less its ``serve.wait`` child
(the wait for the step's logits). It is the engine's own time per step
(dispatch, logits copy, finite check, argmaxes, bookkeeping), in which the
chip has no decode step queued. Silent where the program opened no
``serve.`` span."""
import program_spans as P


def read(run):
    own = P.own_ns(P.spans(run), "serve.step", "serve.wait")
    return float(own.mean()) / 1e6 if len(own) else None
