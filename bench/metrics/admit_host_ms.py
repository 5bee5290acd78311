"""Host time per admission, in ms: the mean, over the admissions in the
window, of the program's ``serve.admit`` span less its ``serve.wait`` child
(the wait for the prefill's logits): the prefill and insert dispatches and
the first token's copy and argmax. Silent where the program opened no
``serve.`` span."""
import program_spans as P


def read(run):
    own = P.own_ns(P.spans(run), "serve.admit", "serve.wait")
    return float(own.mean()) / 1e6 if len(own) else None
