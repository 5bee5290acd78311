"""Mean active slots per decode step: the ``active`` stat of the program's
``serve.step`` spans in the window. Silent where the program opened no
``serve.`` span."""
import program_spans as P


def read(run):
    active = [st["active"] for n, _, _, st in P.spans(run)
              if n == "serve.step"]
    return sum(active) / len(active) if active else None
