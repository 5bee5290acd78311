"""The program's own host spans in a traced run.

``repro.serve.ServeEngine.run`` opens ``jax.profiler.TraceAnnotation``
spans named ``serve.*`` (``serve.run``, ``serve.admit``, ``serve.step`` and
their children, ``serve.gc``), with stats such as a decode step's
``active`` slots. They are host events on the clock of the device trace.
:func:`spans` reads them from the run's own ``.xplane.pb``, once per run,
cut to the run's windows; a program that opens none (an older commit)
gives ``[]``, and every reader of them is then silent.

A span is ``(name, start_ns, end_ns, stats)``.
"""
from __future__ import annotations

import os
from typing import Dict, List, Tuple

import numpy as np

import harness
import trace_reduce as T

PREFIX = "serve."
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

Span = Tuple[str, int, int, Dict]


def read_xplane(path: str) -> List[Span]:
    """Every host event named ``serve.*`` in one trace file, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(T.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda sp: sp[1])


def clip(spans: List[Span], windows) -> List[Span]:
    """Spans cut to the windows, by start."""
    out = []
    for lo, hi in windows:
        for name, s, e, stats in spans:
            s2, e2 = max(s, lo), min(e, hi)
            if e2 > s2:
                out.append((name, s2, e2, stats))
    return sorted(out, key=lambda sp: sp[1])


def spans(run) -> List[Span]:
    """The run's program spans in its windows (``run.program_spans``,
    read from the trace of the run's cell on first use)."""
    found = getattr(run, "program_spans", None)
    if found is None:
        logdir = os.path.join(ROOT, harness.TRACE_DIR, run.cell["name"])
        try:
            found = clip(read_xplane(T.latest_xplane(logdir)), run.windows)
        except FileNotFoundError:
            found = []
        run.program_spans = found
    return found


def named(spans_: List[Span], name: str) -> np.ndarray:
    """(start, end) rows of the spans named ``name``, by start."""
    rows = [(s, e) for n, s, e, _ in spans_ if n == name]
    return np.array(rows, np.int64).reshape(-1, 2)


def own_ns(spans_: List[Span], parent: str, child: str) -> np.ndarray:
    """Per span named ``parent``: its duration less that of the spans named
    ``child`` that lie inside it (the spans of one name do not overlap)."""
    p, c = named(spans_, parent), named(spans_, child)
    own = (p[:, 1] - p[:, 0]).astype(np.float64)
    if len(p) and len(c):
        i = np.searchsorted(p[:, 0], c[:, 0], side="right") - 1
        inside = (i >= 0) & (c[:, 1] <= p[np.maximum(i, 0), 1])
        np.subtract.at(own, i[inside], (c[:, 1] - c[:, 0])[inside])
    return own
