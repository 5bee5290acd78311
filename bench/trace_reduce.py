"""Reduction of a profiler trace to the benchmark's numbers.

Two steps, kept apart so that the second can be tested on a small recorded
trace:

1. :func:`reduce_xplane` reads the ``.xplane.pb`` that ``jax.profiler``
   wrote and keeps the device's operations (line ``XLA Ops`` of each
   ``/device:TPU:<n>`` plane, as an :func:`ops_table`), its executables
   (line ``XLA Modules``) and the host's ``TraceAnnotation`` spans that the
   benchmark itself opened (names that start with ``bench.``), the last two
   as lists of ``[name, start_ns, duration_ns]``. Device and host times
   share one clock.
2. The functions below read that reduced trace: busy time as the union of
   the operation intervals inside the windows, device time per executable,
   and the host span that covers each idle gap.
"""
from __future__ import annotations

import glob
import os
import re
from array import array
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


def latest_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def ops_table(events) -> Dict:
    """Device operations as arrays: each distinct name once, and per event
    its name's index, start and end in ns. Accepts ``[name, start, dur]``
    triples; a table passes through."""
    if isinstance(events, dict):
        return events
    names: Dict[str, int] = {}
    idx = [names.setdefault(n, len(names)) for n, _, _ in events]
    start = np.array([s for _, s, _ in events], np.int64)
    dur = np.array([d for _, _, d in events], np.int64)
    return {"names": list(names), "idx": np.array(idx, np.int64),
            "start": start, "end": start + dur}


def reduce_xplane(path: str) -> Dict:
    """``{"devices": {plane: {"ops": table, "modules": [...]}},
    "host": [...]}`` from one trace file (``ops_table`` for the table)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict] = {}
    host: List = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    d["modules"].extend([ev.name, int(ev.start_ns),
                                         int(ev.duration_ns)]
                                        for ev in line.events)
                elif line.name == OPS_LINE:
                    # millions of events with long names: keep each name once
                    names: Dict[str, int] = {}
                    buf = array("q")
                    for ev in line.events:
                        buf.extend((names.setdefault(ev.name, len(names)),
                                    int(ev.start_ns), int(ev.duration_ns)))
                    a = np.frombuffer(buf, np.int64).reshape(-1, 3)
                    d["ops"] = {"names": list(names), "idx": a[:, 0],
                                "start": a[:, 1], "end": a[:, 1] + a[:, 2]}
        else:
            for line in plane.lines:
                host.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events
                            if ev.name.startswith(HOST_SPAN_PREFIX))
    for d in devices.values():
        d["ops"] = ops_table(d["ops"])
    return {"devices": devices, "host": host}


def windows_of(reduced: Dict, span: str) -> List[Tuple[int, int]]:
    """[start, end) in ns of every host span named ``span``: the measured
    window is their union (the streams or builds, not what runs between)."""
    return sorted((start, start + dur) for name, start, dur in reduced["host"]
                  if name == span)


def window_ns(windows) -> int:
    return sum(e - s for s, e in windows)


def clip(events, windows) -> List[Tuple[str, int, int]]:
    """Events cut to the windows, as (name, start, end)."""
    out = []
    for lo, hi in windows:
        for name, start, dur in events:
            s, e = max(start, lo), min(start + dur, hi)
            if e > s:
                out.append((name, s, e))
    return out


def union_ns(intervals) -> int:
    """Length of the union of (start, end) intervals."""
    a = np.array(sorted(intervals), np.int64).reshape(-1, 2)
    return _union(a[:, 0], a[:, 1])


def _union(start, end) -> int:
    """Union length of intervals sorted by start."""
    if len(start) == 0:
        return 0
    reach = np.maximum.accumulate(end)
    prev = np.concatenate([[start[0]], reach[:-1]])
    return int(np.clip(end - np.maximum(start, prev), 0, None).sum())


def _ops_in(ops: Dict, lo: int, hi: int):
    """(name index, start, end) of the operations cut to [lo, hi), by
    start."""
    keep = (ops["end"] > lo) & (ops["start"] < hi)
    s = np.maximum(ops["start"][keep], lo)
    e = np.minimum(ops["end"][keep], hi)
    order = np.argsort(s, kind="stable")
    return ops["idx"][keep][order], s[order], e[order]


def busy_ns(reduced: Dict, windows) -> float:
    """Union of operation intervals in the windows, averaged over devices."""
    devs = reduced["devices"]
    if not devs:
        return 0.0
    total = 0
    for d in devs.values():
        ops = ops_table(d["ops"])
        for lo, hi in windows:
            _, s, e = _ops_in(ops, lo, hi)
            total += _union(s, e)
    return total / len(devs)


def module_counts(reduced: Dict, windows) -> Dict[str, List[int]]:
    """Executable name -> [total ns, executions] inside the windows,
    summed over devices."""
    acc: Dict[str, List[int]] = {}
    for d in reduced["devices"].values():
        for name, s, e in clip(d["modules"], windows):
            a = acc.setdefault(name, [0, 0])
            a[0] += e - s
            a[1] += 1
    return acc


LOST_SHARE = 0.25     # of the calls, at most recorded as missing
SPLIT_SHARE = 0.02    # of the calls, at most recorded twice


def executed(reduced: Dict, windows, calls: int) -> Optional[List[int]]:
    """[total ns, executions] of the costliest executable that ran about
    ``calls`` times in the windows, or None. About, because the profiler's
    record of a long window can miss executions or split a few: a count
    from ``calls`` less ``LOST_SHARE`` of it to ``calls`` plus
    ``SPLIT_SHARE`` of it matches (rounded down, so exact for a few
    calls). The per-execution time stays
    that of the executions recorded. Of the executables that run with the
    decode step, the conversions of its token ids run more often (also per
    admission) and cost far less; the inserts run once per admission."""
    lo, hi = calls - int(LOST_SHARE * calls), calls + int(SPLIT_SHARE * calls)
    hits = [v for v in module_counts(reduced, windows).values()
            if lo <= v[1] <= hi]
    return max(hits) if hits else None


def short_op(text: str) -> str:
    """``%name op shape`` from an operation's HLO text, layouts dropped."""
    m = re.match(r"(%[\w.\-]+) = (.*)", text)
    if not m:
        return text[:120]
    op = re.search(r"[})\]] ([a-z][\w\-]*)\(", m.group(2))
    shape = re.sub(r"\{[^}]*\}", "", m.group(2)[:op.start() + 1]
                   if op else m.group(2))
    return f"{m.group(1)} {op.group(1) if op else ''} {shape[:80]}".strip()


def top_ops(reduced: Dict, windows, k: int = 10) -> List[List]:
    """The ``k`` device operations that took the most time, in seconds,
    averaged over devices."""
    acc: Dict[str, float] = defaultdict(float)
    devs = reduced["devices"]
    for d in devs.values():
        ops = ops_table(d["ops"])
        for lo, hi in windows:
            idx, s, e = _ops_in(ops, lo, hi)
            per = np.bincount(idx, weights=(e - s).astype(np.float64),
                              minlength=len(ops["names"]))
            for n in np.nonzero(per)[0]:
                acc[ops["names"][n]] += per[n]
    n = max(len(devs), 1)
    return [[short_op(name), t / n / 1e9] for name, t in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(reduced: Dict, windows, k: int = 10) -> List[List]:
    """The ``k`` longest device idle gaps of the first device, in seconds,
    each named by the innermost benchmark host span that covers its middle
    (``"none"`` where no span does)."""
    devs = reduced["devices"]
    if not devs:
        return []
    ops = ops_table(devs[sorted(devs)[0]]["ops"])
    gaps = []
    for lo, hi in windows:
        _, s, e = _ops_in(ops, lo, hi)
        reach = np.maximum.accumulate(np.concatenate([[lo], e]))
        starts = np.concatenate([s, [hi]])
        open_ = starts > reach
        gaps += list(zip(reach[open_].tolist(), starts[open_].tolist()))
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [(n, s, s + d) for n, s, d in reduced["host"]]
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) // 2
        cover = [(se - ss, n) for n, ss, se in spans if ss <= mid < se]
        out.append([min(cover)[1] if cover else "none", (e - s) / 1e9])
    return out
