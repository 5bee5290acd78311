"""The reduction from a trace to metrics: busy and idle time, device time
per executable and the labels of idle gaps, on a hand-made trace with
known answers and on a short trace recorded on a TPU v5e."""
import json
import os

import pytest

import trace_reduce as T
from conftest import ROOT

DATA = os.path.join(ROOT, "tests", "bench", "data")


def _hand():
    """Two windows [0, 100) and [200, 300) ns on one device."""
    ops = [["a", 10, 20], ["b", 25, 15], ["c", 90, 30],   # 10-40, 90-100
           ["d", 150, 10],                                # outside
           ["a", 190, 20], ["e", 250, 10]]                # 200-210, 250-260
    modules = [["jit_step(1)", 10, 30], ["jit_step(1)", 190, 20],
               ["jit_conv(2)", 90, 30], ["jit_conv(2)", 250, 10],
               ["jit_prefill(3)", 40, 5]]
    host = [["bench.stream", 0, 100], ["bench.stream", 200, 100],
            ["bench.step", 40, 30], ["bench.other", 600, 5]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_hand_made_trace():
    r = _hand()
    win = T.windows_of(r, "bench.stream")
    assert win == [(0, 100), (200, 300)]
    assert T.window_ns(win) == 200
    # busy: [10, 40) + [90, 100) + [200, 210) + [250, 260) = 30+10+10+10
    assert T.busy_ns(r, win) == 60
    counts = T.module_counts(r, win)
    assert counts["jit_step(1)"] == [30 + 10, 2]
    assert counts["jit_conv(2)"] == [10 + 10, 2]
    # two executables ran twice: the costlier is the step
    assert T.executed(r, win, 2) == [40, 2]
    assert T.executed(r, win, 7) is None
    gaps = T.idle_gaps(r, win, k=3)
    # gaps: [0,10) [40,90) [260,300) [210,250): longest first, each named
    # by the innermost host span over its middle
    assert gaps == [["bench.step", 50e-9], ["bench.stream", 40e-9],
                    ["bench.stream", 40e-9]]
    assert T.top_ops(r, win, k=1) == [["a", 30e-9]]


@pytest.mark.parametrize("recorded,calls,found", [
    (2000, 2000, True), (1990, 2000, True), (2040, 2000, True),
    (1500, 2000, True), (1499, 2000, False), (2041, 2000, False)])
def test_executed_about_as_often(recorded, calls, found):
    """``calls`` decode calls, of which the profiler recorded ``recorded``
    executions; the token-id conversions ran once per call and three times
    per each of 300 admissions, the inserts once per admission."""
    step = [["jit_step(1)", 1000 * i, 500] for i in range(recorded)]
    conv = [["jit_conv(2)", 1000 * i + 600, 1] for i in range(calls + 900)]
    ins = [["jit_insert(3)", 1000 * i + 700, 200] for i in range(300)]
    r = {"devices": {"/device:TPU:0": {"ops": [], "modules":
                                        step + conv + ins}},
         "host": [["bench.stream", 0, 10**7]]}
    win = T.windows_of(r, "bench.stream")
    want = [500 * recorded, recorded] if found else None
    assert T.executed(r, win, calls) == want
    assert T.executed(r, win, 300) == [200 * 300, 300]


def test_union_of_overlapping_intervals():
    assert T.union_ns([(0, 10), (5, 15), (20, 30), (30, 31)]) == 26
    assert T.union_ns([]) == 0


def test_short_op_names():
    text = ("%copy.53 = bf16[1,64,1024,12,64]{4,3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[1,64,1024,12,64]{2,4,3,1,0:T(8,128)(2,1)S(1)} %x)")
    assert T.short_op(text) == "%copy.53 copy bf16[1,64,1024,12,64]"


def _sweep_busy(ops, lo, hi):
    """Busy ns by a sweep over boundary points, independent of union_ns."""
    edges = []
    for _, s, d in ops:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    busy, depth, last = 0, 0, None
    for t, step in sorted(edges):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


RECORDED = os.path.join(DATA, "trace_v5e_zip2x_decode.json")


def test_recorded_trace():
    """60 ms from the middle of a traced window of
    ``gpt2-small-zip2x.decode-heavy`` on a TPU v5e."""
    with open(RECORDED) as f:
        r = json.load(f)
    win = T.windows_of(r, "bench.stream")
    assert len(win) == 1
    (lo, hi), = win
    ops = r["devices"]["/device:TPU:0"]["ops"]
    busy = T.busy_ns(r, win)
    assert busy == _sweep_busy(ops, lo, hi)
    assert 0 < busy < T.window_ns(win)
    mods = T.module_counts(r, win)
    assert sum(v[0] for v in mods.values()) <= T.window_ns(win)
    for calls in {v[1] for v in mods.values()}:
        want = max(v for v in mods.values() if v[1] == calls)
        assert T.executed(r, win, calls) == want
    gaps = T.idle_gaps(r, win, k=10)
    assert all(g[0].startswith("bench.") for g in gaps)
    assert sum(g[1] for g in gaps) <= (T.window_ns(win) - busy) / 1e9 + 1e-12
