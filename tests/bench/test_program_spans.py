"""The program's own spans (``bench/program_spans.py``) and the per-layer
metrics that read them: a tiny ``ServeEngine`` traced on the CPU inside a
``bench.stream`` span, and each reader on hand-made spans and executables
with known answers."""
import gc
import types

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import harness
import program_spans as P
import serve_job
import trace_reduce as T
from conftest import BENCH

READERS = ("host_step_ms", "admit_host_ms", "decode_batch",
           "insert_device_ms")
CHILDREN = {"serve.admit": ("serve.prefill", "serve.insert", "serve.wait",
                            "serve.sample"),
            "serve.step": ("serve.dispatch", "serve.wait", "serve.pull",
                           "serve.check", "serve.sample", "serve.bookkeep")}


def reader(name):
    return harness.load_module(BENCH, "metrics", name).read


class Collecting(serve_job.Recorder):
    """The harness's adapter, collecting garbage in the third decode
    step of a stream."""

    def step(self, cache, tokens):
        if self.steps == 2:
            gc.collect()
        return super().step(cache, tokens)


@pytest.fixture(scope="module")
def traced(tmp_path_factory, tiny_cfg, tiny_params):
    """One stream of 6 requests through 2 slots, traced as the harness
    traces a cell (host spans only), under a checkout root of its own."""
    from repro.serve import DenseServeModel, Request, ServeEngine
    root = tmp_path_factory.mktemp("checkout")
    cell = "tiny.decode"
    logdir = root / harness.TRACE_DIR / cell
    rec = Collecting(DenseServeModel(tiny_cfg, tiny_params, 48),
                     traced=False)
    eng = ServeEngine(rec, num_slots=2)
    eng.warmup((8, 16))
    rng = np.random.default_rng(4)
    reqs = [Request(rid=i, tokens=rng.integers(0, 256, 5 + 2 * i),
                    steps=int(s), arrival=0.0)
            for i, s in enumerate((6, 3, 1, 8, 4, 2))]
    eng.run(reqs)       # compiles the host-side conversions
    rec.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(str(logdir), profiler_options=opts):
        with jax.profiler.TraceAnnotation(serve_job.STREAM_SPAN):
            report = eng.run(reqs)
    path = T.latest_xplane(str(logdir))
    reduced = T.reduce_xplane(path)
    run = types.SimpleNamespace(
        cell={"name": cell}, reduced=reduced,
        windows=T.windows_of(reduced, serve_job.STREAM_SPAN))
    return types.SimpleNamespace(root=str(root), run=run, path=path,
                                 reqs=reqs, report=report, rec=rec)


def test_engine_spans_in_a_cpu_trace(traced, monkeypatch):
    monkeypatch.setattr(P, "ROOT", traced.root)
    run, report = traced.run, traced.report
    spans = P.spans(run)
    assert run.program_spans is spans
    names = [sp[0] for sp in spans]
    assert names.count("serve.run") == 1
    assert names.count("serve.step") == report.steps == traced.rec.steps
    admits = [sp for sp in spans if sp[0] == "serve.admit"]
    assert sorted(sp[3]["rid"] for sp in admits) == \
        [r.rid for r in traced.reqs]
    assert all(sp[3]["prompt_len"] == traced.reqs[sp[3]["rid"]].prompt_len
               for sp in admits)
    # the spans lie in the window unclipped, on the trace's one clock
    assert P.read_xplane(traced.path) == spans
    (lo, hi), = run.windows
    assert all(lo <= s <= e <= hi for _, s, e, _ in spans)
    # every child inside a parent of its kind, every parent in serve.run
    (_, r0, r1, _), = [sp for sp in spans if sp[0] == "serve.run"]
    for parent in CHILDREN:
        box = P.named(spans, parent)
        assert ((box[:, 0] >= r0) & (box[:, 1] <= r1)).all()
    for kid in {k for kids in CHILDREN.values() for k in kids}:
        box = np.concatenate([P.named(spans, parent) for parent, kids
                              in CHILDREN.items() if kid in kids])
        for s, e in P.named(spans, kid):
            assert ((box[:, 0] <= s) & (e <= box[:, 1])).any(), (kid, s, e)
    # the collection in the third step, and the engine's gc hook is gone
    (_, s2, e2, _), = [sp for sp in spans if sp[0] == "serve.step"
                       and sp[3]["step"] == 2]
    assert any(sp[0] == "serve.gc" and sp[3]["generation"] == 2
               and s2 <= sp[1] <= sp[2] <= e2 for sp in spans)
    assert not [cb for cb in gc.callbacks
                if type(cb).__name__ == "_GCSpans"]
    # the decode batch the spans count is the one the harness rebuilds
    positions = serve_job.step_positions(traced.rec.admissions, traced.reqs,
                                         traced.rec.steps, 2)
    n = (positions >= 0).sum(1)
    assert [sp[3]["active"] for sp in sorted(
        (sp for sp in spans if sp[0] == "serve.step"),
        key=lambda sp: sp[3]["step"])] == n.tolist()
    assert reader("decode_batch")(run) == pytest.approx(n.mean())
    for name in ("host_step_ms", "admit_host_ms"):
        assert reader(name)(run) > 0


def test_executables_named_in_a_cpu_trace(traced):
    """On the CPU the trace has no ``XLA Modules`` line: the dispatch of
    each executable is a host event named by its jitted function, which is
    ``jit_serve_decode``/``jit_serve_insert`` on the chip's line."""
    spans = P.read_xplane(traced.path)
    where = {"PjitFunction(serve_decode)": P.named(spans, "serve.dispatch"),
             "PjitFunction(serve_insert)": P.named(spans, "serve.insert"),
             "PjitFunction(serve_prefill)": P.named(spans, "serve.prefill")}
    seen = dict.fromkeys(where, 0)
    for plane in ProfileData.from_file(traced.path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in where:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    box = where[ev.name]
                    assert ((box[:, 0] <= s) & (e <= box[:, 1])).any()
                    seen[ev.name] += 1
    assert all(seen.values()), seen


def _hand_run(spans, modules):
    """A run whose trace held ``spans`` and, on one device, ``modules``."""
    reduced = {"devices": {"/device:TPU:0": {"ops": [], "modules":
                                              modules}},
               "host": [["bench.stream", 0, 1000]]}
    return types.SimpleNamespace(
        cell={"name": "hand"}, reduced=reduced, program_spans=spans,
        windows=T.windows_of(reduced, "bench.stream"))


HAND_SPANS = [
    ("serve.run", 0, 1000, {"requests": 2}),
    ("serve.admit", 10, 60, {"rid": 0}), ("serve.wait", 30, 50, {}),
    ("serve.admit", 60, 90, {"rid": 1}), ("serve.wait", 70, 75, {}),
    ("serve.step", 100, 200, {"step": 0, "active": 2}),
    ("serve.wait", 110, 150, {}),
    # a retried step: two waits inside one span
    ("serve.step", 200, 400, {"step": 1, "active": 2}),
    ("serve.wait", 210, 260, {}), ("serve.wait", 300, 350, {}),
    ("serve.step", 400, 430, {"step": 2, "active": 1}),
    ("serve.gc", 420, 425, {"generation": 0}),
]
HAND_MODULES = [["jit_serve_insert(7)", 50, 6], ["jit_serve_insert(7)", 80,
                                                   4],
                ["jit_serve_decode(9)", 150, 40],
                ["jit__insert_impl(3)", 90, 100]]


@pytest.mark.parametrize("name,want", [
    # steps: 100-40, 200-100, 30 ns
    ("host_step_ms", (60 + 100 + 30) / 3 / 1e6),
    # admissions: 50-20, 30-5 ns
    ("admit_host_ms", (30 + 25) / 2 / 1e6),
    ("decode_batch", 5 / 3),
    # two inserts of 6 and 4 ns; the other names are not the insert
    ("insert_device_ms", 5 / 1e6)])
def test_reader_on_hand_made_trace(name, want):
    assert reader(name)(_hand_run(HAND_SPANS, HAND_MODULES)) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_silent_without_program_spans(name):
    """A program that opens no ``serve.`` span and names its insert
    otherwise (an older commit) gives every new reader nothing to read."""
    assert reader(name)(_hand_run([], HAND_MODULES[2:])) is None


def test_spans_clipped_to_windows_and_parsed_once(tmp_path, monkeypatch):
    spans = [("serve.step", 5, 20, {}), ("serve.step", 40, 60, {}),
             ("serve.step", 70, 80, {})]
    assert P.clip(spans, [(0, 10), (50, 75)]) == [
        ("serve.step", 5, 10, {}), ("serve.step", 50, 60, {}),
        ("serve.step", 70, 75, {})]
    calls = []
    monkeypatch.setattr(P, "read_xplane",
                        lambda path: calls.append(path) or spans)
    monkeypatch.setattr(P, "ROOT", str(tmp_path))
    logdir = tmp_path / harness.TRACE_DIR / "c" / "plugins" / "profile"
    logdir.mkdir(parents=True)
    (logdir / "x.xplane.pb").write_bytes(b"")
    run = types.SimpleNamespace(cell={"name": "c"}, windows=[(0, 100)])
    assert P.spans(run) == spans and P.spans(run) == spans
    assert len(calls) == 1
    # no trace of the cell at all: nothing to read
    empty = types.SimpleNamespace(cell={"name": "none"}, windows=[(0, 1)])
    assert P.spans(empty) == []
