"""A configuration, a traffic mix and a per-layer metric added as files,
with manifest entries, are found by name; so is a configuration of a new
architecture, whose family, reference and counts are files of its own
names that get its dict whole: no file of the harness changes."""
import json
import os

import numpy as np

import harness
import traffic
from conftest import BENCH, TINY_MIX, tiny_ctx


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_new_files_are_found_by_name(tmp_path):
    bench = str(tmp_path)
    _write(os.path.join(bench, "configs", "new-model.json"),
           json.dumps({"name": "new-model", "family": "gpt2", "n_layer": 1}))
    mix = {"job": "serve_job", "kind": "offline_backlog", "slots": 2,
           "max_len": 64, "requests_per_stream": 5, "sampling": "greedy",
           "prompt": {"dist": "lognormal", "median": 9, "sigma": 0.3,
                      "min": 4, "max": 20},
           "output": {"dist": "lognormal", "median": 6, "sigma": 0.3,
                      "min": 2, "max": 30}}
    _write(os.path.join(bench, "traffic", "new-mix.json"), json.dumps(mix))
    _write(os.path.join(bench, "metrics", "queue_depth.serve.py"),
           "def read(run):\n    return run.depth * 2\n")
    _write(os.path.join(bench, "metrics", "silent_share.py"),
           "def read(run):\n    return None\n")

    cfg = harness.load_json(bench, "configs", "new-model")
    assert cfg["name"] == "new-model"
    loaded = harness.load_json(bench, "traffic", "new-mix")
    reqs = traffic.stream(loaded, 100, 7, 0)
    assert len(reqs) == 5 and all(r.prompt_len + r.steps <= 64
                                  for r in reqs)

    manifest = {
        "workloads": [{"name": "new-model.new-mix", "config": "new-model",
                       "traffic": "new-mix", "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "queue_depth.serve", "unit": "1",
                       "moves": "tokens_per_s"},
                      {"name": "silent_share", "unit": "%",
                       "moves": "tokens_per_s"},
                      {"name": "elsewhere", "unit": "%",
                       "moves": "tokens_per_s", "workloads": ["other"]}]}
    cell = harness.cell_of(manifest, "new-model.new-mix")
    assert cell["traffic"] == "new-mix"
    metrics = harness.per_layer_of(manifest, cell["name"])
    assert [m["name"] for m in metrics] == ["queue_depth.serve",
                                            "silent_share"]

    class Run:
        depth = 21

    got = harness.read_metrics(bench, metrics, Run())
    assert got == {"queue_depth.serve": {"value": 42.0, "unit": "1"}}


def test_missing_files_are_refused(tmp_path):
    import pytest
    with pytest.raises(harness.Refused):
        harness.load_json(str(tmp_path), "configs", "absent")
    with pytest.raises(harness.Refused):
        harness.load_module(str(tmp_path), "metrics", "absent")
    with pytest.raises(harness.Refused):
        harness.cell_of({"workloads": []}, "absent")


def test_the_manifest_names_existing_files():
    from conftest import ROOT
    manifest = harness.load_manifest(ROOT)
    for c in manifest["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for cell in manifest["workloads"]:
        cfg = harness.load_json(BENCH, "configs", cell["config"])
        harness.load_json(BENCH, "traffic", cell["traffic"])
        harness.load_module(BENCH, "families", cfg["family"])
        harness.load_module(BENCH, "references", cfg["reference"])
        harness.load_module(BENCH, "counts", cfg["reference"])
        for m in harness.per_layer_of(manifest, cell["name"]):
            harness.load_module(BENCH, "metrics", m["name"])


# A second architecture, stated under keys of its own. Its family, reference
# and counts wrap GPT-2's under new names, translating the keys, and log
# what they are handed; nothing of it lies under the benchmark's directory.
TOY = {"name": "toy-tiny", "family": "toy", "reference": "toy", "depth": 2,
       "width": 64, "heads": 4, "head_width": 16, "ffn": 128,
       "context": 64, "vocab_size": 256, "norm_eps": 1e-5,
       "compute_dtype": "bfloat16", "check": {"max_logit_gap": 0.1}}

TOY_HEAD = """import harness

BENCH = {bench!r}
CALLS = []


def gpt2_cfg(cfg):
    return {{"name": cfg["name"], "n_layer": cfg["depth"],
            "n_embd": cfg["width"], "n_head": cfg["heads"],
            "head_dim": cfg["head_width"], "n_inner": cfg["ffn"],
            "n_positions": cfg["context"], "vocab_size": cfg["vocab_size"],
            "layer_norm_epsilon": cfg["norm_eps"],
            "compute_dtype": cfg["compute_dtype"]}}
"""

TOY_FAMILY = """
_gpt2 = harness.load_module(BENCH, "families", "gpt2")
ADMITTED = []      # [decode steps before, prompt length, slot]


class Witness:
    \"\"\"GPT-2's adapter, logging each admission as the engine makes it.\"\"\"

    def __init__(self, model):
        self.model, self.max_len, self.steps = model, model.max_len, 0

    def init_slots(self, n):
        return self.model.init_slots(n)

    def prefill(self, tokens):
        ADMITTED.append([self.steps, int(tokens.shape[0]), None])
        return self.model.prefill(tokens)

    def insert(self, cache, row, slot, pos):
        ADMITTED[-1][2] = int(slot)
        return self.model.insert(cache, row, slot, pos)

    def step(self, cache, tokens):
        self.steps += 1
        return self.model.step(cache, tokens)


def serve_model(cfg, seed, max_len):
    CALLS.append(cfg)
    model, plain = _gpt2.serve_model(gpt2_cfg(cfg), seed, max_len)
    return Witness(model), plain
"""

TOY_REFERENCE = """
_gpt2 = harness.load_module(BENCH, "references", "gpt2")


def make_gaps(cfg, control=False):
    CALLS.append((cfg, control))
    return _gpt2.make_gaps(gpt2_cfg(cfg), control=control)
"""

TOY_COUNTS = """
_gpt2 = harness.load_module(BENCH, "counts", "gpt2")


def decode_steps(cfg, positions):
    CALLS.append((cfg, positions))
    return _gpt2.decode_steps(gpt2_cfg(cfg), positions)


def prefill_flops(cfg, s):
    return _gpt2.prefill_flops(gpt2_cfg(cfg), s)
"""


def test_a_new_architecture_joins_by_files_alone(tmp_path):
    import jax
    bench = str(tmp_path / "bench")
    head = TOY_HEAD.format(bench=BENCH)
    _write(os.path.join(bench, "configs", "toy-tiny.json"), json.dumps(TOY))
    _write(os.path.join(bench, "families", "toy.py"), head + TOY_FAMILY)
    _write(os.path.join(bench, "references", "toy.py"), head + TOY_REFERENCE)
    _write(os.path.join(bench, "counts", "toy.py"), head + TOY_COUNTS)

    ctx = tiny_ctx(cfg=harness.load_json(bench, "configs", "toy-tiny"),
                   bench=bench)
    ctx.trace = True
    ctx.trace_dir = str(tmp_path / "trace")
    ctx.peaks_dev = harness.load_peaks(BENCH)["devices"]["TPU v5 lite"]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    ctx.profiler = lambda: jax.profiler.trace(ctx.trace_dir,
                                              profiler_options=opts)
    result, checks = ctx.job.run(ctx)

    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    # each module got the configuration's dict itself
    assert ctx.family.CALLS == [ctx.cfg]
    assert len(ctx.reference.CALLS) == 1
    assert ctx.reference.CALLS[0][0] is ctx.cfg
    assert ctx.reference.CALLS[0][1] is False
    run = result["run"]
    assert run["counts"] is ctx.counts
    streams = len(run["positions"])
    assert streams == result["notes"]["streams"] >= 1
    assert [c[1] is p for c, p in zip(ctx.counts.CALLS, run["positions"])] \
        == [True] * streams
    assert all(c[0] is ctx.cfg for c in ctx.counts.CALLS)

    # each request's rows, against its admission as the adapter saw it:
    # the window's streams made the last admissions
    per = TINY_MIX["requests_per_stream"]
    admitted = ctx.family.ADMITTED[-streams * per:]
    for i, positions in enumerate(run["positions"]):
        reqs = traffic.stream(TINY_MIX, TOY["vocab_size"], ctx.seed, i)
        adm = admitted[i * per:(i + 1) * per]
        assert positions.shape == (result["notes"]["decode_steps"][i],
                                   TINY_MIX["slots"])
        assert run["prompts"][i].tolist() == [r.prompt_len for r in reqs]
        start = adm[0][0]
        want = np.full(positions.shape, -1)
        for (a, s, c), r in zip(adm, reqs):
            assert s == r.prompt_len
            k = r.steps - 1
            want[a - start:a - start + k, c] = s + np.arange(k)
        assert (positions == want).all()
