"""The Mellum2 configuration's files at small widths on the CPU: the program
(prefill, then decode through the slot cache, with window rings) against
the plain reference's full forward, the counts of a decode step, the
engine's routing counter, and the serving job run traced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import program_spans as P
import trace_reduce as T
from conftest import BENCH, tiny_ctx

family = harness.load_module(BENCH, "families", "mellum2")
reference = harness.load_module(BENCH, "references", "mellum2")
counts = harness.load_module(BENCH, "counts", "mellum2")

# one period of the published pattern at small widths: window 8, 16
# experts of which 4 are held, 4 per token
TINY = {"name": "mellum2-tiny", "family": "mellum2", "reference": "mellum2",
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 4,
        "num_experts_published": 16, "num_experts_per_tok": 4,
        "num_hidden_layers": 4,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "mlp_layer_types": ["sparse"] * 4, "sliding_window": 8,
        "vocab_size": 256, "rms_norm_eps": 1e-6, "hidden_act": "silu",
        "norm_topk_prob": True, "attention_bias": False,
        "tie_word_embeddings": False, "max_position_embeddings": 131072,
        "rope_parameters": {
            "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                               "factor": 16,
                               "original_max_position_embeddings": 8192,
                               "beta_fast": 32, "beta_slow": 1,
                               "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "compute_dtype": "bfloat16", "source": "test",
        "check": {"max_logit_gap": 0.1}}
MIX = {"job": "serve_job", "kind": "offline_backlog", "slots": 4,
       "max_len": 64, "requests_per_stream": 10,
       "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 5,
                  "max": 40},
       "output": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4,
                  "max": 24},
       "sampling": "greedy", "check_tokens": 200}

# Median over positions of the largest |logit difference| to the float32
# reference. The program computes in bfloat16 (operands rounded to 2^-9
# relative): 0.017-0.034 on these seeds, with logits of std about 0.9. A
# token whose router choice flips under that rounding moves a few
# positions by more, which is why the median and not the maximum is held.
# The fp8 control (operands at 2^-4) reads 0.27-0.40.
MEDIAN_TOL = 0.1


def _served_logits(cfg, seed, prompt_len, steps, slot=1):
    """The program's logits at each position from the prompt's last on,
    decoding the given tokens in slot ``slot`` of two, and the tokens."""
    model, plain = family.serve_model(cfg, seed, 64)
    toks = np.random.default_rng(seed).integers(0, cfg["vocab_size"],
                                                prompt_len + steps)
    cache = model.init_slots(2)
    logits, row = model.prefill(toks[:prompt_len])
    cache = model.insert(cache, row, slot, prompt_len)
    out = [np.asarray(logits)[0, 0]]
    for t in range(steps - 1):
        feed = np.zeros((2, 1), np.int32)
        feed[slot, 0] = toks[prompt_len + t]
        logits, cache = model.step(cache, jnp.asarray(feed))
        out.append(np.asarray(logits)[slot, 0])
    return np.stack(out), toks[:prompt_len + steps - 1], plain()


def _reference(cfg, w, toks, quant=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda w, t: reference.forward(w, t, cfg, quant=quant))(
                w, jnp.asarray(toks)))


@pytest.mark.parametrize("prompt_len,seed,head_dim", [
    (5, 2, 16),     # shorter than the window, in a bucket of 8
    (11, 3, 16),    # longer than the window, shorter than its bucket of 16
    (16, 5, 16),    # longer than the window, filling its bucket
    (11, 3, 128),   # heads of 128 lanes: the K/V cache held heads-major
])
def test_prefill_and_decode_follow_the_reference(prompt_len, seed, head_dim):
    """40 decode steps wrap each window ring of 8 five times; every
    position's logits stay within ``MEDIAN_TOL`` of the reference's, and
    the fp8 control's do not."""
    cfg = dict(TINY, head_dim=head_dim)
    prog, toks, w = _served_logits(cfg, seed, prompt_len, 40)
    ref = _reference(cfg, w, toks)[prompt_len - 1:]
    diff = np.abs(prog - ref).max(-1)
    assert np.median(diff) < MEDIAN_TOL, np.median(diff)
    low = _reference(cfg, w, toks, quant="fp8")[prompt_len - 1:]
    assert np.median(np.abs(low - ref).max(-1)) > MEDIAN_TOL


def test_decode_steps_charge_window_keys():
    """Two steps of three slots (one empty) against hand counts: a full
    layer reads p + 1 keys, a window layer min(p + 1, 8)."""
    pos = np.array([[3, 20, -1], [4, 21, 9]])
    flops, nbytes = counts.decode_steps(TINY, pos)
    d, hq, hkv, V = 64, 64, 32, 256
    full = np.array([4 + 21, 5 + 22 + 10])
    window = np.array([4 + 8, 5 + 8 + 8])
    n = np.array([2, 3])
    keys = full + 3 * window
    per_tok = 4 * 2 * (2 * d * hq + 2 * d * hkv + d * 16 + 1.0 * 3 * d * 32)
    np.testing.assert_allclose(flops, n * (per_tok + 2 * d * V)
                               + 4 * hq * keys)
    weights = (4 * ((2 * d * hq + 2 * d * hkv + d * 16 + 4 * 3 * d * 32) * 2
                    + 2 * d * 4) + d * 4 + V * d * 2)
    np.testing.assert_array_equal(
        nbytes, weights + n * d * 2 + 2 * hkv * 2 * (keys + 4 * n))
    # a prompt of 10: full layers attend 1..10 keys, window ones min(i, 8)
    s = 10
    want = (s * per_tok + 4 * hq * (55 + 3 * (36 + 8 + 8)) + 2 * d * V)
    assert counts.prefill_flops(TINY, s) == pytest.approx(want)


def test_routing_counter_counts_every_assignment_of_active_slots():
    """With every expert held, each active slot's token makes top-k held
    assignments in every layer: the counter of a run is active tokens
    times k times layers, copied once after the drain; a second run
    counts from zero."""
    from repro.serve import ServeEngine
    from repro.serve.workload import Request
    cfg = dict(TINY, num_experts=16)
    model, _ = family.serve_model(cfg, 7, 64)
    engine = ServeEngine(model, num_slots=3)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, tokens=rng.integers(0, 256, 6 + i), steps=3 + 2 * i,
                    arrival=0.0) for i in range(5)]
    for _ in range(2):
        report = engine.run(reqs)
        active = sum(r.steps - 1 for r in report.records)
        assert engine.route["assignments"] == active * 4 * 4
        assert 0 < engine.route["experts_hit"] <= 16 * 4 * report.steps
        assert 1 <= engine.route["max_expert_tokens"] <= 3


def test_serve_job_runs_the_configuration_traced(tmp_path):
    """The configuration through ``serve_job.run`` with a profiler trace:
    correct, with one ``serve.route`` span a stream, whose held
    assignments are at most every active token's top-k in every layer."""
    ctx = tiny_ctx(cfg=TINY, mix=MIX)
    ctx.trace = True
    ctx.trace_dir = str(tmp_path / "trace")
    ctx.peaks_dev = harness.load_peaks(BENCH)["devices"]["TPU v5 lite"]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    ctx.profiler = lambda: jax.profiler.trace(ctx.trace_dir,
                                              profiler_options=opts)
    result, checks = ctx.job.run(ctx)
    assert result["correct"], checks
    run = result["run"]
    spans = P.clip(P.read_xplane(T.latest_xplane(ctx.trace_dir)),
                   run["windows"])
    route = [st for n, _, _, st in spans if n == "serve.route"]
    assert len(route) == result["notes"]["streams"]
    tokens = sum(int((p >= 0).sum()) for p in run["positions"])
    assert 0 < sum(st["assignments"] for st in route) <= tokens * 4 * 4


def test_the_configuration_file_keeps_the_catalog_numbers():
    """The benchmark's file states the published config, the experts held
    here apart, and the program reads it at the published widths."""
    cfg = harness.load_json(BENCH, "configs", "mellum2-12b-a2.5b-ep8")
    assert cfg["num_experts"] == 8 and cfg["num_experts_published"] == 64
    p = family.program_config(cfg)
    assert (p.num_layers, p.d_model, p.num_heads, p.num_kv_heads,
            p.resolved_head_dim, p.d_ff, p.vocab_size, p.window_size) == \
        (28, 2304, 32, 4, 128, 896, 98304, 1024)
    assert p.attention_period == ("sliding_window",) * 3 + ("full",)
    assert (p.num_experts, p.num_experts_per_tok, p.experts_held) == (64, 8, 8)
    assert not p.tie_embeddings and p.norm_eps == 1e-6
