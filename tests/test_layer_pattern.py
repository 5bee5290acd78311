"""A layer pattern of window and full attention, YaRN on the full layers and
an expert layer that holds some of the experts (one rank of expert
parallelism), at small widths on the CPU."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MELLUM2_12B, smoke_config
from repro.models import attention as attn_mod
from repro.models import model as model_mod
from repro.models import moe as moe_mod
from repro.models.layers import apply_rope, rope_frequencies
from repro.models.transformer import forward, model_init
from repro.serve import DenseServeModel


def _yarn_table(dim, base, factor, orig, beta_fast, beta_slow):
    """Inverse frequencies as Hugging Face's ``_compute_yarn_parameters``
    states them, in float64."""
    def correction_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    pos_freqs = base ** (np.arange(0, dim, 2) / dim)
    extra, inter = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1 - ramp
    return inter * (1 - keep) + extra * keep, low, high


def test_yarn_frequencies_follow_the_published_formula():
    """The full layers' frequencies at Mellum2's published settings: the
    correction range is dimensions 18 to 35 of 64, frequencies below it
    kept, above it divided by the factor 16, and a linear ramp between.
    float32 against float64: relative 1e-6, which the same table rounded
    to bfloat16 (relative 2^-9) does not meet."""
    y = MELLUM2_12B.full_rope_scaling
    want, low, high = _yarn_table(128, 500000.0, y.factor,
                                  y.original_max_position, y.beta_fast,
                                  y.beta_slow)
    assert (low, high) == (18, 35)
    got = np.asarray(rope_frequencies(128, 500000.0, y), np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    rounded = np.asarray(jnp.asarray(want, jnp.bfloat16), np.float64)
    assert not np.allclose(rounded, want, rtol=1e-6, atol=0)
    plain = np.asarray(rope_frequencies(128, 500000.0), np.float64)
    np.testing.assert_allclose(got[:low + 1], plain[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(got[high:], plain[high:] / 16, rtol=1e-6)
    assert y.attention_factor == pytest.approx(1.2772588722239782)


@pytest.mark.parametrize("yarn", [None, MELLUM2_12B.full_rope_scaling])
def test_rope_rotates_each_heads_halves(yarn):
    """The flattened rotation equals the rotate-half form per head, with
    cos and sin scaled by the attention factor under YaRN."""
    x = jax.random.normal(jax.random.key(0), (2, 5, 3, 16), jnp.float32)
    pos = jnp.array([[0, 3, 9, 100, 4000], [7, 8, 9, 10, 11]])
    freqs = rope_frequencies(16, 500000.0, yarn)
    ang = pos[..., None] * freqs
    scale = 1.0 if yarn is None else yarn.attention_factor
    cos, sin = (jnp.cos(ang) * scale)[..., None, :], \
        (jnp.sin(ang) * scale)[..., None, :]
    x1, x2 = x[..., :8], x[..., 8:]
    want = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    got = apply_rope(x, pos, 500000.0, yarn)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _moe_cfg(**kw):
    base = dict(num_experts=8, num_experts_per_tok=2, experts_held=4,
                d_model=16, d_ff=8, dtype="float32")
    base.update(kw)
    return smoke_config("mellum2-12b-a2.5b").replace(**base)


def _expert(p, e, x):
    g = x @ p["wg"][e]
    return (jax.nn.silu(g) * (x @ p["wu"][e])) @ p["wd"][e]


def test_held_experts_drop_nothing_when_every_token_routes_to_one():
    """Every token's two choices are held experts 0 and 1, 64 tokens on
    each, where a capacity of 1.25 times the even share would keep 20:
    the held layer keeps all 128 assignments (float32, tolerance of
    float32 sums)."""
    cfg = _moe_cfg()
    p, _ = moe_mod.moe_init(jax.random.key(1), cfg, 0)
    x = jnp.abs(jax.random.normal(jax.random.key(2), (4, 16, 16))) + 0.1
    router = jnp.zeros((16, 8)).at[:, 0].set(10.0).at[:, 1].set(5.0)
    p = dict(p, router=router)
    out, tokens = moe_mod.moe_apply_held(cfg, p, x)
    assert tokens.tolist() == [64, 64, 0, 0]
    xf = x.reshape(-1, 16)
    w = jax.nn.softmax(xf @ router, -1)[:, :2]
    w = w / w.sum(-1, keepdims=True)
    want = w[:, :1] * _expert(p, 0, xf) + w[:, 1:] * _expert(p, 1, xf)
    np.testing.assert_allclose(out.reshape(-1, 16), want, rtol=1e-5,
                               atol=1e-5)
    assert moe_mod.capacity(64, cfg) < 64


def test_eight_shares_sum_to_the_uncut_layer():
    """A 64-expert top-8 layer cut into eight ranks of 8 experts: rank r
    holds experts 8r..8r+7 (its router's columns rolled so that they come
    first) and the shares' outputs add up to the layer that holds all 64;
    every assignment lands on exactly one rank (float32)."""
    full = _moe_cfg(num_experts=64, num_experts_per_tok=8, experts_held=64,
                    d_model=32, d_ff=16)
    p, _ = moe_mod.moe_init(jax.random.key(3), full, 0)
    x = jax.random.normal(jax.random.key(4), (3, 8, 32))
    whole, whole_tokens = moe_mod.moe_apply_held(full, p, x)
    share = full.replace(experts_held=8)
    total, assigned = 0.0, 0
    for r in range(8):
        pr = {"router": jnp.roll(p["router"], -8 * r, axis=1),
              **{k: p[k][8 * r:8 * r + 8] for k in ("wg", "wu", "wd")}}
        out, tokens = moe_mod.moe_apply_held(share, pr, x)
        total = total + out
        assigned += int(tokens.sum())
        assert tokens.tolist() == whole_tokens[8 * r:8 * r + 8].tolist()
    assert assigned == 3 * 8 * 8
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)


def _pattern_model(window=8, max_len=64):
    cfg = smoke_config("mellum2-12b-a2.5b").replace(
        window_size=window, experts_held=2, dtype="float32")
    params = model_init(cfg, jax.random.key(5))[0]
    return cfg, params, DenseServeModel(cfg, params, max_len)


def test_ring_is_built_from_the_true_prompt_length():
    """A prompt of 11 tokens, prefilled in its bucket of 16 with a window
    of 8: each window layer's ring holds the K/V of positions 3..10, each
    at p % 8, as an unpadded prefill of the 11 tokens gives them; the full
    layer holds positions 0..10 from the start. Built from the bucket, the
    ring would hold the padding's positions 8..15."""
    cfg, params, model = _pattern_model()
    tokens = np.arange(1, 12)
    _, row = model.prefill(tokens)
    _, exact = model_mod.serve_prefill(cfg, params,
                                       {"tokens": jnp.asarray(tokens[None])},
                                       max_len=64)
    assert [c["k"].shape[2] for c in row["attn"]] == [8, 8, 8, 64]
    for j, kind in enumerate(cfg.attention_period):
        got, want = row["attn"][j], exact["attn"][j]
        held = slice(None) if kind == "sliding_window" else slice(0, 11)
        for name in ("k", "v"):
            np.testing.assert_allclose(got[name][:, :, held],
                                       want[name][:, :, held],
                                       rtol=1e-5, atol=1e-5)
    # entry p % 8 of a window ring holds position p, for p in 3..10
    out = model_mod.transformer.forward(cfg, params,
                                        jnp.asarray(tokens[None]),
                                        mode="prefill")
    ring = row["attn"][0]["k"][:, 0]
    for p in range(3, 11):
        np.testing.assert_allclose(ring[:, p % 8],
                                   out["cache"][0]["k"][:, 0, p],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("head_dim", [32, 128])
def test_lockstep_decode_matches_forward(head_dim):
    """Prefill 12 tokens, then decode 28 in lockstep (one position for the
    batch, the ``generate`` path), wrapping each window ring of 8 three
    times, against the full forward of the 40 tokens. Heads of 128 lanes
    hold the K/V cache heads-major, (B, HKV, S, D); heads of 32
    positions-major. float32 throughout, all experts held (nothing routed
    elsewhere), so only the order of float32 sums differs: 1e-4."""
    cfg = smoke_config("mellum2-12b-a2.5b").replace(
        window_size=8, head_dim=head_dim, experts_held=4, dtype="float32")
    assert attn_mod.heads_major(cfg) == (head_dim == 128)
    params = model_init(cfg, jax.random.key(7))[0]
    toks = jax.random.randint(jax.random.key(8), (2, 40), 0, cfg.vocab_size)
    full = forward(cfg, params, toks)["logits"]
    logits, cache = model_mod.serve_prefill(cfg, params,
                                            {"tokens": toks[:, :12]},
                                            max_len=64)
    axis = 1 + attn_mod.kv_pos_axis(cfg)
    assert [c["k"].shape[axis] for c in cache["attn"]] == [8, 8, 8, 64]
    np.testing.assert_allclose(logits[:, 0], full[:, 11], rtol=1e-4,
                               atol=1e-4)
    for t in range(12, 40):
        logits, cache = model_mod.serve_step(cfg, params, cache,
                                             toks[:, t:t + 1])
        np.testing.assert_allclose(logits[:, 0], full[:, t], rtol=1e-4,
                                   atol=1e-4)
