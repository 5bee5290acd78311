"""Plain float32 reference of a Mellum2 decoder (``model_type`` ``mellum``),
as the benchmark's configuration states it, for one rank of expert
parallelism.

Per layer: RMSNorm, grouped-query attention with rotary positions (plain
RoPE on ``sliding_attention`` layers, YaRN with its attention factor on
``full_attention`` layers, both from ``rope_parameters``) under a causal
mask, and on window layers also a window (the key at k is visible from the
query at q iff q - W < k <= q); the residual; RMSNorm; a router over all
``num_experts_published`` experts (softmax, top-k, renormalised); the held
experts (``num_experts`` of them, 0 .. num_experts - 1), each a SwiGLU MLP
computed on every token and weighted by the token's routing weight for it
(0 where the token did not choose it); the residual. Then the final
RMSNorm and the untied LM head.

It reads the benchmark's weights (``bench/families/mellum2.plain_tree``:
per position of the ``layer_types`` period, that position's layers
stacked), bfloat16 arrays, upcast to float32 one layer at a time, and
imports nothing of the program. Matrix products run at ``highest`` precision, so
that on a TPU they are float32 products. Queries are taken in blocks, so
that a 4,096-position replay fits beside the weights.

``quant="fp8"`` is the control: every matrix product's operands are rounded
to float8 (e4m3) first, the nearest precision below the configuration's
bfloat16.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


def _round(x, quant):
    if quant is None:
        return x
    if quant == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f"unknown precision {quant!r}")


def _mm(a, b, quant):
    return jnp.matmul(_round(a, quant), _round(b, quant))


def _rms(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rope_table(rope: Dict, head_dim: int):
    """(inverse frequencies, cos/sin factor) of one ``rope_parameters``
    entry, in float64 on the host: plain RoPE, or YaRN as the published
    formula gives it (Hugging Face ``_compute_yarn_parameters``)."""
    theta = float(rope["rope_theta"])
    base = theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    inv = 1.0 / base
    if rope["rope_type"] == "default":
        return inv, 1.0
    assert rope["rope_type"] == "yarn", rope
    factor, orig = float(rope["factor"]), rope["original_max_position_embeddings"]

    def dim(rotations):
        return (head_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    extrapolated = 1.0 - ramp
    inv = inv / factor * (1.0 - extrapolated) + inv * extrapolated
    return inv, float(rope["attention_factor"])


def _rotate(x, pos, table):
    """x (T, heads, head_dim) at positions ``pos`` (T,)."""
    inv, scale = table
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, window: int, quant):
    """Causal (and windowed, if ``window``) grouped attention, queries in
    blocks: q (T, Hq, D), k and v (T, Hkv, D)."""
    t, hq, dh = q.shape
    hkv = k.shape[1]
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    qb = q.reshape(t // blk, blk, hkv, hq // hkv, dh)
    kpos = jnp.arange(t)

    def block(args):
        qi, start = args
        qpos = start + jnp.arange(blk)
        s = jnp.einsum("qhgd,khd->hgqk", _round(qi, quant),
                       _round(k, quant)) / math.sqrt(dh)
        seen = kpos[None, :] <= qpos[:, None]
        if window:
            seen &= kpos[None, :] > qpos[:, None] - window
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", _round(p, quant), _round(v, quant))

    out = jax.lax.map(block, (qb, jnp.arange(t // blk) * blk))
    return out.reshape(t, hq * dh)


def _experts(h, lp, cfg, quant):
    """The held experts' part of the MoE output of h (T, d)."""
    k, held = cfg["num_experts_per_tok"], cfg["num_experts"]
    probs = jax.nn.softmax(_mm(h, lp["router"], quant), axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    topw = topw / topw.sum(-1, keepdims=True)
    # (T, held): the token's weight for each held expert, 0 if not chosen
    weight = (jax.nn.one_hot(topi, held) * topw[..., None]).sum(1)

    def expert(wg, wu, wd):
        return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd,
                   quant)

    y = jax.vmap(expert)(lp["wg"], lp["wu"], lp["wd"])       # (held, T, d)
    return jnp.einsum("te,etd->td", weight, y)


def forward(w, tokens, cfg: Dict, quant=None):
    """Logits (T, vocab) of one token sequence (T,), causal."""
    t = tokens.shape[0]
    dh, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    types = list(cfg["layer_types"])
    n = next(p for p in range(1, len(types) + 1)
             if types[:p] * (len(types) // p) == types)
    tables = {kind: rope_table(cfg["rope_parameters"][kind], dh)
              for kind in set(types)}
    pos = jnp.arange(t)

    def layer(x, lp, kind):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        h = _rms(x, lp["ln1"], eps)
        q = _mm(h, lp["wq"], quant).reshape(t, hq, dh)
        k = _mm(h, lp["wk"], quant).reshape(t, hkv, dh)
        v = _mm(h, lp["wv"], quant).reshape(t, hkv, dh)
        q, k = _rotate(q, pos, tables[kind]), _rotate(k, pos, tables[kind])
        window = cfg["sliding_window"] if kind == "sliding_attention" else 0
        x = x + _mm(_attend(q, k, v, window, quant), lp["wo"], quant)
        return x + _experts(_rms(x, lp["ln2"], eps), lp, cfg, quant)

    def period(x, lps):
        for j in range(n):
            x = layer(x, lps[j], types[j])
        return x, None

    x = w["embed"][tokens].astype(jnp.float32)
    # layer j of each period: w["layers"][j], stacked over the periods
    x, _ = jax.lax.scan(period, x, list(w["layers"]))
    x = _rms(x, w["norm"].astype(jnp.float32), eps)
    return _mm(x, w["head"].astype(jnp.float32).T, quant)


def make_gaps(cfg: Dict, control: bool = False):
    """A jitted ``(w, tokens, served, score) -> gaps`` over one padded
    sequence, for the configuration's plain dict ``cfg``: at each position
    where ``score`` holds, the reference's best logit minus its logit of
    ``served`` (the token the program produced from that position). With
    ``control`` it also returns the same gap for the token that the fp8
    control puts first."""

    def gaps(w, tokens, served, score):
        with jax.default_matmul_precision("highest"):
            ref = forward(w, tokens, cfg)
            best = ref.max(-1)
            got = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
            out = jnp.where(score, best - got, 0.0)
            if not control:
                return out
            low = forward(w, tokens, cfg, quant="fp8")
            pick = jnp.argmax(low, -1)
            lowgot = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            return out, jnp.where(score, best - lowgot, 0.0)

    return jax.jit(gaps)
