"""Plain float32 reference of GPT-2 (Radford et al. 2019), as the benchmark's
configurations state it: learned positions, pre-LayerNorm blocks, causal
multi-head attention without projection biases, a tanh-GELU MLP with
biases, a final LayerNorm and an LM head tied to the token embedding.

It reads the benchmark's plain weight tree (``bench/weights.py``), whose
layers may have fewer heads or a narrower MLP than the dense model, or none.
It imports nothing of the program. Matrix products run at ``highest``
precision, so that on a TPU they are float32 products.

``quant="fp8"`` is the control: every matrix product's operands are rounded
to float8 (e4m3) first, the nearest precision below the configuration's
bfloat16 compute.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def _round(x, quant):
    if quant is None:
        return x
    if quant == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f"unknown precision {quant!r}")


def _mm(a, b, quant):
    return jnp.matmul(_round(a, quant), _round(b, quant))


def _ln(x, n, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * n["g"] + n["b"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def forward(w, tokens, *, head_dim: int, eps: float, quant=None):
    """Logits (T, vocab) of one token sequence (T,), causal."""
    t = tokens.shape[0]
    x = w["embed"][tokens] + w["pos"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for lp in w["layers"]:
        if "wq" in lp:
            h = _ln(x, lp["ln1"], eps)
            heads = lp["wq"].shape[1] // head_dim
            q, k, v = (_mm(h, lp[n], quant).reshape(t, heads, head_dim)
                       for n in ("wq", "wk", "wv"))
            s = jnp.einsum("qhd,khd->hqk", _round(q, quant),
                           _round(k, quant)) / math.sqrt(head_dim)
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            o = jnp.einsum("hqk,khd->qhd", _round(p, quant), _round(v, quant))
            x = x + _mm(o.reshape(t, heads * head_dim), lp["wo"], quant)
        if "wi" in lp:
            h = _ln(x, lp["ln2"], eps)
            a = _gelu(_mm(h, lp["wi"], quant) + lp["bi"])
            x = x + _mm(a, lp["wd"], quant) + lp["bd"]
    x = _ln(x, w["lnf"], eps)
    return _mm(x, w["embed"].T, quant)


def make_gaps(cfg: Dict, control: bool = False):
    """A jitted ``(w, tokens, served, score) -> gaps`` over one padded
    sequence, for the configuration's plain dict ``cfg`` (its ``head_dim``
    and ``layer_norm_epsilon``): at each position where ``score`` holds,
    the reference's best logit minus its logit of ``served`` (the token the
    program produced from that position). With ``control`` it also returns
    the same gap for the token that the fp8 control puts first."""
    head_dim, eps = int(cfg["head_dim"]), float(cfg["layer_norm_epsilon"])

    def gaps(w, tokens, served, score):
        with jax.default_matmul_precision("highest"):
            ref = forward(w, tokens, head_dim=head_dim, eps=eps)
            best = ref.max(-1)
            got = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
            out = jnp.where(score, best - got, 0.0)
            if not control:
                return out
            low = forward(w, tokens, head_dim=head_dim, eps=eps, quant="fp8")
            pick = jnp.argmax(low, -1)
            lowgot = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            return out, jnp.where(score, best - lowgot, 0.0)

    return jax.jit(gaps)
