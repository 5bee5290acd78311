"""Primitive layers: norms, rotary embeddings, initializers.

Parameters are plain pytrees (nested dicts of jnp arrays). Every init
function returns ``(params, specs)`` where ``specs`` mirrors ``params`` with
tuples of *logical axis names* per dimension; ``repro.distributed.sharding``
maps logical names to mesh axes.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Logical axis vocabulary:
#   "layers"  — stacked-layer leading dim (never sharded)
#   "embed"   — d_model dim (FSDP-sharded over data axes)
#   "heads"   — fused q-head output dim (TP over "model")
#   "kv"      — fused kv-head output dim (TP over "model" if divisible)
#   "mlp"     — d_ff dim (TP over "model")
#   "experts" — expert dim (EP over "model")
#   "vocab"   — vocabulary dim (TP over "model")
#   "ssm"     — ssm inner dim (TP over "model")
#   None      — replicated


def compute_dtype(cfg) -> jnp.dtype:
    """Activation/compute dtype for ``cfg``.

    ``cfg.dtype`` is either a plain dtype name ("float32", "bfloat16", ...)
    or ``"mixed_<dtype>"`` — fp32 master params with ``<dtype>`` compute.
    Every batch factory and activation cast must go through this helper;
    ``jnp.dtype(cfg.dtype)`` directly chokes on the mixed spelling.
    """
    d = cfg.dtype
    if isinstance(d, str) and d.startswith("mixed_"):
        d = d[len("mixed_"):]
    return jnp.dtype(d)


def dense_init(key, shape, in_axis: int = -2, dtype=jnp.float32):
    """Truncated-normal fan-in init, stored fp32 then cast at use."""
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def norm_init(cfg, nlayers: Optional[int] = None, dim: Optional[int] = None):
    d = dim if dim is not None else cfg.d_model
    shape = (nlayers, d) if nlayers else (d,)
    spec_prefix = ("layers",) if nlayers else ()
    p = {"scale": jnp.ones(shape, jnp.float32)}
    s = {"scale": spec_prefix + (None,)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros(shape, jnp.float32)
        s["bias"] = spec_prefix + (None,)
    return p, s


def apply_norm(cfg, p, x, out_dtype=None):
    out_dtype = out_dtype or x.dtype
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"] + p["bias"]
    else:  # rmsnorm
        y = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.norm_eps)
        y = y * p["scale"]
    return y.astype(out_dtype)


# ----------------------------------------------------------------------
# Rotary position embeddings
# ----------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float, yarn=None) -> jnp.ndarray:
    """Inverse frequencies of the rotary pairs; with ``yarn`` (a
    ``configs.Yarn``) those of YaRN, as Hugging Face's
    ``_compute_yarn_parameters`` states them: interpolated by the factor
    below the correction range that ``beta_fast``/``beta_slow`` give over
    the original context (floor and ceil of the correction dimensions),
    kept above it, and ramped linearly between."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    extrapolated = 1.0 / (theta ** exponent)
    if yarn is None:
        return extrapolated
    interpolated = 1.0 / (yarn.factor * theta ** exponent)

    def correction_dim(rotations):
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp       # share of the extrapolated frequency
    return interpolated * (1.0 - keep) + extrapolated * keep


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               yarn=None) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq). With ``yarn``
    the frequencies are YaRN's and cos and sin are scaled by its
    ``attention_factor``.

    Computed over the heads flattened, (..., seq, heads * head_dim), each
    head's halves swapped by two lane rotations: elementwise work on a 4-d
    (heads, head_dim) view lets the TPU compiler give the projection that
    feeds it another layout, and then relayout its weights every step."""
    *lead, heads, head_dim = x.shape
    half = head_dim // 2
    freqs = rope_frequencies(head_dim, theta, yarn)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., s, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    # (..., s, heads * head_dim): each head [c, c] and [s, s]
    cos = jnp.tile(jnp.concatenate([cos, cos], -1), heads)
    sin = jnp.tile(jnp.concatenate([sin, sin], -1), heads)
    flat = x.reshape(*lead, heads * head_dim).astype(jnp.float32)
    # rotate_half per head: [-x2, x1]
    first = (jnp.arange(heads * head_dim) % head_dim) < half
    rot = jnp.where(first, -jnp.roll(flat, -half, axis=-1),
                    jnp.roll(flat, half, axis=-1))
    out = flat * cos + rot * sin
    return out.reshape(x.shape).astype(x.dtype)


def layer_rope(cfg, kind: str):
    """(theta, yarn) of a layer of attention kind ``kind``."""
    return cfg.rope_theta, (cfg.full_rope_scaling if kind == "full"
                            else None)


# ----------------------------------------------------------------------
# Embeddings
# ----------------------------------------------------------------------

def embedding_init(key, cfg):
    p = {"table": dense_init(key, (cfg.vocab_size, cfg.d_model), in_axis=-1)}
    s = {"table": ("vocab", "embed")}
    if cfg.pos_emb == "learned":
        p["pos"] = dense_init(jax.random.fold_in(key, 1),
                              (cfg.max_position, cfg.d_model), in_axis=-1)
        s["pos"] = (None, "embed")
    return p, s


def embed_tokens(cfg, p, tokens, positions=None):
    x = jnp.take(p["table"], tokens, axis=0).astype(compute_dtype(cfg))
    if cfg.pos_emb == "learned":
        if positions is None:
            positions = jnp.arange(tokens.shape[-1])
        x = x + jnp.take(p["pos"], positions, axis=0).astype(x.dtype)
    return x


def unembed(cfg, emb_p, head_p, x):
    """Project hidden states back to vocabulary logits (fp32)."""
    if cfg.tie_embeddings:
        w = emb_p["table"]
    else:
        w = head_p["w"]
    return jnp.einsum("...d,vd->...v", x, w.astype(x.dtype)
                      ).astype(jnp.float32)


def lm_head_init(key, cfg):
    if cfg.tie_embeddings:
        return {}, {}
    return ({"w": dense_init(key, (cfg.vocab_size, cfg.d_model), in_axis=-1)},
            {"w": ("vocab", "embed")})
