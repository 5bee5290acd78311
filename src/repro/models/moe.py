"""Mixture-of-Experts FFN: token-choice top-k routing with capacity-based
sort dispatch (fixed shapes, SPMD-friendly; experts shard over "model").

Dispatch is the sorted-scatter formulation: (token, expert) assignments are
sorted by expert id, each expert keeps its first `capacity` tokens, expert
FFNs run as dense batched einsums over (E, C, d), and outputs scatter-add
back with routing weights. FLOPs scale with top_k (not num_experts), unlike
the dense-dispatch einsum formulation.

:func:`moe_apply_held` is the layer of one rank of expert parallelism: it
holds experts ``0 .. cfg.experts_held - 1``, routes every token over all
``cfg.num_experts``, and computes its own experts' part of the result for
the tokens routed to them, as grouped matrix products over the held
experts (``jax.lax.ragged_dot``). It has no capacity and drops nothing.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..distributed.activation import constrain_batch
from .layers import dense_init

CAPACITY_FACTOR = 1.25


def moe_init(key, cfg, nlayers: int):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    held = cfg.experts_held or e
    pfx = (nlayers,) if nlayers else ()
    spfx = ("layers",) if nlayers else ()
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], pfx + (d, e)),
        "wg": dense_init(ks[1], pfx + (held, d, f)),
        "wu": dense_init(ks[2], pfx + (held, d, f)),
        "wd": dense_init(ks[3], pfx + (held, f, d)),
    }
    s = {
        "router": spfx + ("embed", None),
        "wg": spfx + ("experts", "embed", "mlp_noshard"),
        "wu": spfx + ("experts", "embed", "mlp_noshard"),
        "wd": spfx + ("experts", "mlp_noshard", "embed"),
    }
    return p, s


def capacity(tokens: int, cfg) -> int:
    c = int(math.ceil(tokens * cfg.num_experts_per_tok / cfg.num_experts
                      * CAPACITY_FACTOR))
    return max(8, -(-c // 8) * 8)  # round up to 8


def moe_apply(cfg, p, x, capture=None):
    dt = x.dtype
    b, s, d = x.shape
    t = b * s
    k = cfg.num_experts_per_tok
    e = cfg.num_experts
    c = capacity(t, cfg)

    xf = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xf, p["router"].astype(dt)
                        ).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)  # (t, k)
    topw = topw / jnp.maximum(jnp.sum(topw, -1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)

    # ---- sorted capacity dispatch ----
    flat_e = topi.reshape(-1)                       # (t*k,)
    flat_w = topw.reshape(-1).astype(dt)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e, stable=True)
    se, sw, stok = flat_e[order], flat_w[order], flat_tok[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts            # segment starts
    pos = jnp.arange(t * k) - starts[se]
    keep = pos < c
    buf_idx = jnp.where(keep, se * c + pos, e * c)  # overflow slot dropped

    disp_tok = jnp.full((e * c + 1,), t, jnp.int32).at[buf_idx].set(
        stok.astype(jnp.int32))[:-1].reshape(e, c)
    disp_w = jnp.zeros((e * c + 1,), dt).at[buf_idx].set(sw)[:-1].reshape(e, c)

    xpad = jnp.concatenate([xf, jnp.zeros((1, d), dt)], axis=0)
    # expert-shard the dispatched tokens: combined with the batch-sharded
    # combine output below, XLA lowers the MoE combine as reduce-scatter
    # (half the all-reduce wire; net 1.6x step time on dbrx train_4k —
    # EXPERIMENTS.md §Perf H-B discusses the compute-side trade-off)
    gathered = _constrain_experts(xpad[disp_tok])   # (e, c, d)

    g = jnp.einsum("ecd,edf->ecf", gathered, p["wg"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", gathered, p["wu"].astype(dt))
    h = jax.nn.silu(g) * u
    if capture is not None:
        capture["wd_in"] = h            # (e, c, f): per-expert FC2 inputs
        capture["wd_valid"] = disp_tok < t
    y = jnp.einsum("ecf,efd->ecd", h, p["wd"].astype(dt))
    y = y * disp_w[..., None]

    # pin the combine output to token(batch)-sharding: XLA then combines
    # the per-expert-shard partials with a reduce-scatter to the token
    # shards instead of a full all-reduce (EXPERIMENTS.md §Perf H-B)
    out = jnp.zeros((t + 1, d), dt).at[disp_tok.reshape(-1)].add(
        y.reshape(-1, d))[:t]
    out = constrain_batch(out)
    return out.reshape(b, s, d), aux


def route(cfg, p, xf):
    """Router of (t, d) tokens over all ``cfg.num_experts``, in float32:
    softmax, top-k, renormalised. Returns (weights, experts), each (t, k)."""
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    return topw / jnp.sum(topw, -1, keepdims=True), topi


def moe_apply_held(cfg, p, x, live=None):
    """The held experts' part of the MoE output for ``x`` (b, s, d).

    Every (token, expert) assignment to a held expert is kept: the
    assignments are sorted by expert, those to experts held elsewhere
    last, and the three SwiGLU products run as ragged matrix products over
    the held experts' groups, so the rows past the last group (assignments
    held elsewhere) are no held expert's work. Returns (out, tokens): the
    weighted sum of the held experts' outputs, and per held expert the
    number of assignments of the tokens where ``live`` (b * s,) holds
    (every token without it), an int32 (held,).
    """
    dt = x.dtype
    b, s, d = x.shape
    t, k = b * s, cfg.num_experts_per_tok
    held = p["wg"].shape[0]
    xf = x.reshape(t, d)
    topw, topi = route(cfg, p, xf)
    flat_e = topi.reshape(-1)                           # (t*k,)
    mine = flat_e < held
    group = jnp.where(mine, flat_e, held)               # held elsewhere: last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    rows = xf[order // k]
    g = jax.lax.ragged_dot(rows, p["wg"].astype(dt), sizes)
    u = jax.lax.ragged_dot(rows, p["wu"].astype(dt), sizes)
    y = jax.lax.ragged_dot(jax.nn.silu(g) * u, p["wd"].astype(dt), sizes)
    y = y[jnp.argsort(order)]                           # back to (t*k,)
    w = topw.reshape(-1)
    y = jnp.where(mine[:, None], y.astype(jnp.float32) * w[:, None], 0.0)
    out = y.reshape(t, k, d).sum(1).astype(dt).reshape(b, s, d)
    if live is None:
        counted = mine
    else:
        counted = mine & jnp.repeat(live.reshape(-1), k)
    tokens = jnp.bincount(jnp.where(counted, flat_e, held),
                          length=held + 1)[:held].astype(jnp.int32)
    return out, tokens


def _constrain_experts(x):
    """Pin (e, c, d) to experts->model when a mesh context is installed."""
    import jax as _jax
    from ..distributed import activation as _act
    mesh = getattr(_act._ctx, "mesh", None)
    if mesh is None or "model" not in mesh.shape \
            or x.shape[0] % mesh.shape["model"] != 0:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    return _jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P("model", None, None)))
