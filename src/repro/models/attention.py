"""Attention: GQA/MHA self- and cross-attention with RoPE / sliding-window,
dense and flash (lax-scan online-softmax) implementations, and KV caching.

The flash_lax path is the algorithmic twin of ``repro.kernels.flash_attention``
(Pallas): same online-softmax blocking, expressed with ``lax.scan`` so that it
lowers on any backend and the dry-run HLO reflects flash memory behaviour.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .layers import apply_rope, compute_dtype, dense_init, layer_rope

NEG_INF = -1e30
# named scope of the attention between the projections, per kind; the
# compiler keeps it in each operation's ``op_name`` metadata
ATTN_SCOPE = {"full": "attn_full", "sliding_window": "attn_window"}


def attention_init(key, cfg, nlayers: int, cross: bool = False):
    d, dh = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    pfx = (nlayers,) if nlayers else ()
    spfx = ("layers",) if nlayers else ()
    p = {
        "wq": dense_init(ks[0], pfx + (d, hq * dh)),
        "wk": dense_init(ks[1], pfx + (d, hkv * dh)),
        "wv": dense_init(ks[2], pfx + (d, hkv * dh)),
        "wo": dense_init(ks[3], pfx + (hq * dh, d)),
    }
    s = {
        "wq": spfx + ("embed", "heads"),
        "wk": spfx + ("embed", "kv"),
        "wv": spfx + ("embed", "kv"),
        "wo": spfx + ("heads", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros(pfx + (hq * dh,), jnp.float32)
        p["bk"] = jnp.zeros(pfx + (hkv * dh,), jnp.float32)
        p["bv"] = jnp.zeros(pfx + (hkv * dh,), jnp.float32)
        s["bq"] = spfx + ("heads",)
        s["bk"] = spfx + ("kv",)
        s["bv"] = spfx + ("kv",)
    if cross:
        # tanh gate on the cross-attn residual branch (llama-3.2-vision style)
        p["gate"] = jnp.zeros(pfx, jnp.float32)
        s["gate"] = spfx if spfx else ()
    return p, s


def _project_qkv(cfg, p, x, kv_x):
    dt = x.dtype
    dh = cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", kv_x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", kv_x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(*q.shape[:-1], hq, dh)
    k = k.reshape(*k.shape[:-1], hkv, dh)
    v = v.reshape(*v.shape[:-1], hkv, dh)
    return q, k, v


def _grouped(q, hkv):
    """(B,S,HQ,D) -> (B,S,HKV,G,D)."""
    b, s, hq, dh = q.shape
    return q.reshape(b, s, hkv, hq // hkv, dh)


def dense_attention(q, k, v, *, causal: bool, window: int = 0,
                    q_positions=None, k_positions=None):
    """Grouped-head dense attention. q: (B,Sq,HQ,D), k/v: (B,Sk,HKV,D)."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    qg = _grouped(q, hkv)
    scale = 1.0 / math.sqrt(dh)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * scale  # (B,HKV,G,Sq,Sk)
    if q_positions is None:
        q_positions = jnp.arange(sq)
    if k_positions is None:
        k_positions = jnp.arange(k.shape[1])
    qpos = q_positions[:, None]
    kpos = k_positions[None, :]
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits.astype(jnp.float32), NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, sq, hq, dh)


def flash_attention_lax(q, k, v, *, causal: bool, window: int = 0,
                        block_k: int = 1024, q_offset: int = 0):
    """Online-softmax attention, scanning over KV blocks (flash twin).

    Never materializes the (Sq, Sk) score matrix in HBM: per scan step only a
    (B,HKV,G,Sq,block_k) tile is live, which XLA keeps in the fused loop body.
    """
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    sk = k.shape[1]
    nblocks = (sk + block_k - 1) // block_k
    pad = nblocks * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qg = _grouped(q, hkv)
    scale = 1.0 / math.sqrt(dh)
    kb = k.reshape(b, nblocks, block_k, hkv, dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblocks, block_k, hkv, dh).transpose(1, 0, 2, 3, 4)
    qpos = q_offset + jnp.arange(sq)

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, sq, hkv, g, dh), jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, blk_idx = inp
        kpos = blk_idx * block_k + jnp.arange(block_k)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kblk).astype(jnp.float32)
        logits = logits * scale
        mask = kpos[None, :] < sk
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        logits = jnp.where(mask, logits, NEG_INF)
        m_blk = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = (acc * alpha.transpose(0, 3, 1, 2)[..., None]
                   + jnp.einsum("bhgqk,bkhd->bqhgd",
                                p.astype(q.dtype), vblk).astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (kb, vb, jnp.arange(nblocks)))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.astype(q.dtype).reshape(b, sq, hq, dh)


def flash_attention_chunked(q, k, v, *, causal: bool, window: int = 0,
                            block_k: int = 1024, max_chunks: int = 16,
                            chunk_target: int = 2048):
    """Query-chunked flash: python-unrolled loop over q chunks, each with a
    *statically sliced* causal/window KV prefix (halves causal FLOPs and
    bounds the live score tile), kv-scanned flash inside each chunk."""
    b, sq, hq, dh = q.shape
    nq = max(1, min(max_chunks, -(-sq // chunk_target)))
    bq = -(-sq // nq)
    outs = []
    for i in range(nq):
        lo = i * bq
        hi = min(sq, (i + 1) * bq)
        if lo >= sq:
            break
        qc = q[:, lo:hi]
        k_hi = hi if causal else k.shape[1]
        k_lo = max(0, lo - window) if window else 0
        kc, vc = k[:, k_lo:k_hi], v[:, k_lo:k_hi]
        outs.append(flash_attention_lax(
            qc, kc, vc, causal=causal, window=window, block_k=block_k,
            q_offset=lo - k_lo))
    return jnp.concatenate(outs, axis=1)


def _select_impl(cfg, sq, sk):
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "flash_lax" if (sq > 2048 and sk > 2048) else "dense"
    return impl


def self_attention(cfg, p, x, *, cache=None, cache_pos=None, capture=None,
                   kind=None):
    """Self-attention for train/prefill (cache=None) or decode (cache given).

    kind: the layer's attention kind ("full" | "sliding_window"; default
    ``cfg.attention``), which sets its window and its RoPE.
    cache: dict(k=(B,Sc,HKV,D), v=...), or (B,HKV,Sc,D) where
    :func:`heads_major` — a ring buffer for sliding-window.
    cache_pos: absolute position of the current token — a scalar int32
    (lockstep batch, the classic ``generate`` loop) or a (B,) int32 vector
    (per-slot positions, the continuous-batching serving engine; each slot
    writes its own cache row and masks its own prefix).
    Everything between the projections (RoPE, the cache's write and read,
    scores, softmax, weighted sum) runs under the named scope
    ``ATTN_SCOPE[kind]``.
    Returns (out, new_cache).
    """
    b, sq, _ = x.shape
    kind = kind or cfg.attention
    q, k, v = _project_qkv(cfg, p, x, x)
    with jax.named_scope(ATTN_SCOPE.get(kind, "attn")):
        out, new_cache = _attend(cfg, q, k, v, kind, cache, cache_pos)
    flat = out.reshape(b, sq, -1)
    if capture is not None:
        capture["wo_in"] = flat
    y = jnp.einsum("bsh,hd->bsd", flat, p["wo"].astype(x.dtype))
    return y, new_cache


def _attend(cfg, q, k, v, kind, cache, cache_pos):
    """Attention of projected q, k, v (B,S,H,D) for ``self_attention``."""
    b, sq = q.shape[:2]
    causal = cfg.causal
    window = cfg.window_size if kind == "sliding_window" else 0
    theta, yarn = layer_rope(cfg, kind)

    if cache is None:
        if cfg.pos_emb == "rope":
            pos = jnp.arange(sq)[None, :]
            q = apply_rope(q, pos, theta, yarn)
            k = apply_rope(k, pos, theta, yarn)
        impl = _select_impl(cfg, sq, sq)
        if impl == "flash_lax":
            out = flash_attention_chunked(q, k, v, causal=causal,
                                          window=window,
                                          block_k=cfg.flash_block_k)
        else:
            out = dense_attention(q, k, v, causal=causal, window=window)
        new_cache = None
    else:
        # single-token decode: sq == 1
        hm = heads_major(cfg)
        axis = kv_pos_axis(cfg)
        sc = cache["k"].shape[axis]
        vec = jnp.ndim(cache_pos) == 1  # per-slot positions (serving engine)
        if cfg.pos_emb == "rope":
            posq = cache_pos[:, None] if vec else \
                jnp.broadcast_to(cache_pos.reshape(1, 1), (b, 1))
            q = apply_rope(q, posq, theta, yarn)
            k = apply_rope(k, posq, theta, yarn)
        slot = (cache_pos % sc) if window else jnp.minimum(cache_pos, sc - 1)
        k, v = cache_rows(cfg, k), cache_rows(cfg, v)
        if vec:
            # slot i writes its own row, as a select over the cache axis:
            # the TPU compiler keeps the buffer (positions minor) in place,
            # where a per-row scatter makes it convert the whole buffer
            # out of its layout and back
            hit = jnp.arange(sc)[None, :] == slot[:, None]
            hit = hit[:, None, :, None] if hm else hit[:, :, None, None]
            ck = jnp.where(hit, k.astype(cache["k"].dtype), cache["k"])
            cv = jnp.where(hit, v.astype(cache["v"].dtype), cache["v"])
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot,
                                                     axis=axis)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot,
                                                     axis=axis)
        # positions of cached entries; posb broadcasts the scalar path so
        # one mask expression covers both (identical values for scalars)
        idx = jnp.arange(sc)
        posb = cache_pos[:, None] if vec else \
            jnp.broadcast_to(cache_pos, (1,))[:, None]        # (B|1, 1)
        if window:
            # ring buffer: entry i holds abs position p with p % sc == i,
            # p in (cache_pos - sc, cache_pos]
            kpos = posb - ((posb - idx[None, :]) % sc)
        else:
            kpos = jnp.broadcast_to(idx[None, :], posb.shape[:1] + (sc,))
        valid = (kpos <= posb) & (kpos >= 0)  # >=0: unwritten ring slots
        if window:
            valid &= kpos > posb - window
        qg = _grouped(q, cfg.num_kv_heads)
        scale = 1.0 / math.sqrt(cfg.resolved_head_dim)
        cache_dims = "bhkd" if hm else "bkhd"
        logits = jnp.einsum(f"bqhgd,{cache_dims}->bhgqk", qg, ck) * scale
        logits = jnp.where(valid[:, None, None, None, :],
                           logits.astype(jnp.float32), NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum(f"bhgqk,{cache_dims}->bqhgd", probs, cv)
        out = out.reshape(b, sq, cfg.num_heads, cfg.resolved_head_dim)
        new_cache = {"k": ck, "v": cv}

    return out, new_cache


def cross_attention(cfg, p, x, kv_cache, *, capture=None):
    """Cross-attention against precomputed (k, v) from encoder/vision states.

    kv_cache: dict(k=(B,T,HKV,D), v=(B,T,HKV,D)) — computed once by
    ``cross_kv`` below; shared between train/prefill/decode.
    """
    b, sq, _ = x.shape
    dt = x.dtype
    dh = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
    q = q.reshape(b, sq, cfg.num_heads, dh)
    out = dense_attention(q, kv_cache["k"], kv_cache["v"], causal=False)
    flat = out.reshape(b, sq, -1)
    if capture is not None:
        capture["wo_in"] = flat
    y = jnp.einsum("bsh,hd->bsd", flat, p["wo"].astype(dt))
    if "gate" in p:
        y = jnp.tanh(p["gate"]).astype(dt) * y
    return y


def cross_kv(cfg, p, kv_x):
    """Precompute cross-attention K/V from encoder/vision hidden states."""
    dt = kv_x.dtype
    dh = cfg.resolved_head_dim
    k = jnp.einsum("btd,dh->bth", kv_x, p["wk"].astype(dt))
    v = jnp.einsum("btd,dh->bth", kv_x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    b, t, _ = k.shape
    return {"k": k.reshape(b, t, cfg.num_kv_heads, dh),
            "v": v.reshape(b, t, cfg.num_kv_heads, dh)}


def heads_major(cfg) -> bool:
    """Whether the K/V cache holds each slot's heads before its positions,
    (B, HKV, S, D), instead of (B, S, HKV, D): for heads that fill the
    TPU's 128 lanes. A decode step's scores and weighted sum are then
    matrix products batched over (slot, head) on the buffer as it is laid
    out; over (B, S, HKV, D) the TPU compiler converts each layer's whole
    buffer into that order every step."""
    return cfg.resolved_head_dim % 128 == 0


def kv_pos_axis(cfg) -> int:
    """Axis of the positions in one layer's K/V cache (batch first)."""
    return 2 if heads_major(cfg) else 1


def cache_rows(cfg, x):
    """Keys or values (B, S, HKV, D) in the K/V cache's order."""
    return x.swapaxes(1, 2) if heads_major(cfg) else x


def kv_shape(cfg, batch: int, positions: int, heads: int):
    """Shape of one layer's K (or V) cache."""
    dh = cfg.resolved_head_dim
    if heads_major(cfg):
        return (batch, heads, positions, dh)
    return (batch, positions, heads, dh)


def init_kv_cache(cfg, batch: int, seq_len: int, nlayers: int, dtype,
                  kind=None):
    """Allocate the self-attention KV cache of ``nlayers`` layers of one
    attention kind (default ``cfg.attention``): a ring buffer of the window
    for sliding-window layers."""
    kind = kind or cfg.attention
    window = cfg.window_size if kind == "sliding_window" else 0
    sc = min(seq_len, window) if window else seq_len
    shape = (nlayers,) + kv_shape(cfg, batch, sc, cfg.num_kv_heads)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
