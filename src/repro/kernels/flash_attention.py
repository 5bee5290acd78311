"""Pallas TPU flash attention (forward): online-softmax over KV blocks.

HBM->VMEM tiling via BlockSpec: per grid step the kernel sees one
(block_q, head_dim) query tile and one (block_k, head_dim) KV tile; the
(block_q, block_k) score tile lives only in VMEM/VREGs — the O(Sq*Sk)
matrix never touches HBM. Heads are folded into the leading grid dim;
GQA is expressed through the K/V index_map (q head -> kv head), so
repeated KV heads are never materialized.

Supports causal + sliding-window masking and a q_offset for
chunked-prefill use. MXU alignment: block_q/block_k multiples of 128,
head_dim padded to 128 by the ops.py wrapper if needed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int, block_q: int,
                  block_k: int, sk: int, q_offset: int, nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                      # (bq, d)
    k = k_ref[0]                      # (bk, d)
    v = v_ref[0]

    qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < sk
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * scale
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]                        # (bq, 1)
    m_blk = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    alpha = jnp.exp(m_prev - m_new)              # (bq, 1)
    p = jnp.exp(s - m_new)                       # (bq, bk)
    l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           q_offset: int = None, interpret: bool):
    """q: (BH, Sq, D), k/v: (BHKV, Sk, D). BH = BHKV * group. fp32/bf16."""
    bh, sq, d = q.shape
    bhkv, sk, _ = k.shape
    group = bh // bhkv
    if q_offset is None:
        q_offset = sk - sq
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    pad_q = nq * block_q - sq
    pad_k = nk * block_k - sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(d), causal=causal,
        window=window, block_q=block_q, block_k=block_k, sk=sk,
        q_offset=q_offset, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, nq * block_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
