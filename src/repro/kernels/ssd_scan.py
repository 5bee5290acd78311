"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk pass.

Per (batch, chunk, head-block) grid step the kernel computes, entirely in
VMEM:
  scores  = C_chunk @ B_chunk^T                       (Q, Q)  MXU
  L       = exp(segsum(dA)) (causal decay matrix)     (Q, Q)  per head
  y_diag  = (scores * L) @ (x*dt)                     per head
  states  = (B * decay_to_end)^T @ (x*dt)             chunk -> state
The O(Q^2) decay/score tiles never reach HBM. The (cheap, sequential)
inter-chunk recurrence and the y_off correction stay in lax (ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _ssd_kernel(xdt_ref, dcol_ref, drow_ref, b_ref, c_ref, y_ref, st_ref,
                *, q: int, hb: int):
    # blocks: xdt/y (1,1,hb,Q,P)  dcol (1,1,hb,Q,1)  drow (1,1,hb,1,Q)
    #         b/c (1,1,Q,N)  st (1,1,hb,P,N) — every block's minor two
    #         dims are (Q or 1 or P, N or P or Q): whole array dims or
    #         tile multiples, never a slice of the head axis
    B = b_ref[0, 0].astype(jnp.float32)            # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)            # (Q, N)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril = jj <= ii
    for h in range(hb):
        xdt = xdt_ref[0, 0, h].astype(jnp.float32)     # (Q, P)
        dcol = dcol_ref[0, 0, h].astype(jnp.float32)   # (Q, 1)
        drow = drow_ref[0, 0, h].astype(jnp.float32)   # (1, Q)
        # causal decay L[i,j] = exp(dacs[i] - dacs[j]) for i >= j
        L = jnp.exp(jnp.where(tril, dcol - drow, NEG_INF))
        y = jnp.dot(scores * L, xdt, preferred_element_type=jnp.float32)
        y_ref[0, 0, h] = y.astype(y_ref.dtype)
        # chunk state: sum_j exp(dacs[-1] - dacs[j]) xdt[j,p] B[j,n]
        xw = xdt * jnp.exp(dcol[q - 1:, :] - dcol)     # (Q, P)
        st = jax.lax.dot_general(xw, B, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        st_ref[0, 0, h] = st.astype(st_ref.dtype)     # (P, N)


def ssd_intra_chunk_kernel(xdt, dacs, B, C, *, head_block: int = 8,
                           interpret: bool):
    """Intra-chunk SSD.

    xdt:  (b, nc, q, h, p) — dt-scaled inputs
    dacs: (b, nc, q, h)    — cumulative sum of dt*A within chunk
    B, C: (b, nc, q, n)
    Returns (y_diag: (b,nc,q,h,p) fp32, states: (b,nc,h,p,n) fp32).

    The kernel runs head-major: heads move ahead of the chunk positions
    so that a head block is never the minor dimension of a block (the TPU
    compiler tiles the minor two dims by (8, 128) or takes them whole).
    """
    b, nc, q, h, p = xdt.shape
    n = B.shape[-1]
    hb = min(head_block, h)
    while h % hb:
        hb -= 1
    nh = h // hb

    xh = xdt.transpose(0, 1, 3, 2, 4)                  # (b, nc, h, q, p)
    dh = dacs.transpose(0, 1, 3, 2)                    # (b, nc, h, q)
    kernel = functools.partial(_ssd_kernel, q=q, hb=hb)
    y, st = pl.pallas_call(
        kernel,
        grid=(b, nc, nh),
        in_specs=[
            pl.BlockSpec((1, 1, hb, q, p), lambda i, c, j: (i, c, j, 0, 0)),
            pl.BlockSpec((1, 1, hb, q, 1), lambda i, c, j: (i, c, j, 0, 0)),
            pl.BlockSpec((1, 1, hb, 1, q), lambda i, c, j: (i, c, j, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, c, j: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, c, j: (i, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hb, q, p), lambda i, c, j: (i, c, j, 0, 0)),
            pl.BlockSpec((1, 1, hb, p, n), lambda i, c, j: (i, c, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, h, q, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(xh, dh[..., None], dh[..., None, :], B, C)
    return y.transpose(0, 1, 3, 2, 4), st
