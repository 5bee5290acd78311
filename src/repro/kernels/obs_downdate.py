"""Pallas TPU kernel fusing the structured-OBS rank-``gs`` downdate.

Every Algorithm-1 step updates both the weights and the inverse Hessian:

  W    <- (W    - Hinv[:,S] @ KsWS)    * keep[:,None]
  Hinv <- (Hinv - Hinv[:,S] @ KsHcolT) * keep[:,None] * keep[None,:]

Written naively, ``HcolS @ (Ks @ HcolS.T)`` materializes a (d, d)
intermediate in HBM before the subtract, and the keep mask adds two more
full passes. This kernel streams one (block_d, d) row strip of Hinv and
one (block_d, d_out) strip of W through VMEM per grid step, performs the
two small (block_d, gs) x (gs, ·) MXU matmuls, subtracts, applies the
mask, and writes the strips back — one read + one write of each operand,
no intermediates.

The grid is 1-D over row strips; the right-hand factors (gs rows) and the
column mask are broadcast to every step. The pipeline double-buffers both
input strips and both output strips, so one step holds
``2 x 2 x block_d x (d_out + d) x 4`` bytes plus the gs-row factors: at
block_d=256 and d = d_out = 4096 fp32 that is 32 MiB, twice the 16 MiB of
scoped VMEM the TPU compiler grants a kernel by default. ``block_d`` is
therefore capped from ``d`` (``_strip_rows``) so the strips stay within
``VMEM_BUDGET``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# bytes of the default 16 MiB scoped VMEM given to the four row strips;
# the rest is headroom for the gs-row factors and the column mask
VMEM_BUDGET = 12 * 2**20


def _strip_rows(d_in: int, d_out: int, block_d: int) -> int:
    """Row-strip height: at most ``block_d``, small enough that the
    double-buffered W and Hinv strips (in and out) fit ``VMEM_BUDGET``,
    a multiple of 8 (the sublane tile), and a divisor of ``d_in`` where
    one exists so that Hinv needs no padding."""
    fit = VMEM_BUDGET // (16 * (d_in + d_out))
    rows = max(8, min(block_d, fit) // 8 * 8)
    if rows >= d_in:
        return d_in
    for r in range(rows, 7, -8):
        if d_in % r == 0:
            return r
    return rows


def _downdate_kernel(w_ref, h_ref, a_ref, kw_ref, kh_ref, krow_ref,
                     kall_ref, wo_ref, ho_ref):
    a = a_ref[...].astype(jnp.float32)            # (bd, gs)
    krow = krow_ref[...].astype(jnp.float32)      # (bd, 1)
    wo_ref[...] = (w_ref[...].astype(jnp.float32)
                   - jnp.dot(a, kw_ref[...].astype(jnp.float32),
                             preferred_element_type=jnp.float32)) * krow
    ho_ref[...] = (h_ref[...].astype(jnp.float32)
                   - jnp.dot(a, kh_ref[...].astype(jnp.float32),
                             preferred_element_type=jnp.float32)) \
        * krow * kall_ref[...].astype(jnp.float32)


def obs_downdate_kernel(W: jnp.ndarray, Hinv: jnp.ndarray,
                        HcolS: jnp.ndarray, KsWS: jnp.ndarray,
                        KsHcolT: jnp.ndarray, keep: jnp.ndarray, *,
                        block_d: int = 256, interpret: bool,
                        d_live: int | None = None):
    """(W, Hinv, HcolS, KsWS, KsHcolT, keep) -> (W_new, Hinv_new).

    Shapes as in kernels.ref.obs_downdate_ref. d_in is padded up to a
    block_d multiple internally (padded keep rows are 0, so the padding
    never leaks into the live block).

    ``d_live`` (static) restricts the grid to the live prefix produced by
    live-set compaction: only ceil(d_live / block_d) row strips are
    streamed, the dead [d_live, d_in) tail is written back as zeros
    without ever entering VMEM.
    """
    if d_live is not None and d_live < W.shape[0]:
        from .ref import live_prefix_downdate
        return live_prefix_downdate(
            functools.partial(obs_downdate_kernel, block_d=block_d,
                              interpret=interpret),
            W, Hinv, HcolS, KsWS, KsHcolT, keep, d_live)
    d_in, d_out = W.shape
    gs = HcolS.shape[1]
    block_d = _strip_rows(d_in, d_out, block_d)
    nb = pl.cdiv(d_in, block_d)
    dp = nb * block_d
    pad = dp - d_in
    if pad:
        W = jnp.pad(W, ((0, pad), (0, 0)))
        Hinv = jnp.pad(Hinv, ((0, pad), (0, pad)))
        HcolS = jnp.pad(HcolS, ((0, pad), (0, 0)))
        KsHcolT = jnp.pad(KsHcolT, ((0, 0), (0, pad)))
        keep = jnp.pad(keep, (0, pad))
    krow = keep.reshape(dp, 1)
    kall = keep.reshape(1, dp)

    w_new, h_new = pl.pallas_call(
        _downdate_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_d, d_out), lambda i: (i, 0)),
            pl.BlockSpec((block_d, dp), lambda i: (i, 0)),
            pl.BlockSpec((block_d, gs), lambda i: (i, 0)),
            pl.BlockSpec((gs, d_out), lambda i: (0, 0)),
            pl.BlockSpec((gs, dp), lambda i: (0, 0)),
            pl.BlockSpec((block_d, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_d, d_out), lambda i: (i, 0)),
            pl.BlockSpec((block_d, dp), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((dp, d_out), jnp.float32),
            jax.ShapeDtypeStruct((dp, dp), jnp.float32),
        ],
        interpret=interpret,
    )(W, Hinv, HcolS, KsWS, KsHcolT, krow, kall)
    return w_new[:d_in], h_new[:d_in, :d_in]
