"""Pallas TPU kernel for calibration Hessian accumulation H += X^T X.

The compute hot-spot of ZipLM database construction: X is (N, D) with N =
batch*seq calibration tokens (large), D the module's input width. Tiled as
(block_d x block_n) x (block_n x block_d) MXU matmuls accumulating fp32 in
VMEM scratch over the N grid dimension; X streams HBM->VMEM once per
(i, j) output tile row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _xtx_kernel(xi_ref, xj_ref, o_ref, acc_ref, *, nn: int):
    n_idx = pl.program_id(2)

    @pl.when(n_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xi = xi_ref[...].astype(jnp.float32)      # (bn, bd_i)
    xj = xj_ref[...].astype(jnp.float32)      # (bn, bd_j)
    acc_ref[...] += jax.lax.dot_general(
        xi, xj, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(n_idx == nn - 1)
    def _finish():
        o_ref[...] = acc_ref[...]


def _xtx_acc_kernel(xi_ref, xj_ref, a_ref, o_ref, acc_ref, *, nn: int):
    """Same tile stream, but the VMEM accumulator is seeded from a prior
    Hessian tile — folds ``H + X^T X`` into one pass (no separate add)."""
    n_idx = pl.program_id(2)

    @pl.when(n_idx == 0)
    def _init():
        acc_ref[...] = a_ref[...]

    xi = xi_ref[...].astype(jnp.float32)      # (bn, bd_i)
    xj = xj_ref[...].astype(jnp.float32)      # (bn, bd_j)
    acc_ref[...] += jax.lax.dot_general(
        xi, xj, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(n_idx == nn - 1)
    def _finish():
        o_ref[...] = acc_ref[...]


def hessian_accum_kernel(x: jnp.ndarray, acc=None, *, block_d: int = 256,
                         block_n: int = 512, interpret: bool
                         ) -> jnp.ndarray:
    """(N, D) -> (D, D) fp32 = X^T X, or ``acc + X^T X`` when ``acc`` is a
    (D, D) running Hessian (the calibration streaming update)."""
    n, d = x.shape
    block_d = min(block_d, d)
    block_n = min(block_n, n)
    nd = pl.cdiv(d, block_d)
    nn = pl.cdiv(n, block_n)
    pad_d = nd * block_d - d
    pad_n = nn * block_n - n
    if pad_d or pad_n:
        x = jnp.pad(x, ((0, pad_n), (0, pad_d)))

    x_specs = [
        pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, i)),
        pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, j)),
    ]
    common = dict(
        grid=(nd, nd, nn),
        out_specs=pl.BlockSpec((block_d, block_d), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nd * block_d, nd * block_d),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_d, block_d), jnp.float32)],
        interpret=interpret,
    )
    if acc is None:
        out = pl.pallas_call(
            functools.partial(_xtx_kernel, nn=nn),
            in_specs=x_specs, **common,
        )(x, x)
    else:
        a = acc.astype(jnp.float32)
        if pad_d:
            a = jnp.pad(a, ((0, pad_d), (0, pad_d)))
        out = pl.pallas_call(
            functools.partial(_xtx_acc_kernel, nn=nn),
            in_specs=x_specs + [
                pl.BlockSpec((block_d, block_d), lambda i, j, k: (i, j))],
            **common,
        )(x, x, a)
    return out[:d, :d]
