"""Public wrappers around the Pallas kernels, with a guarded fallback.

``interpret=None`` resolves from the backend (``_default_interpret``):
compiled on TPU, interpret mode elsewhere. The raw kernel functions take
``interpret`` as a required argument, so only these wrappers decide it.

Graceful degradation: every public op routes through ``_run_guarded`` —
a kernel failure (trace/compile error, or an injected ``kernel.pallas``
fault) trips a per-op circuit breaker on the ambient RobustnessReport
and the call is re-run on the jitted ``kernels.ref`` oracle; once open,
the breaker short-circuits straight to the reference path (the demotion
is counted and logged once per op).  Device-side failures raised from
*inside* an already-traced caller (e.g. the vmap'd prune loop) cannot be
caught here — ``core.database`` retries the whole chunk with
``use_kernel=False`` for that case.  Clean runs never enter the except
path, so outputs are bit-identical with the guard in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..robustness import faults as _faults
from ..robustness.report import current_report
from . import ref
from .flash_attention import flash_attention_kernel
from .hessian_accum import hessian_accum_kernel
from .obs_downdate import obs_downdate_kernel
from .ssd_scan import ssd_intra_chunk_kernel


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _run_guarded(op: str, kernel_thunk, ref_thunk):
    """Run the Pallas path unless this op's breaker is open; on failure
    trip the breaker and fall back to the jnp reference oracle.

    Every op name guarded here must have a matching entry in the
    ``repro.analysis.pallas_audit`` registry (signature / output-aval /
    grid contracts of the kernel-ref twin are CI-checked); the two-way
    drift check fails the analysis gate otherwise."""
    rep = current_report()
    key = f"kernel.pallas:{op}"
    if rep.breaker_open(key):
        return ref_thunk()
    try:
        _faults.hit("kernel.pallas")
        return kernel_thunk()
    except Exception as e:
        rep.trip(key, reason=f"{op}: {e!r}")
        return ref_thunk()


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def _flash_attention_impl(q, k, v, *, causal=True, window=0, block_q=128,
                          block_k=128, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, k.shape[1], d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, k.shape[1], d)
    out = flash_attention_kernel(qf, kf, vf, causal=causal, window=window,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def _flash_attention_ref(q, k, v, causal, window):
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hq, k.shape[1], d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hq, v.shape[1], d)
    out = ref.attention_ref(qf, kf, vf, causal=causal, window=window)
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3).astype(q.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=None):
    """q: (B, Sq, HQ, D), k/v: (B, Sk, HKV, D) -> (B, Sq, HQ, D)."""
    return _run_guarded(
        "flash_attention",
        lambda: _flash_attention_impl(q, k, v, causal=causal, window=window,
                                      block_q=block_q, block_k=block_k,
                                      interpret=interpret),
        lambda: _flash_attention_ref(q, k, v, causal, window))


# ---------------------------------------------------------------------------
# hessian accumulation
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("block_d", "block_n",
                                             "interpret"))
def _hessian_accum_impl(x, acc=None, *, block_d=256, block_n=512,
                        interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return hessian_accum_kernel(x, acc, block_d=block_d, block_n=block_n,
                                interpret=interpret)


@jax.jit
def _hessian_accum_ref(x, acc=None):
    h = ref.hessian_ref(x)
    return h if acc is None else acc + h


def hessian_accum(x, acc=None, *, block_d=256, block_n=512, interpret=None):
    """(N, D) -> (D, D) fp32 X^T X; with ``acc`` (D, D) returns
    ``acc + X^T X`` in one tile-stream pass (calibration update)."""
    return _run_guarded(
        "hessian_accum",
        lambda: _hessian_accum_impl(x, acc, block_d=block_d,
                                    block_n=block_n, interpret=interpret),
        lambda: _hessian_accum_ref(x, acc))


# ---------------------------------------------------------------------------
# OBS downdate
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("block_d", "interpret",
                                             "d_live"))
def _obs_downdate_impl(W, Hinv, HcolS, KsWS, KsHcolT, keep, *, block_d=256,
                       interpret=None, d_live=None):
    interpret = _default_interpret() if interpret is None else interpret
    return obs_downdate_kernel(W, Hinv, HcolS, KsWS, KsHcolT, keep,
                               block_d=block_d, interpret=interpret,
                               d_live=d_live)


@functools.partial(jax.jit, static_argnames=("d_live",))
def _obs_downdate_ref(W, Hinv, HcolS, KsWS, KsHcolT, keep, d_live=None):
    return ref.obs_downdate_ref(W, Hinv, HcolS, KsWS, KsHcolT, keep,
                                d_live=d_live)


def obs_downdate(W, Hinv, HcolS, KsWS, KsHcolT, keep, *, block_d=256,
                 interpret=None, d_live=None):
    """Fused OBS rank-gs W/Hinv downdate (see kernels.obs_downdate).

    Semantics match kernels.ref.obs_downdate_ref exactly, including the
    static ``d_live`` live-prefix restriction used by live-set compaction
    (rows/cols >= d_live are dead and come back zero).
    """
    return _run_guarded(
        "obs_downdate",
        lambda: _obs_downdate_impl(W, Hinv, HcolS, KsWS, KsHcolT, keep,
                                   block_d=block_d, interpret=interpret,
                                   d_live=d_live),
        lambda: _obs_downdate_ref(W, Hinv, HcolS, KsWS, KsHcolT, keep,
                                  d_live=d_live))


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("chunk", "head_block",
                                             "interpret"))
def _ssd_chunked_impl(x, dt, A, B, C, *, chunk=128, head_block=8,
                      interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nc, q = sp // chunk, chunk

    xb = x.reshape(b, nc, q, h, p)
    dtb = dt.reshape(b, nc, q, h).astype(jnp.float32)
    Bb = B.reshape(b, nc, q, n)
    Cb = C.reshape(b, nc, q, n)
    dacs = jnp.cumsum(dtb * A, axis=2)
    xdt = (xb.astype(jnp.float32) * dtb[..., None])

    y_diag, states = ssd_intra_chunk_kernel(xdt, dacs, Bb, Cb,
                                            head_block=head_block,
                                            interpret=interpret)

    chunk_decay = jnp.exp(dacs[:, :, -1, :])  # (b,nc,h)

    def body(prev, inp):
        st, dec = inp
        return prev * dec[..., None, None] + st, prev

    final, prev_states = jax.lax.scan(
        body, jnp.zeros((b, h, p, n), jnp.float32),
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)

    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp",
                       Cb.astype(jnp.float32), prev_states, jnp.exp(dacs))
    y = (y_diag + y_off).reshape(b, sp, h, p)[:, :s]
    return y.astype(x.dtype), final


_ssd_ref = jax.jit(ref.ssd_ref)


def ssd_chunked_kernel(x, dt, A, B, C, *, chunk=128, head_block=8,
                       interpret=None):
    """Full SSD via the Pallas intra-chunk kernel + lax inter-chunk scan.

    Same signature/semantics as models.ssm.ssd_chunked.
    """
    return _run_guarded(
        "ssd",
        lambda: _ssd_chunked_impl(x, dt, A, B, C, chunk=chunk,
                                  head_block=head_block,
                                  interpret=interpret),
        lambda: _ssd_ref(x, dt, A, B, C))
