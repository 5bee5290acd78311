"""Calibration: run the model over calibration batches with capture mode on
and accumulate per-module Hessians ``X^T X`` (fp32, streamed over batches).

One jitted, buffer-donated step consumes a batch and updates *all* module
Hessians at once — the forward pass and every ``X^T X`` fuse into a single
compiled call per batch, instead of a Python loop of one dispatch per
module. The inner accumulation is the Pallas ``hessian_accum`` kernel's
jnp twin; ``use_kernel=True`` routes through the kernel (interpret mode
on CPU); the kernel path seeds its VMEM accumulator from the running
Hessian so ``H + X^T X`` is one tile-stream pass.

Mesh-aware path: with a mesh (passed explicitly, or discovered from the
installed ``distributed.activation`` context) whose data axes divide every
calibration batch, the step runs under ``shard_map`` — each device runs
the capture forward on its batch shard, accumulates its *partial*
``X^T X`` locally, and the partials are ``psum``-ed over the data axes
into replicated per-module Hessians. Still one jitted, buffer-donated
call per batch; the single-device path is kept verbatim as the
equivalence reference (tests/test_sharded_calibration.py asserts fp32
agreement and identical pruning orders).

Numerical self-healing: every batch carries a finite sentinel — if any
captured activation of the batch is non-finite (a poisoned batch, or an
injected ``calib.batch`` fault via the robustness layer's poison
scalar), the whole batch's update is skipped for *all* modules
(``jnp.where(ok, new, old)``) and counted, so the result equals a clean
run over the remaining batches exactly — pruning-order equivalence is
asserted in tests/test_faults.py.  A fault-free run is bit-identical:
the poison scalar is exactly 1.0 (IEEE multiplicative identity) and a
true-predicate select returns the updated value unchanged.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..distributed.activation import activation_context, \
    get_activation_context
from ..distributed.sharding import axis_size, data_axes_for
from ..models.transformer import forward
from ..robustness import faults as _faults
from ..robustness.report import current_report
from .structures import PrunableModule, get_capture, registry


def xtx(x: jnp.ndarray, valid: Optional[jnp.ndarray] = None,
        use_kernel: bool = False, acc: Optional[jnp.ndarray] = None
        ) -> jnp.ndarray:
    """X^T X for X: (N, d); optionally mask invalid rows and/or fold the
    result into a running accumulator ``acc`` (returns acc + X^T X)."""
    x = x.astype(jnp.float32)
    if valid is not None:
        x = x * valid[:, None].astype(jnp.float32)
    if use_kernel:
        from ..kernels import ops as kops
        return kops.hessian_accum(x, acc)
    h = x.T @ x
    return h if acc is None else acc + h


def _donate():
    # donate the accumulators so each batch updates them in place
    # (donation is a no-op on CPU and would only emit warnings there)
    return (0, 1) if jax.default_backend() != "cpu" else ()


@functools.lru_cache(maxsize=16)
def _fused_step(cfg, use_kernel: bool):
    """Compiled once per (cfg, use_kernel) — gradual_prune calls
    collect_hessians per target and must not re-trace the forward."""
    mods = registry(cfg)

    def _step(hessians, counts, params, tokens, frontend, poison):
        caps = forward(cfg, params, tokens, frontend_embeds=frontend,
                       capture=True)["captures"]
        # batch-level finite sentinel: poison is exactly 1.0 on the clean
        # path (bit-exact identity); any non-finite capture anywhere in
        # the batch skips the whole batch's update for every module
        xs, ok = {}, jnp.bool_(True)
        for mod in mods:
            x, valid = get_capture(caps, mod)
            x = x * poison
            ok &= jnp.all(jnp.isfinite(x))
            xs[mod.name] = (x, valid)
        new_h: Dict[str, jnp.ndarray] = {}
        new_c: Dict[str, jnp.ndarray] = {}
        for mod in mods:
            x, valid = xs[mod.name]
            h_upd = xtx(x, valid, use_kernel=use_kernel,
                        acc=hessians[mod.name])
            new_h[mod.name] = jnp.where(ok, h_upd, hessians[mod.name])
            n = (jnp.float32(x.shape[0]) if valid is None
                 else jnp.sum(valid).astype(jnp.float32))
            new_c[mod.name] = counts[mod.name] + jnp.where(ok, n, 0.0)
        return new_h, new_c, ok

    return jax.jit(_step, donate_argnums=_donate())


@functools.lru_cache(maxsize=16)
def _fused_step_sharded(cfg, use_kernel: bool, mesh, data_axes: Tuple[str]):
    """Data-parallel twin of ``_fused_step``: per-device capture forward +
    partial X^T X, psum-reduced over ``data_axes`` into replicated
    accumulators."""
    mods = registry(cfg)
    batch_spec = P(data_axes)

    def _step(hessians, counts, params, tokens, frontend, poison):
        caps = forward(cfg, params, tokens, frontend_embeds=frontend,
                       capture=True)["captures"]
        # batch-global sentinel: a batch is skipped on EVERY device if
        # any shard saw a non-finite capture (psum of per-shard bad
        # flags), keeping the skip decision identical to the
        # single-device reference path
        xs, ok = {}, jnp.bool_(True)
        for mod in mods:
            x, valid = get_capture(caps, mod)
            x = x * poison
            ok &= jnp.all(jnp.isfinite(x))
            xs[mod.name] = (x, valid)
        bad = jax.lax.psum(1.0 - ok.astype(jnp.float32), data_axes)
        ok = bad == 0.0
        new_h: Dict[str, jnp.ndarray] = {}
        new_c: Dict[str, jnp.ndarray] = {}
        for mod in mods:
            x, valid = xs[mod.name]
            part = xtx(x, valid, use_kernel=use_kernel)
            n = (jnp.float32(x.shape[0]) if valid is None
                 else jnp.sum(valid).astype(jnp.float32))
            new_h[mod.name] = hessians[mod.name] \
                + jnp.where(ok, jax.lax.psum(part, data_axes), 0.0)
            new_c[mod.name] = counts[mod.name] \
                + jnp.where(ok, jax.lax.psum(n, data_axes), 0.0)
        return new_h, new_c, ok

    f = jax.shard_map(_step, mesh=mesh,
                      in_specs=(P(), P(), P(), batch_spec, batch_spec, P()),
                      out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(f, donate_argnums=_donate())


def _resolve_mesh(mesh, data_axes):
    """Explicit mesh wins; else the activation context's (mesh, batch
    axes); data_axes defaults to the mesh's conventional data axes."""
    if mesh is None:
        mesh, ctx_axes = get_activation_context()
        if data_axes is None:
            data_axes = ctx_axes
    if mesh is None:
        return None, None
    if data_axes is None:
        data_axes = data_axes_for(mesh)
    if isinstance(data_axes, str):
        data_axes = (data_axes,)
    return mesh, tuple(data_axes)


def collect_hessians(cfg, params, batches: List[Dict], *,
                     use_kernel: bool = False, mesh=None,
                     data_axes=None) -> Dict[str, jnp.ndarray]:
    """Returns {module_name: H_raw = sum X^T X / n_samples} over batches.

    With a mesh (explicit or from the activation context) whose data-axis
    size divides every batch, calibration runs data-parallel; otherwise it
    falls back to the single-device reference path, and a multi-device
    mesh that fell back is counted as a ``calib.sharded`` demotion.
    """
    if not batches:
        raise ValueError("collect_hessians needs at least one calibration "
                         "batch (got an empty list)")
    mods = registry(cfg)
    mesh, data_axes = _resolve_mesh(mesh, data_axes)
    ndev = axis_size(mesh, data_axes) if mesh is not None else 1
    sharded = ndev > 1 and all(
        b["tokens"].shape[0] % ndev == 0 for b in batches)
    if ndev > 1 and not sharded:
        current_report().count("demotions", "calib.sharded")
        print(f"[robustness] calib: a batch does not divide the {ndev} "
              f"data shards; calibrating on one device")

    hessians = {m.name: jnp.zeros((m.d_in, m.d_in), jnp.float32)
                for m in mods}
    counts = {m.name: jnp.zeros((), jnp.float32) for m in mods}
    flags = []  # per-batch finite sentinels (device; fetched once at end)
    if sharded:
        step = _fused_step_sharded(cfg, use_kernel, mesh, data_axes)
        rep = NamedSharding(mesh, P())
        dp = NamedSharding(mesh, P(data_axes))
        params = jax.device_put(params, rep)
        hessians = jax.device_put(hessians, rep)
        counts = jax.device_put(counts, rep)
        # the constraint hooks inside `forward` must stay no-ops while the
        # shard_map body traces (with_sharding_constraint is a global-view
        # op); restore the caller's context afterwards
        with activation_context(None, None):
            for batch in batches:
                tokens = jax.device_put(batch["tokens"], dp)
                fe = batch.get("frontend")
                fe = jax.device_put(fe, dp) if fe is not None else None
                poison = jnp.float32(_faults.poison_scalar("calib.batch"))
                hessians, counts, ok = step(hessians, counts, params,
                                            tokens, fe, poison)
                flags.append(ok)
    else:
        step = _fused_step(cfg, use_kernel)
        for batch in batches:
            poison = jnp.float32(_faults.poison_scalar("calib.batch"))
            hessians, counts, ok = step(hessians, counts, params,
                                        batch["tokens"],
                                        batch.get("frontend"), poison)
            flags.append(ok)

    # surface skipped (poisoned) batches: the accumulators already hold
    # exactly the clean batches' sums, equal to a clean run minus the
    # skipped batches
    flags = [bool(f) for f in jax.device_get(flags)]
    skipped = flags.count(False)
    if skipped:
        rep = current_report()
        rep.count("detected", "calib.batch", skipped)
        rep.count("recovered", "calib.batch", skipped)
        print(f"[robustness] calib: skipped {skipped}/{len(batches)} "
              f"non-finite calibration batch(es)")
    if skipped == len(batches):
        raise FloatingPointError(
            "every calibration batch produced non-finite activations — "
            "no Hessian could be accumulated")

    # normalize by sample count (keeps damping scale-invariant)
    counts = jax.device_get(counts)
    return {k: hessians[k] / max(float(counts[k]), 1.0) for k in hessians}
