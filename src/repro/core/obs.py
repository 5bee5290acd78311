"""Structured Optimal Brain Surgeon — the ZipLM pruning algorithm (Alg. 1).

Given the out-side matrix ``W`` (d_in, d_out) of a layer, its calibration
Hessian ``H = 2 X^T X + lambda I`` (d_in, d_in), and equal-width contiguous
row-groups ("structures"), remove structures one at a time:

  score(S) = sum_c W[S,c]^T ((H^-1)[S,S])^-1 W[S,c]        (Eq. 2)
  delta    = -H^-1[:,S] ((H^-1)[S,S])^-1 W[S,:]            (Eq. 3)
  H^-1    <-  H^-1 - H^-1[:,S] ((H^-1)[S,S])^-1 H^-1[S,:]  (Eq. 4)

Each removal costs O(|S| d^2) instead of an O(d^3) re-inversion. Snapshots
of ``W`` are recorded at the requested sparsity levels, building the
per-layer database consumed by the SPDY search.

The inner step factors the (gs, gs) diagonal blocks of ``H^-1`` with a
symmetric Cholesky instead of a general inverse — scores come from one
triangular solve (``||L^-1 W_S||^2``), the update from two ``cho_solve``s
— and the rank-``gs`` W/Hinv downdate is expressed through a single fused
primitive (``kernels.ref.obs_downdate_ref``, or the Pallas twin
``kernels.ops.obs_downdate`` when ``use_kernel=True``) so the (d, d)
outer-product intermediate never materializes separately from the update.

``prune_structured_batched`` vmaps the whole loop over a stack of modules
with identical (d_in, d_out, group_size, levels) signature: all L layers
of a group prune simultaneously, turning ~L small matmuls per step into
one batched matmul per step (the database-construction hot path).

``prune_structured_compact`` (and its batched twin) additionally shrinks
the *working problem* as structures die: at level boundaries where the
live set has fallen below ``ratio`` of the current working size (and at
least ``min_rows`` rows remain — compaction below that is overhead), the
surviving structures are permuted to a contiguous prefix and Algorithm 1
continues on the (d_live, d_live) Hinv / (d_live, d_out) W submatrices.
The schedule is derived from the static ``levels`` grid so every segment
compiles to fixed shapes; the carried compact-slot -> original-structure
permutation maps removal orders back to global indices and scatters each
snapshot back to its original rows at level boundaries. Per-step downdate
traffic then tracks the live set (~3x less over a full 0.9^i grid run)
instead of paying the dense (d_in, d_in) cost to the last removal.

Compaction kicks in with the defaults (ratio=0.75, min_rows=64,
pad_rows=16) once a level boundary leaves <= 75% of the working
structures alive and at least 64 live rows remain — e.g. a d_ff=1024 FFN
on the 0.9^i grid compacts 9 times (1024 -> 752 -> 560 -> ... -> 80
working rows); modules smaller than min_rows never compact and behave
exactly like the plain path. Measured 1.2-1.45x db-build over the
uncompacted batched engine on a 2-core CPU container (BENCH_db.json
``db_build_compact``), growing with d_in as Hinv outgrows cache.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.linalg import cho_solve, solve_triangular


class PruneResult(NamedTuple):
    snapshots: jnp.ndarray   # (n_levels, d_in, d_out) W at each level
    errors: jnp.ndarray      # (n_levels,) cumulative squared error
    order: jnp.ndarray       # (n_remove,) structure removed at each step
    base_norm: jnp.ndarray   # ||W X||^2 = tr(W^T H_raw W) proxy (see note)
    # compacted runs only: final compact-slot -> original-structure map
    # (the permutation carried through live-set compaction); None on the
    # uncompacted paths, where slots == original indices throughout
    perm: Optional[jnp.ndarray] = None


def build_hessian(xtx: jnp.ndarray, damp_frac: float = 1e-4) -> jnp.ndarray:
    """H = 2 X^T X + lambda I with relative damping (batched over any
    leading dims)."""
    d = xtx.shape[-1]
    h = 2.0 * xtx
    diag = jnp.diagonal(h, axis1=-2, axis2=-1)
    damp = damp_frac * jnp.mean(diag, axis=-1) + 1e-12
    return h + damp[..., None, None] * jnp.eye(d, dtype=h.dtype)


def _diag_blocks(m: jnp.ndarray, gs: int) -> jnp.ndarray:
    """(d, d) -> (n, gs, gs) diagonal blocks for contiguous groups."""
    n = m.shape[0] // gs
    return m.reshape(n, gs, n, gs)[jnp.arange(n), :, jnp.arange(n), :]


def _slot_schedule(n_remove: int, levels: Tuple[int, ...]) -> jnp.ndarray:
    """levels is static: precompute which snapshot slot (if any) each step
    writes; non-level steps write to a scrap slot n_levels, so the body
    stores one (d, d_out) slice instead of re-masking the whole
    (n_levels, d, d_out) stack every step."""
    n_levels = len(levels)
    slot_np = np.full((n_remove + 1,), n_levels, np.int32)
    for idx, lvl in enumerate(levels):
        slot_np[lvl] = idx
    return jnp.asarray(slot_np)


def _select_and_downdate(W, Hinv, removed, *, gs: int, use_kernel: bool,
                         interpret: Optional[bool],
                         d_live: Optional[int] = None):
    """One Algorithm-1 step on the current working arrays: score the live
    structures, pick the cheapest, run the fused rank-gs W/Hinv downdate.

    Shared by the plain and live-set-compacted cores so the two paths are
    arithmetically identical per step. ``d_live`` statically restricts the
    downdate to the compacted live prefix (tail rows/cols are dead).

    Returns (W_new, Hinv_new, removed_new, s, err_s).
    """
    from ..kernels import ref as kref

    n = removed.shape[0]
    d_out = W.shape[1]
    if gs == 1:
        # scalar structures: the (1,1) block solve is a division —
        # no factorization needed
        diag = jnp.diagonal(Hinv)                       # (n,)
        safe = jnp.where(removed, 1.0, diag)
        scores = jnp.sum(W * W, axis=1) / safe
        scores = jnp.where(removed, jnp.inf,
                           jnp.maximum(scores, 0.0))
        s = jnp.argmin(scores)
        HcolS = jax.lax.dynamic_slice_in_dim(Hinv, s, 1, 1)  # (d, 1)
        WS = jax.lax.dynamic_slice_in_dim(W, s, 1, 0)   # (1, d_out)
        inv_s = 1.0 / safe[s]
        KsWS = WS * inv_s                               # (1, d_out)
        KsHcolT = HcolS.T * inv_s                       # (1, d_in)
    else:
        blocks = _diag_blocks(Hinv, gs)                 # (n, gs, gs)
        eye = jnp.eye(gs, dtype=jnp.float32)
        safe = jnp.where(removed[:, None, None], eye[None], blocks)
        # symmetric PD blocks: Cholesky + triangular solve, not inv
        Lc = jnp.linalg.cholesky(safe)                  # (n, gs, gs)
        Wb = W.reshape(n, gs, d_out)
        V = solve_triangular(Lc, Wb, lower=True)        # L^-1 W_S
        scores = jnp.sum(V * V, axis=(1, 2))
        scores = jnp.where(removed, jnp.inf,
                           jnp.maximum(scores, 0.0))
        s = jnp.argmin(scores)
        HcolS = jax.lax.dynamic_slice_in_dim(Hinv, s * gs, gs, 1)
        WS = jax.lax.dynamic_slice_in_dim(W, s * gs, gs, 0)
        chol_s = (jax.lax.dynamic_slice_in_dim(Lc, s, 1, 0)[0], True)
        KsWS = cho_solve(chol_s, WS)                    # (gs, d_out)
        KsHcolT = cho_solve(chol_s, HcolS.T)            # (gs, d_in)

    removed = removed.at[s].set(True)

    # paper: explicitly re-apply the overall mask — fp downdate creep
    # otherwise repopulates previously-removed rows over many steps
    if gs == 1:
        row_keep = (~removed).astype(jnp.float32)
    else:
        row_keep = jnp.repeat(~removed, gs).astype(jnp.float32)
    if use_kernel:
        from ..kernels import ops as kops
        W_new, Hinv_new = kops.obs_downdate(
            W, Hinv, HcolS, KsWS, KsHcolT, row_keep, interpret=interpret,
            d_live=d_live)
    else:
        W_new, Hinv_new = kref.obs_downdate_ref(
            W, Hinv, HcolS, KsWS, KsHcolT, row_keep, d_live=d_live)
    return W_new, Hinv_new, removed, s, scores[s]


def _prune_core(W: jnp.ndarray, Hinv: jnp.ndarray, *, group_size: int,
                n_remove: int, levels: Tuple[int, ...],
                use_kernel: bool = False,
                interpret: Optional[bool] = None) -> PruneResult:
    """Algorithm 1 body — un-jitted so it can be vmapped over a module
    stack (see prune_structured / prune_structured_batched)."""
    gs = group_size
    d_in, d_out = W.shape
    n = d_in // gs
    n_levels = len(levels)

    W = W.astype(jnp.float32)
    Hinv = Hinv.astype(jnp.float32)

    slot_arr = _slot_schedule(n_remove, levels)

    snaps0 = jnp.zeros((n_levels + 1, d_in, d_out), jnp.float32)
    errs0 = jnp.zeros((n_levels + 1,), jnp.float32)
    if levels[0] == 0:  # dense snapshot
        snaps0 = snaps0.at[0].set(W)

    def body(i, carry):
        W, Hinv, removed, cum_err, snaps, errs, order = carry
        W_new, Hinv_new, removed, s, err = _select_and_downdate(
            W, Hinv, removed, gs=gs, use_kernel=use_kernel,
            interpret=interpret)
        cum_err = cum_err + err
        order = order.at[i].set(s.astype(jnp.int32))

        # snapshot if (i+1) matches a level (scrap slot otherwise)
        slot = slot_arr[i + 1]
        snaps = jax.lax.dynamic_update_slice(
            snaps, W_new[None], (slot, jnp.int32(0), jnp.int32(0)))
        errs = errs.at[slot].set(cum_err)
        return (W_new, Hinv_new, removed, cum_err, snaps, errs, order)

    init = (W, Hinv, jnp.zeros((n,), bool), jnp.zeros((), jnp.float32),
            snaps0, errs0, jnp.zeros((n_remove,), jnp.int32))
    _, _, _, _, snaps, errs, order = jax.lax.fori_loop(
        0, n_remove, body, init)

    return PruneResult(snapshots=snaps[:n_levels], errors=errs[:n_levels],
                       order=order, base_norm=jnp.zeros(()))


def _pad_structs(live: int, gs: int, pad_rows: int, cap: int) -> int:
    """Smallest structure count >= live whose row count (structs * gs) is
    a pad_rows multiple (TPU lane alignment for the compacted working
    arrays), capped at the current working size."""
    if pad_rows <= 1:
        return live
    for w in range(live, cap + 1):
        if (w * gs) % pad_rows == 0:
            return w
    return live


def _compaction_schedule(n: int, gs: int, n_remove: int,
                         levels: Tuple[int, ...], *, ratio: float = 0.75,
                         min_rows: int = 64, pad_rows: int = 16
                         ) -> List[Tuple[int, int, int, int]]:
    """Static segment plan for a live-set-compacted Algorithm-1 run.

    Returns ``[(start, end, work_n, live_n), ...]`` covering steps
    ``[0, n_remove)``: during a segment the working arrays hold ``work_n``
    structure slots, of which the first ``live_n`` were live at segment
    entry — the padded tail slots are statically dead (the masked tail of
    the ``d_live`` downdate). Compaction points sit on level boundaries
    (so snapshots scatter back exactly there) where the live set has
    dropped below ``ratio`` of the current working size and at least
    ``min_rows`` rows survive — compacting smaller problems costs more in
    permutes/dispatch than the downdate saves.
    """
    segs: List[Tuple[int, int, int, int]] = []
    start, work_n, live_n = 0, n, n
    for lv in levels:
        if lv <= start or lv >= n_remove:
            continue
        live = n - lv
        if live * gs < min_rows or live > ratio * work_n:
            continue
        new_work = _pad_structs(live, gs, pad_rows, cap=work_n)
        if new_work >= work_n:
            continue
        segs.append((start, lv, work_n, live_n))
        start, work_n, live_n = lv, new_work, live
    segs.append((start, n_remove, work_n, live_n))
    return segs


def _prune_core_compact(W: jnp.ndarray, Hinv: jnp.ndarray, *,
                        group_size: int, n_remove: int,
                        levels: Tuple[int, ...], use_kernel: bool = False,
                        interpret: Optional[bool] = None,
                        ratio: float = 0.75, min_rows: int = 64,
                        pad_rows: int = 16) -> PruneResult:
    """Live-set-compacted Algorithm 1: identical pruning decisions to
    ``_prune_core`` (the per-step math is shared via
    ``_select_and_downdate``), but between the static segments of
    ``_compaction_schedule`` the surviving structures are permuted to a
    contiguous prefix and the loop continues on the shrunk submatrices.

    Removal orders are recorded through the carried compact-slot ->
    original-structure map, and each snapshot is scattered back to its
    original row positions at the segment boundary, so the returned
    PruneResult is layout-identical to the uncompacted one.
    """
    gs = group_size
    d_in, d_out = W.shape
    n = d_in // gs
    n_levels = len(levels)

    W = W.astype(jnp.float32)
    Hinv = Hinv.astype(jnp.float32)

    segs = _compaction_schedule(n, gs, n_remove, levels, ratio=ratio,
                                min_rows=min_rows, pad_rows=pad_rows)
    slot_arr = _slot_schedule(n_remove, levels)

    full_snaps = jnp.zeros((n_levels, d_in, d_out), jnp.float32)
    if levels[0] == 0:  # dense snapshot
        full_snaps = full_snaps.at[0].set(W)
    errs = jnp.zeros((n_levels + 1,), jnp.float32)
    order = jnp.zeros((n_remove,), jnp.int32)
    orig_idx = jnp.arange(n, dtype=jnp.int32)
    removed = jnp.zeros((n,), bool)
    cum_err = jnp.zeros((), jnp.float32)

    for seg_i, (start, end, work_n, live_n) in enumerate(segs):
        if seg_i:
            # stable sort keeps the live structures in their current
            # relative order (argmin tie-breaks match the full path) and
            # moves them to the prefix; the first work_n slots are the
            # live set plus the statically-dead padded tail
            cur_n = removed.shape[0]
            perm = jnp.argsort(removed, stable=True)[:work_n]
            orig_idx = orig_idx[perm]
            removed = removed[perm]
            W = W.reshape(cur_n, gs, d_out)[perm].reshape(-1, d_out)
            H4 = Hinv.reshape(cur_n, gs, cur_n, gs)
            Hinv = H4[perm][:, :, perm].reshape(work_n * gs, work_n * gs)

        d_work = work_n * gs
        d_live = live_n * gs if live_n < work_n else None
        seg_snaps = jnp.zeros((n_levels + 1, d_work, d_out), jnp.float32)

        def body(i, carry, _dl=d_live, _oi=orig_idx):
            W, Hinv, removed, cum_err, snaps, errs, order = carry
            W_new, Hinv_new, removed, s, err = _select_and_downdate(
                W, Hinv, removed, gs=gs, use_kernel=use_kernel,
                interpret=interpret, d_live=_dl)
            cum_err = cum_err + err
            order = order.at[i].set(_oi[s])
            slot = slot_arr[i + 1]
            snaps = jax.lax.dynamic_update_slice(
                snaps, W_new[None], (slot, jnp.int32(0), jnp.int32(0)))
            errs = errs.at[slot].set(cum_err)
            return (W_new, Hinv_new, removed, cum_err, snaps, errs, order)

        W, Hinv, removed, cum_err, seg_snaps, errs, order = \
            jax.lax.fori_loop(start, end, body,
                              (W, Hinv, removed, cum_err, seg_snaps, errs,
                               order))

        # scatter this segment's level snapshots back to original rows
        # (rows of structures compacted away in earlier segments stay 0)
        row_idx = (orig_idx[:, None] * gs
                   + jnp.arange(gs, dtype=jnp.int32)[None, :]).reshape(-1)
        for j, lvl in enumerate(levels):
            if start < lvl <= end:
                scat = jnp.zeros((d_in, d_out), jnp.float32
                                 ).at[row_idx].set(seg_snaps[j])
                full_snaps = full_snaps.at[j].set(scat)

    return PruneResult(snapshots=full_snaps, errors=errs[:n_levels],
                       order=order, base_norm=jnp.zeros(()), perm=orig_idx)


_COMPACT_STATICS = ("group_size", "n_remove", "levels", "use_kernel",
                    "interpret", "ratio", "min_rows", "pad_rows")


def _named(name: str):
    """Name the function that ``jax.jit`` wraps: its executable is then
    ``jit_<name>`` in a profiler trace (every Algorithm-1 executable's
    name starts with ``jit_prune_obs``)."""
    def deco(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn
    return deco


@functools.partial(jax.jit, static_argnames=_COMPACT_STATICS)
@_named("prune_obs_compact")
def prune_structured_compact(W: jnp.ndarray, Hinv: jnp.ndarray, *,
                             group_size: int, n_remove: int,
                             levels: Tuple[int, ...],
                             use_kernel: bool = False,
                             interpret: Optional[bool] = None,
                             ratio: float = 0.75, min_rows: int = 64,
                             pad_rows: int = 16) -> PruneResult:
    """Live-set-compacted Algorithm 1 (see ``_prune_core_compact``).

    Same contract as ``prune_structured`` — identical pruning orders and
    layout-identical snapshots — with per-step cost tracking the live set.
    """
    return _prune_core_compact(W, Hinv, group_size=group_size,
                               n_remove=n_remove, levels=levels,
                               use_kernel=use_kernel, interpret=interpret,
                               ratio=ratio, min_rows=min_rows,
                               pad_rows=pad_rows)


@functools.partial(jax.jit, static_argnames=_COMPACT_STATICS)
@_named("prune_obs_batched_compact")
def prune_structured_batched_compact(W: jnp.ndarray, Hinv: jnp.ndarray, *,
                                     group_size: int, n_remove: int,
                                     levels: Tuple[int, ...],
                                     use_kernel: bool = False,
                                     interpret: Optional[bool] = None,
                                     ratio: float = 0.75,
                                     min_rows: int = 64,
                                     pad_rows: int = 16) -> PruneResult:
    """Vmapped live-set-compacted Algorithm 1 over a stacked module group
    (the compacted twin of ``prune_structured_batched``): the whole group
    compacts in lockstep on the shared static schedule."""
    fn = functools.partial(_prune_core_compact, group_size=group_size,
                           n_remove=n_remove, levels=levels,
                           use_kernel=use_kernel, interpret=interpret,
                           ratio=ratio, min_rows=min_rows,
                           pad_rows=pad_rows)
    return jax.vmap(fn)(W, Hinv)


@functools.partial(jax.jit, static_argnames=("group_size", "n_remove",
                                             "levels", "use_kernel",
                                             "interpret"))
@_named("prune_obs")
def prune_structured(W: jnp.ndarray, Hinv: jnp.ndarray, *, group_size: int,
                     n_remove: int, levels: Tuple[int, ...],
                     use_kernel: bool = False,
                     interpret: Optional[bool] = None) -> PruneResult:
    """Run Algorithm 1, snapshotting W after `levels[i]` removals.

    levels must be ascending; level 0 (dense) is always implicit in
    snapshots[0] if levels[0] == 0.
    """
    return _prune_core(W, Hinv, group_size=group_size, n_remove=n_remove,
                       levels=levels, use_kernel=use_kernel,
                       interpret=interpret)


@functools.lru_cache(maxsize=32)
def _sharded_prune_jit(mesh, axes: Tuple[str, ...], group_size: int,
                       n_remove: int, levels: Tuple[int, ...],
                       use_kernel: bool, interpret: Optional[bool],
                       compact: bool, ratio: float, min_rows: int,
                       pad_rows: int):
    """Compiled once per (mesh, axes, statics): shard_map of the vmapped
    Algorithm-1 core over the leading module axis, with ragged module
    counts padded up to the device count inside the jit (padded lanes
    replicate module 0 and are sliced off after the gather)."""
    from jax.sharding import PartitionSpec as P

    from ..distributed.sharding import axis_size, pad_leading

    if compact:
        core = functools.partial(
            _prune_core_compact, group_size=group_size, n_remove=n_remove,
            levels=levels, use_kernel=use_kernel, interpret=interpret,
            ratio=ratio, min_rows=min_rows, pad_rows=pad_rows)
    else:
        core = functools.partial(
            _prune_core, group_size=group_size, n_remove=n_remove,
            levels=levels, use_kernel=use_kernel, interpret=interpret)

    def _body(W, Hinv):
        # every device prunes its module shard independently — module
        # groups are embarrassingly parallel, so the compiled schedule
        # carries ZERO collectives (budgeted by repro.analysis)
        res = jax.vmap(core)(W, Hinv)
        return res.snapshots, res.errors, res.order

    spec = P(axes)
    ndev = axis_size(mesh, axes)
    f = jax.shard_map(_body, mesh=mesh, in_specs=(spec, spec),
                      out_specs=(spec, spec, spec), check_vma=False)

    def prune_obs_sharded(W, Hinv):
        b = W.shape[0]
        snaps, errs, order = f(pad_leading(W, ndev),
                               pad_leading(Hinv, ndev))
        return snaps[:b], errs[:b], order[:b]

    return jax.jit(prune_obs_sharded)


def prune_structured_sharded(W: jnp.ndarray, Hinv: jnp.ndarray, *,
                             mesh, axes, group_size: int, n_remove: int,
                             levels: Tuple[int, ...],
                             use_kernel: bool = False,
                             interpret: Optional[bool] = None,
                             compact: bool = False, ratio: float = 0.75,
                             min_rows: int = 64, pad_rows: int = 16
                             ) -> PruneResult:
    """Device-parallel twin of ``prune_structured_batched[_compact]``:
    the stacked module group is sharded over ``mesh``'s ``axes`` via
    ``shard_map``, each device running the identical vmapped Algorithm-1
    core on its module shard.  Lanes never interact, so the results are
    bit-exactly those of the single-device vmapped reference (asserted
    by tests/test_sharded_db.py on a forced 2-device host); the d_live
    prefix of the compact path is a static per-segment constant and
    shards unchanged.  Module counts that do not divide the device count
    are padded with replicas of module 0 and sliced off after.
    """
    if isinstance(axes, str):
        axes = (axes,)
    jitted = _sharded_prune_jit(mesh, tuple(axes), group_size, n_remove,
                                tuple(levels), use_kernel, interpret,
                                compact, ratio, min_rows, pad_rows)
    snaps, errs, order = jitted(W, Hinv)
    return PruneResult(snapshots=snaps, errors=errs, order=order,
                       base_norm=jnp.zeros(()))


@functools.partial(jax.jit, static_argnames=("group_size", "n_remove",
                                             "levels", "use_kernel",
                                             "interpret"))
@_named("prune_obs_batched")
def prune_structured_batched(W: jnp.ndarray, Hinv: jnp.ndarray, *,
                             group_size: int, n_remove: int,
                             levels: Tuple[int, ...],
                             use_kernel: bool = False,
                             interpret: Optional[bool] = None
                             ) -> PruneResult:
    """Vmapped Algorithm 1 over a stacked module group.

    W: (L, d_in, d_out), Hinv: (L, d_in, d_in) — every layer of the group
    runs the same fori_loop in lockstep; one batched matmul per step
    replaces L serial ones. Returns a PruneResult whose fields carry a
    leading L dim.
    """
    fn = functools.partial(_prune_core, group_size=group_size,
                           n_remove=n_remove, levels=levels,
                           use_kernel=use_kernel, interpret=interpret)
    return jax.vmap(fn)(W, Hinv)


def module_drop_error(W: jnp.ndarray, H: jnp.ndarray) -> jnp.ndarray:
    """||W X||^2 = tr(W^T H_raw W) with H_raw = X^T X (module-drop error,
    and the denominator of the SPDY prior p_s)."""
    Wf = W.astype(jnp.float32)
    return jnp.einsum("ic,ij,jc->", Wf, H.astype(jnp.float32), Wf)


@jax.jit
def module_drop_errors(W: jnp.ndarray, H: jnp.ndarray) -> jnp.ndarray:
    """Batched module_drop_error: (L, d_in, d_out) x (L, d_in, d_in) -> (L,)."""
    return jax.vmap(module_drop_error)(W, H)


def optimal_update_bruteforce(W, H, rows) -> jnp.ndarray:
    """Reference: solve argmin ||W'X - WX|| with W'[rows]=0 directly
    (lstsq on the remaining rows). Used by tests as the oracle."""
    d_in = W.shape[0]
    keep = np.setdiff1d(np.arange(d_in), np.asarray(rows))
    Hkk = np.asarray(H, np.float64)[np.ix_(keep, keep)]
    Hkf = np.asarray(H, np.float64)[np.ix_(keep, np.arange(d_in))]
    # W'_keep = argmin_Z || [Z;0] X - W X ||^2  =>  Hkk Z = Hk: W
    Z = np.linalg.solve(Hkk, Hkf @ np.asarray(W, np.float64))
    out = np.zeros_like(np.asarray(W, np.float64))
    out[keep] = Z
    return jnp.asarray(out, jnp.float32)
