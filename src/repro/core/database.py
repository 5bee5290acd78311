"""Per-layer pruning database (paper §3.2): for every prunable module, the
ZipLM-updated weight snapshot, squared error, and SPDY prior at each
sparsity level — produced in a single run per module, exploiting the
one-structure-at-a-time nature of Algorithm 1.

Construction is batched: modules are grouped by identical
``(group_size, n_structures, d_out, levels)`` signature — all L attention
layers share one shape, all L FFN layers another — and each group runs
Algorithm 1 under ``jax.vmap`` (obs.prune_structured_batched), so
``build_database`` issues a handful of compiled calls instead of ~2L.
``batched=False`` keeps the serial per-module path as the equivalence
reference.

``SnapshotCache`` keeps the stacked snapshots device-resident so SPDY's
per-candidate ``apply_assignment`` is one gather + jitted stitch per
module kind instead of ~|modules| host->device transfers.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..robustness import faults as _faults
from ..robustness.healing import damp_schedule
from ..robustness.report import current_report
from ..runtime.device import free_device_bytes
from .obs import (build_hessian, module_drop_error, module_drop_errors,
                  prune_structured, prune_structured_batched,
                  prune_structured_batched_compact, prune_structured_compact,
                  prune_structured_sharded)
from .structures import (UNITS, PrunableModule, get_matrix, level_grid,
                         registry, set_matrix)

# damping-escalation ladder: retries beyond the caller's damp, each one
# decade up (damp * 10**k) — bounded so a hopeless Hessian fails loudly
DAMP_RETRIES = 4

# share of the device's free bytes one vmapped Algorithm-1 chunk may take
DEVICE_MEM_FRACTION = 0.8


def module_bytes(d_in: int, d_out: int, n_levels: int) -> int:
    """Device bytes one module adds to a vmapped Algorithm-1 chunk: fp32
    W and Hinv in, carried and updated (x3), and the (n_levels + 1)-deep
    fp32 snapshot stack carried through the loop and returned (x4). An
    upper bound on what the TPU compiler reports for GPT2-small's FFN
    (1.71 vs 1.62 GiB per module, tests/test_tpu_compile.py)."""
    return 4 * (3 * d_in * (d_in + d_out)
                + 4 * (n_levels + 1) * d_in * d_out)


def chunk_size(n_mods: int, per_module: int, max_batch: int,
               n_shards: int = 1, free: Optional[int] = None) -> int:
    """Modules per vmapped chunk: as many as ``DEVICE_MEM_FRACTION`` of
    the free device bytes (``free``, read from the device when None)
    holds on each of ``n_shards`` devices, at most ``max_batch``, spread
    evenly so that every chunk of a group compiles to one shape. Without
    a reported device limit (the CPU backend) only ``max_batch`` binds."""
    if free is None:
        free = free_device_bytes()
    cap = max_batch
    if free is not None:
        fit = int(DEVICE_MEM_FRACTION * free) // max(per_module, 1)
        cap = max(1, min(max_batch, fit * n_shards))
    n_chunks = -(-n_mods // cap)
    return -(-n_mods // n_chunks)


def _prune_healed(prune_fn, Ws, Hraw, *, group_size, n_remove, levels,
                  use_kernel, damp):
    """Run Algorithm 1 with numerical self-healing; returns host arrays
    ``(snaps16, errs, orders)`` (with the caller's leading batch dim, if
    any).

    * non-finite ``PruneResult`` (snapshots or errors) -> rebuild
      H/Hinv with the next damping rung and retry, bounded at
      ``DAMP_RETRIES`` (the ``obs.cholesky`` fault site poisons Hinv
      right before the prune, exercising exactly this path);
    * a raising kernel path (Pallas trace/compile/runtime failure
      surfacing at the prune call) -> one ``use_kernel=False`` retry at
      the same rung (the outer rung of the kernels.ops ref-fallback
      ladder — device-side failures inside a traced fori_loop cannot be
      caught at the op boundary).

    Rung 0 is bit-identical to the un-healed code: same damp, and the
    finite check reads values that were going to be fetched anyway.

    The inverse is taken on the host in float64 and Algorithm 1 runs at
    full fp32 matmul precision: on a v5e the TPU's own fp32 inverse of
    GPT2-small's attention Hessians was off by up to 8e-3 relative, and
    the last removal steps of those modules went non-finite at the
    requested damping; from the float64 inverse every module stayed
    finite.
    """
    rep = current_report()
    uk = use_kernel
    rungs = damp_schedule(damp, DAMP_RETRIES)
    attempt = 0
    while True:
        H = build_hessian(Hraw, rungs[attempt])
        # sync: once per chunk per damping rung, outside the removal loop
        Hinv = jnp.asarray(np.linalg.inv(np.asarray(H, np.float64)),
                           jnp.float32)
        Hinv = _faults.poison_array("obs.cholesky", Hinv)
        try:
            with jax.default_matmul_precision("highest"):
                res = prune_fn(Ws, Hinv, group_size=group_size,
                               n_remove=n_remove, levels=levels,
                               use_kernel=uk)
            # sync: DB materialization — the float16 snapshots are
            # fetched exactly once per chunk per damping rung, and the
            # finite check below reads values headed to host anyway
            snaps16 = np.asarray(res.snapshots.astype(jnp.float16))
            errs = np.asarray(res.errors)   # sync: same fetch
            orders = np.asarray(res.order)  # sync: same fetch
        except Exception as e:
            if not uk or isinstance(e, KeyboardInterrupt):
                raise
            rep.trip("kernel.pallas", reason=f"obs prune: {e!r}")
            uk = False
            continue
        if np.isfinite(errs).all() and np.isfinite(snaps16).all():
            if attempt:
                rep.count("recovered", "obs.cholesky")
                print(f"[robustness] obs: healed non-finite prune at "
                      f"damp={rungs[attempt]:g} (rung {attempt})")
            return snaps16, errs, orders
        rep.count("detected", "obs.cholesky")
        rep.count("retries", "obs.cholesky")
        attempt += 1
        if attempt >= len(rungs):
            raise FloatingPointError(
                f"OBS prune stayed non-finite through the damping ladder "
                f"{rungs} — calibration Hessian is unusable")


@dataclass
class ModuleDB:
    mod: PrunableModule
    levels: np.ndarray       # structures removed, ascending; last = full drop
    snapshots: np.ndarray    # (n_levels, d_in, d_out) float16 (host)
    errors: np.ndarray       # cumulative sq. error per level (raw-H scale)
    priors: np.ndarray       # p_s in [0, 1]; 1.0 = module dropped
    base_norm: float
    order: np.ndarray = None  # structure removed at step i (shrink needs it)

    def weights_at(self, removed: int) -> np.ndarray:
        i = int(np.searchsorted(self.levels, removed))
        return self.snapshots[i]

    def kept_structures(self, removed: int) -> np.ndarray:
        """Sorted indices of structures remaining at a level."""
        gone = set(np.asarray(self.order[:removed]).tolist())
        return np.asarray([g for g in range(self.mod.n_structures)
                           if g not in gone])


def _finish_module_db(mod: PrunableModule, levels: np.ndarray,
                      snapshots16: np.ndarray, errors_raw: np.ndarray,
                      base: float, order: np.ndarray) -> ModuleDB:
    """Host-side post-processing shared by the serial and batched paths."""
    errs = np.asarray(errors_raw, np.float64) / 2.0  # H had the paper's 2x
    errs[-1] = base if levels[-1] == mod.n_structures else errs[-1]
    with np.errstate(divide="ignore", invalid="ignore"):
        priors = np.sqrt(np.maximum(errs, 0.0) / max(base, 1e-30))
    priors = np.clip(np.nan_to_num(priors, nan=1.0), 0.0, 1.0)
    return ModuleDB(mod=mod, levels=np.asarray(levels),
                    snapshots=np.asarray(snapshots16, np.float16),
                    errors=errs, priors=priors, base_norm=base,
                    order=np.asarray(order))


def build_module_db(cfg, params, mod: PrunableModule, h_raw,
                    damp: float = 1e-4, compact: bool = False) -> ModuleDB:
    W = get_matrix(cfg, params, mod).astype(jnp.float32)
    levels = level_grid(mod)
    prune = prune_structured_compact if compact else prune_structured
    snaps16, errs, orders = _prune_healed(
        prune, W, h_raw, group_size=mod.group_size,
        n_remove=max(levels), levels=tuple(levels), use_kernel=False,
        damp=damp)
    base = float(module_drop_error(W, h_raw))
    return _finish_module_db(mod, np.asarray(levels), snaps16, errs,
                             base, orders)


def group_modules(cfg, params, mods: List[PrunableModule]
                  ) -> List[Tuple[tuple, List[PrunableModule]]]:
    """Group modules whose Algorithm-1 run compiles to the same program:
    identical (group_size, n_structures, d_out, levels)."""
    groups: Dict[tuple, List[PrunableModule]] = {}
    for mod in mods:
        d_out = get_matrix(cfg, params, mod).shape[1]
        key = (mod.group_size, mod.n_structures, d_out,
               tuple(level_grid(mod)))
        groups.setdefault(key, []).append(mod)
    return list(groups.items())


def build_database(cfg, params, hessians: Dict[str, jnp.ndarray], *,
                   damp: float = 1e-4, verbose: bool = False,
                   batched: bool = True, use_kernel: bool = False,
                   compact: bool = False, max_batch: int = 16,
                   mesh=None, shard_axes=None) -> Dict[str, ModuleDB]:
    """Modules of one shape group run under a single vmap in chunks sized
    from the device's free memory and ``module_bytes`` (`chunk_size`),
    at most ``max_batch`` per chunk, instead of the whole group (L, or
    L*E for MoE) at once.

    ``compact=True`` routes Algorithm 1 through the live-set-compacted
    core (obs.prune_structured[_batched]_compact): identical pruning
    orders, snapshots scattered back to original row layout before
    ``_finish_module_db``, ~the live set's bandwidth instead of the dense
    (d_in, d_in) downdate per step.

    ``mesh`` (with >1 device over ``shard_axes``, default the mesh's
    data axes) shards each vmapped chunk across devices via
    obs.prune_structured_sharded — module groups are embarrassingly
    parallel, so results stay bit-identical to the single-device build
    (the equivalence reference, and the demotion target of the
    ``db.sharded_group`` circuit breaker)."""
    from ..distributed.sharding import axis_size, data_axes_for
    mods = registry(cfg)
    db: Dict[str, ModuleDB] = {}
    rep = current_report()
    if mesh is not None and shard_axes is None:
        shard_axes = data_axes_for(mesh)
    n_shards = axis_size(mesh, shard_axes) if mesh is not None else 1
    if not batched:
        for mod in mods:
            db[mod.name] = build_module_db(cfg, params, mod,
                                           hessians[mod.name], damp,
                                           compact=compact)
    else:
        prune_batched = (prune_structured_batched_compact if compact
                         else prune_structured_batched)
        prune_sharded = functools.partial(
            prune_structured_sharded, mesh=mesh, axes=shard_axes,
            compact=compact)
        for key, gmods in group_modules(cfg, params, mods):
            gs, n, d_out, levels = key
            step = chunk_size(len(gmods),
                              module_bytes(gmods[0].d_in, d_out,
                                           len(levels)),
                              max_batch, n_shards)
            for lo in range(0, len(gmods), step):
                chunk = gmods[lo:lo + step]
                Ws = jnp.stack([get_matrix(cfg, params, m)
                                .astype(jnp.float32) for m in chunk])
                Hraw = jnp.stack([jnp.asarray(hessians[m.name],
                                              jnp.float32) for m in chunk])
                # one host transfer per chunk (float16), not per module;
                # _prune_healed retries the chunk up the damping ladder
                # (and without the kernel) on non-finite results
                snaps16 = None
                if n_shards > 1 and not rep.breaker_open("db.sharded_group"):
                    try:
                        _faults.hit("db.sharded_group")
                        snaps16, errs, orders = _prune_healed(
                            prune_sharded, Ws, Hraw, group_size=gs,
                            n_remove=max(levels), levels=levels,
                            use_kernel=use_kernel, damp=damp)
                    except KeyboardInterrupt:
                        raise
                    except Exception as e:
                        # demotion rung: sharded build -> single-device
                        # vmapped build (the bit-exact reference), once
                        # per report via the circuit breaker
                        rep.trip("db.sharded_group",
                                 reason=f"sharded db chunk: {e!r}")
                        snaps16 = None
                if snaps16 is None:
                    snaps16, errs, orders = _prune_healed(
                        prune_batched, Ws, Hraw, group_size=gs,
                        n_remove=max(levels), levels=levels,
                        use_kernel=use_kernel, damp=damp)
                bases = module_drop_errors(Ws, Hraw)
                # sync: one transfer per chunk (see _prune_healed note)
                bases = np.asarray(bases, np.float64)
                lv = np.asarray(levels)  # sync: host level grid, no device
                for i, m in enumerate(chunk):
                    db[m.name] = _finish_module_db(
                        m, lv, snaps16[i], errs[i],
                        float(bases[i]),  # sync: bases already on host
                        orders[i])
        db = {m.name: db[m.name] for m in mods}  # registry order
    if verbose:
        for name, mdb in db.items():
            p = mdb.priors
            print(f"  db {name}: levels={len(p)} "
                  f"p[1]={p[min(1, len(p)-1)]:.4f} p[-2]={p[-2]:.4f}")
    return db


# ----------------------------------------------------------------------
# device-resident snapshot cache for SPDY evaluation
# ----------------------------------------------------------------------

# each kind's out-side matrix location + stitch index arity come from its
# PruneUnit (structures.py) — the cache stays kind-agnostic
_PARAM_PATH = {kind: u.param_path for kind, u in UNITS.items()}


def _stitch_layers_impl(leaf, snaps, lvl_idx, layer_idx):
    """leaf: (L, d_in, d_out) param stack; snaps: (M, n_lvl, d_in, d_out)."""
    w = snaps[jnp.arange(snaps.shape[0]), lvl_idx].astype(leaf.dtype)
    return leaf.at[layer_idx].set(w)


def _stitch_experts_impl(leaf, snaps, lvl_idx, layer_idx, expert_idx):
    """leaf: (L, E, d_in, d_out); snaps: (M, n_lvl, d_in, d_out)."""
    w = snaps[jnp.arange(snaps.shape[0]), lvl_idx].astype(leaf.dtype)
    return leaf.at[layer_idx, expert_idx].set(w)


_stitch_layers = jax.jit(_stitch_layers_impl)
_stitch_experts = jax.jit(_stitch_experts_impl)

# population-batched stitches: lvl_idx gains a leading (P,) axis; the leaf
# is broadcast on the first group of a kind and carried batched (P, L, ...)
# when a later group (heterogeneous level grids) stitches into it again
_stitch_layers_pop = jax.jit(
    jax.vmap(_stitch_layers_impl, in_axes=(None, None, 0, None)))
_stitch_layers_pop2 = jax.jit(
    jax.vmap(_stitch_layers_impl, in_axes=(0, None, 0, None)))
_stitch_experts_pop = jax.jit(
    jax.vmap(_stitch_experts_impl, in_axes=(None, None, 0, None, None)))
_stitch_experts_pop2 = jax.jit(
    jax.vmap(_stitch_experts_impl, in_axes=(0, None, 0, None, None)))


class SnapshotCache:
    """Device-resident stacked database snapshots with a jitted stitch.

    Built once from a database; ``apply`` assembles any level assignment
    as one gather + scatter per module kind, entirely on device — the hot
    path of SPDY's ~200 eval-with-loss candidates, which previously
    round-tripped every module's float16 snapshot through the host.
    """

    def __init__(self, cfg, db: Dict[str, ModuleDB]):
        self.cfg = cfg
        # modules stack per (kind, level grid): modules of one kind can
        # carry different grids (heterogeneous configs / hand-built DBs),
        # and a shared searchsorted over the wrong grid would stitch the
        # wrong snapshot index — each grid gets its own gather + scatter
        self._groups: Dict[tuple, dict] = {}
        by_key: Dict[tuple, List[ModuleDB]] = {}
        for mdb in db.values():
            # sync: mdb.levels is host metadata (numpy), built once
            key = (mdb.mod.kind, tuple(np.asarray(mdb.levels).tolist()))
            by_key.setdefault(key, []).append(mdb)
        for (kind, levels), mdbs in by_key.items():
            self._groups[(kind, levels)] = {
                "kind": kind,
                "names": [m.mod.name for m in mdbs],
                "levels": np.asarray(levels),  # sync: host metadata
                "layer_idx": jnp.asarray([m.mod.layer for m in mdbs],
                                         jnp.int32),
                "expert_idx": jnp.asarray([m.mod.expert for m in mdbs],
                                          jnp.int32),
                # (M, n_levels, d_in, d_out) float16, uploaded once
                "snaps": jnp.asarray(np.stack([m.snapshots for m in mdbs])),
            }

    def covers(self, assignment: Dict[str, int]) -> bool:
        return all(n in assignment
                   for e in self._groups.values() for n in e["names"])

    def to_device(self, device) -> "SnapshotCache":
        """A replica of the cache with every device-resident array
        (snapshot stacks, index vectors) committed to ``device``.  JAX
        refuses computations over mixed committed placements, so
        per-device SPDY population placement gives each device its own
        replica; host metadata is shared."""
        new = object.__new__(SnapshotCache)
        new.cfg = self.cfg
        new._groups = {}
        for key, e in self._groups.items():
            ne = dict(e)
            for k in ("layer_idx", "expert_idx", "snaps"):
                ne[k] = jax.device_put(e[k], device)
            new._groups[key] = ne
        return new

    def apply(self, params, assignment: Dict[str, int]):
        """Device-side equivalent of apply_assignment for a full
        per-module level assignment."""
        new = jax.tree.map(lambda a: a, params)  # shallow-ish copy of dicts
        layers = new["layers"]
        for e in self._groups.values():
            kind = e["kind"]
            lvl = np.asarray([assignment[n] for n in e["names"]])
            lvl_idx = jnp.asarray(np.searchsorted(e["levels"], lvl),
                                  jnp.int32)
            grp, leaf_key = _PARAM_PATH[kind]
            leaf = layers[grp][leaf_key]
            if UNITS[kind].per_expert:
                leaf = _stitch_experts(leaf, e["snaps"], lvl_idx,
                                       e["layer_idx"], e["expert_idx"])
            else:
                leaf = _stitch_layers(leaf, e["snaps"], lvl_idx,
                                      e["layer_idx"])
            layers[grp][leaf_key] = leaf
        return new

    def stitched_bytes(self, params) -> int:
        """Bytes of the leaves `apply_batched` copies per candidate."""
        paths = {_PARAM_PATH[e["kind"]] for e in self._groups.values()}
        return sum(int(params["layers"][g][k].nbytes) for g, k in paths)

    def batch_axes(self, params):
        """``jax.vmap`` in_axes tree for an `apply_batched` result: 0 on
        every stitched leaf, None (broadcast) everywhere else."""
        axes = jax.tree.map(lambda _: None, params)
        for e in self._groups.values():
            grp, leaf_key = _PARAM_PATH[e["kind"]]
            axes["layers"][grp][leaf_key] = 0
        return axes

    def apply_batched(self, params, assignments):
        """Stitch P level-assignments into one stacked param tree.

        Stitched leaves gain a leading (P,) axis; untouched leaves are the
        original arrays (broadcast under ``batch_axes``).  One gather +
        scatter per module kind for the whole population — the per-round
        device call of the population-batched SPDY search.
        """
        new = jax.tree.map(lambda a: a, params)  # shallow-ish copy of dicts
        layers = new["layers"]
        pop_leaves = set()
        for e in self._groups.values():
            kind = e["kind"]
            lvl = np.asarray([[a[n] for n in e["names"]]
                              for a in assignments])            # (P, M)
            lvl_idx = jnp.asarray(np.searchsorted(e["levels"], lvl),
                                  jnp.int32)
            grp, leaf_key = _PARAM_PATH[kind]
            leaf = layers[grp][leaf_key]
            carried = (grp, leaf_key) in pop_leaves
            if UNITS[kind].per_expert:
                fn = _stitch_experts_pop2 if carried else _stitch_experts_pop
                leaf = fn(leaf, e["snaps"], lvl_idx, e["layer_idx"],
                          e["expert_idx"])
            else:
                fn = _stitch_layers_pop2 if carried else _stitch_layers_pop
                leaf = fn(leaf, e["snaps"], lvl_idx, e["layer_idx"])
            layers[grp][leaf_key] = leaf
            pop_leaves.add((grp, leaf_key))
        return new


def apply_assignment(cfg, params, db: Dict[str, ModuleDB],
                     assignment: Dict[str, int],
                     cache: Optional[SnapshotCache] = None):
    """Stitch the database snapshots for a per-module level assignment into
    the parameter tree (masked model; shrink materializes real speedup).

    With a SnapshotCache the stitch is a device-side gather; without one
    it falls back to per-module host snapshot uploads.
    """
    if cache is not None and cache.covers(assignment):
        return cache.apply(params, assignment)
    new = params
    for name, removed in assignment.items():
        mdb = db[name]
        w = jnp.asarray(mdb.weights_at(removed), jnp.float32)
        new = set_matrix(cfg, new, mdb.mod, w)
    return new
