"""Post-training / one-shot ZipLM pruning (paper §4.3): calibrate →
Hessians → database → structured-SPDY per speedup target → stitched models.

A single run produces the whole family of compressed models, one per
speedup target, each with a runtime guarantee in the given environment.
The family is searched in ONE population-batched pass (`spdy.search_family`):
each target runs a population-vectorized DP per round, every unique
candidate assignment is stitched and scored once for the whole family
(`SnapshotCache.
apply_batched` + a vmapped calibration loss, one host sync per round), and
per-target RNG streams are fold-in derived from ``seed``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

from ..models.model import loss_fn
from ..robustness import faults as _faults
from ..runtime.costmodel import InferenceEnv
from ..runtime.device import free_device_bytes
from .database import (DEVICE_MEM_FRACTION, ModuleDB, SnapshotCache,
                       apply_assignment, build_database)
from .hessian import collect_hessians
from .latency import LatencyTable, build_table
from .spdy import SearchResult, search_family
from .structures import registry


@dataclass
class PrunedVariant:
    target_speedup: float
    params: dict
    assignment: Dict[str, int]
    runtime: float
    speedup: float
    calib_loss: float
    search: SearchResult


@dataclass
class OneShotResult:
    variants: Dict[float, PrunedVariant]
    table: LatencyTable
    db: Dict[str, ModuleDB]
    dense_runtime: float
    dense_loss: float
    hessians: Dict[str, jnp.ndarray]


def _stack_batch_groups(batches):
    """Group same-structure batches and stack each group to (B, ...).

    Lets the calibration loss ``lax.map`` over the batch axis instead of
    unrolling a Python list inside one jit — trace size no longer
    multiplies with the eval-batch count.  Ragged batch sets degrade to
    one group per distinct shape.
    """
    def dt(x):
        return (x.dtype if hasattr(x, "dtype") else np.asarray(x).dtype)

    groups: Dict[tuple, List[dict]] = {}
    for b in batches:
        key = tuple((k, tuple(np.shape(b[k])), np.dtype(dt(b[k])).name)
                    for k in sorted(b))
        groups.setdefault(key, []).append(b)
    return [jax.tree.map(lambda *xs: jnp.stack(xs), *g)
            for g in groups.values()]


def _grouped_mean_loss(cfg, stacked, params):
    """Mean per-batch loss over stacked batch groups — the one loss body
    shared by the serial and population-vmapped calibration scorers."""
    parts = [jax.lax.map(lambda b: loss_fn(cfg, params, b)["loss"], g)
             for g in stacked]
    return jnp.mean(jnp.concatenate([p.reshape(-1) for p in parts]))


def calib_loss_fn(cfg, batches):
    # the eval batches enter the jit as ARGUMENTS, not closure constants:
    # closed-over arrays are baked into every compiled executable (one
    # copy per jit cache entry), which repro.analysis flags as
    # jaxpr.large-const
    stacked = _stack_batch_groups(batches)
    _inner = jax.jit(lambda st, params: _grouped_mean_loss(cfg, st, params))
    _loss = lambda params: _inner(stacked, params)
    fn = lambda params: float(_loss(params))
    fn._jitted = _loss  # exposed for trace-size regression tests
    fn._jitted_inner = _inner   # (stacked, params) -> loss, no baked data
    fn._stacked = stacked
    return fn


def batched_calib_loss_fn(cfg, batches, axes):
    """Vmapped calibration loss over a population-stacked param tree.

    ``axes`` is the `SnapshotCache.batch_axes` tree (0 on stitched leaves,
    None elsewhere).  Returns a fn: params_batched -> (P,) losses,
    device-resident until the caller syncs. The eval batches are jit
    arguments (see calib_loss_fn); ``fn._jitted`` exposes the underlying
    (stacked, params_batched) executable for the analysis suite.
    """
    stacked = _stack_batch_groups(batches)
    _inner = jax.jit(jax.vmap(
        lambda st, params: _grouped_mean_loss(cfg, st, params),
        in_axes=(None, axes)))
    fn = lambda pb: _inner(stacked, pb)
    fn._jitted = _inner
    fn._stacked = stacked
    return fn


def candidate_bytes(cfg, params, cache: SnapshotCache, stacked) -> int:
    """Device bytes one candidate adds to a population eval: its stitched
    copy of the pruned leaves, plus the fp32 logits of one calibration
    batch and loss temporaries of the same size (x4)."""
    tokens = max(int(np.prod(g["tokens"].shape[1:])) for g in stacked)
    return cache.stitched_bytes(params) + 16 * tokens * cfg.vocab_size


def make_batched_eval(cfg, params, cache: SnapshotCache, batches,
                      chunk: int = 32, loss_b=None
                      ) -> Callable[[List[Dict[str, int]]], np.ndarray]:
    """Population scorer for `spdy.search_family`: stitch P assignments
    device-side (`apply_batched`) and score them with one vmapped loss —
    a single host sync per search round.

    Work is chunked at ``chunk`` candidates, lowered on first use to the
    largest power of two whose `candidate_bytes` fit the device's free
    memory, and padded to power-of-two sizes within a chunk, so the
    vmapped jit compiles a handful of shapes instead of one per dedup
    count.  Pass ``loss_b`` (a `batched_calib_loss_fn` result) to reuse
    one compiled loss across scorers whose cfg/batches/axes agree — e.g.
    `gradual_prune` rebuilding the cache per target.

    The returned callable takes ``device=`` (advertised via its
    ``supports_device`` attribute): stitch + loss then run on that
    device against cached per-device replicas of the params, snapshot
    cache and eval batches.  Scores are bitwise those of the unplaced
    call — vmap lanes are independent — so `spdy.search_family` can
    place per-target populations on separate devices without perturbing
    the search (asserted by tests/test_sharded_db.py).
    """
    if loss_b is None:
        loss_b = batched_calib_loss_fn(cfg, batches,
                                       cache.batch_axes(params))
    _replicas: Dict[object, tuple] = {}
    _chunks: Dict[object, int] = {}

    def _chunk(device):
        if device not in _chunks:
            free = free_device_bytes(device)
            fit = chunk
            if free is not None:
                per = candidate_bytes(cfg, params, cache, loss_b._stacked)
                fit = max(1, int(DEVICE_MEM_FRACTION * free) // per)
            _chunks[device] = min(chunk, 1 << (fit.bit_length() - 1))
        return _chunks[device]

    def _replica(device):
        if device is None:
            return params, cache, loss_b._stacked
        if device not in _replicas:
            _replicas[device] = (jax.device_put(params, device),
                                 cache.to_device(device),
                                 jax.device_put(loss_b._stacked, device))
        return _replicas[device]

    def eval_batched(assignments: List[Dict[str, int]],
                     device=None) -> np.ndarray:
        # injected OOM/failure point for the spdy degradation ladder
        _faults.hit("spdy.batched_eval")
        p, c, stacked = _replica(device)
        step = _chunk(device)
        n = len(assignments)
        out = np.empty((n,), np.float64)
        for lo in range(0, n, step):
            part = assignments[lo:lo + step]
            k = len(part)
            padded = min(1 << (k - 1).bit_length(), step)
            part = part + [part[0]] * (padded - k)
            pb = c.apply_batched(p, part)
            # sync: THE one host pull per SPDY eval round — the invariant
            # repro.analysis budgets (PR 4); keep it the only one
            out[lo:lo + k] = np.asarray(loss_b._jitted(stacked, pb),
                                        np.float64)[:k]
        return out

    eval_batched.supports_device = True
    return eval_batched


def oneshot_prune(cfg, params, calib_batches: List[dict],
                  env: InferenceEnv, targets: Sequence[float], *,
                  latency_backend: str = "costmodel",
                  latency_kw: Optional[dict] = None,
                  search_steps: int = 200, search_pop: int = 16,
                  search_batched: bool = True,
                  eval_with_loss: bool = True,
                  eval_batches: Optional[List[dict]] = None,
                  damp: float = 1e-4, use_kernel: bool = False,
                  mesh=None, data_axes=None,
                  seed: int = 0, verbose: bool = False) -> OneShotResult:
    """One-shot family pruning.

    ``mesh``/``data_axes`` shard calibration data-parallel (also picked up
    from the installed activation context); ``latency_kw`` is forwarded to
    ``build_table`` — e.g. ``{"cache_dir": ...}`` so a measured table is
    loaded from / persisted to the latency cache instead of re-timed.
    ``search_pop`` sets the SPDY population per round; ``search_batched=
    False`` keeps the serial equivalence-reference search path.

    The stages run under host spans ``prune.hessians``,
    ``prune.latency_table``, ``prune.db``, ``prune.search`` and
    ``prune.stitch`` (each member's parameters and calibration loss),
    which a ``jax.profiler`` trace records on the device's clock.
    """
    targets = list(targets)  # consumed twice: family search + variants
    with _span("prune.hessians"):
        hessians = collect_hessians(cfg, params, calib_batches,
                                    use_kernel=use_kernel, mesh=mesh,
                                    data_axes=data_axes)
    with _span("prune.latency_table"):
        table = build_table(cfg, env, backend=latency_backend,
                            **(latency_kw or {}))
    with _span("prune.db"):
        db = build_database(cfg, params, hessians, damp=damp,
                            verbose=verbose, mesh=mesh,
                            shard_axes=data_axes)
    # device-resident snapshots only pay off for per-candidate loss eval;
    # without it the final per-target stitch is cheap on the host path
    cache = SnapshotCache(cfg, db) if eval_with_loss else None
    mods = registry(cfg)
    dense_rt = table.dense_runtime(mods)

    loss_eval = calib_loss_fn(cfg, eval_batches or calib_batches[:1])
    dense_loss = loss_eval(params)

    eval_fn = eval_batched = None
    if eval_with_loss:
        def eval_fn(assignment):
            return loss_eval(apply_assignment(cfg, params, db, assignment,
                                              cache=cache))
        eval_batched = make_batched_eval(cfg, params, cache,
                                         eval_batches or calib_batches[:1])

    # one search pass for the whole family: shared candidate pool, shared
    # stitch/eval memo, per-target budgets in the batched DP, per-target
    # fold-in RNG streams
    with _span("prune.search"):
        results = search_family(db, table, targets, steps=search_steps,
                                pop=search_pop, eval_fn=eval_fn,
                                eval_batched=eval_batched, seed=seed,
                                batched=search_batched, verbose=verbose,
                                devices=(list(mesh.devices.flat)
                                         if mesh is not None else None))

    variants: Dict[float, PrunedVariant] = {}
    for t in targets:
        res = results[t]
        with _span("prune.stitch", target=t):
            pruned = apply_assignment(cfg, params, db, res.assignment,
                                      cache=cache)
            calib_loss = loss_eval(pruned)
        variants[t] = PrunedVariant(
            target_speedup=t, params=pruned, assignment=res.assignment,
            runtime=res.runtime, speedup=res.speedup,
            calib_loss=calib_loss, search=res)
        if verbose:
            print(f"target {t}x -> achieved {res.speedup:.2f}x, "
                  f"loss {variants[t].calib_loss:.4f} "
                  f"(dense {dense_loss:.4f})")
    return OneShotResult(variants=variants, table=table, db=db,
                         dense_runtime=dense_rt, dense_loss=dense_loss,
                         hessians=hessians)
