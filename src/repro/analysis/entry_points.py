"""The audited hot entry points.

Each entry builds a *tiny but structurally faithful* instance of one of
the repo's production hot paths — same jit structure, same donation
declarations, same closure discipline as the real call sites — traces
and compiles it on the host backend, and runs the jaxpr/HLO audit
(:mod:`repro.analysis.jaxpr_audit`) over it. Tiny shapes keep the suite
CI-cheap; the hazards audited (host callbacks, baked-in constants,
donation aliasing, weak types, FLOP accounting) are shape-independent
properties of the trace, so what passes here passes at scale.

Entries (names are the budget keys in ``results/analysis/jaxpr_budget
.json``):

* ``hessian.fused_step``   — the fused calibration forward + X^T X
  accumulation (``core.hessian._fused_step``), re-jitted with the
  accumulator donation that production declares off-CPU so the audit
  statically verifies the compiled module aliases every declared buffer.
* ``obs.batched_step``     — the vmapped OBS pruning step
  (``core.obs.prune_structured_batched``), traced through its
  ``static_argnames``.
* ``obs.batched_units``    — the mixed-kind batched database build: one
  traced program running the vmapped Algorithm-1 chunk for *every*
  shape group of a registry spanning attn + ssm + ffn PruneUnit kinds
  (hymba), exactly the per-chunk calls ``database.build_database``
  makes, so a kind whose grouping regresses to baked-in weights or
  host callbacks fails here before it fails at scale.
* ``obs.sharded_step``     — the shard_map'ed Algorithm-1 database
  build (``core.obs._sharded_prune_jit``) on a 1-device mesh: same jit
  structure (pad -> shard_map(vmap) -> slice) as the multi-device
  build, audited for the same hazards; its cross-device collective
  budget lives in the collectives audit (``db_build_sharded``).
* ``spdy.batched_eval``    — the population-vmapped calibration loss
  behind ``oneshot.make_batched_eval`` (the one host sync per SPDY
  round); the calibration batches must enter as jit *arguments*, so a
  regression to closed-over batches fails the ``large_consts`` budget.
* ``shrink.stitched``      — device-resident family-member
  materialization (``core.shrink.shrink_from_stitched``) over a
  ``SnapshotCache.apply`` stitched tree.
* ``serve.prefill``        — one serve-engine prefill bucket, plus the
  "third column" of the predicted-vs-achieved latency loop: the audited
  HLO FLOP/byte counts rooflined on the costmodel hardware spec and
  banded against the ``LatencyTable`` prediction for the same env.
* ``serve.decode``         — the batched decode step over slot caches.
* ``serve.decode_gqa``     — the pruned-engine decode step on a
  GQA-pruned member (one of two KV heads removed with its query-head
  group, layer 1 dropped whole and stitched as identity): the shrunk
  layer params enter as jit arguments and the dropped layer must not
  resurrect any attention compute or cache buffers.
* ``train.step``           — the single-device distillation train step
  with the state donation production declares off-CPU.
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.analysis.jaxpr_audit import audit_jitted, roofline_seconds
from repro.configs import GPT2_SMALL
from repro.configs.base import TrainConfig

# Same shape class as the tests' TINY config: every prunable module kind
# present, two layers, real vocab path.
ANALYSIS_TINY = GPT2_SMALL.replace(
    name="gpt2-analysis-tiny", num_layers=2, d_model=64, d_ff=128,
    num_heads=4, num_kv_heads=4, head_dim=16, vocab_size=256,
    dtype="float32")

EntryResult = Tuple[Dict[str, Any], List[Finding]]


@functools.lru_cache(maxsize=1)
def _tiny_state():
    """(cfg, params) shared across entries — built once per process."""
    from repro.models import model_init
    cfg = ANALYSIS_TINY
    params = model_init(cfg, jax.random.key(0))[0]
    return cfg, params


@functools.lru_cache(maxsize=1)
def _tiny_db():
    """(db, cache) for the stitch/shrink entries (magnitude baseline —
    level grid and snapshot layout identical to the Hessian database,
    without paying a calibration pass per audit run)."""
    from repro.core.database import SnapshotCache
    from repro.core.magnitude import baseline_database
    cfg, params = _tiny_state()
    db = baseline_database(cfg, params, kind="magnitude")
    return db, SnapshotCache(cfg, db)


def _half_heads_assignment(cfg, db) -> Dict[str, int]:
    a = {}
    for l in range(cfg.num_layers):
        a[f"L{l}.attn"] = cfg.num_kv_heads // 2
        a[f"L{l}.ffn"] = 0
    return a


# ----------------------------------------------------------------------
# entries
# ----------------------------------------------------------------------

def entry_hessian_fused_step() -> EntryResult:
    from repro.core.hessian import _fused_step
    from repro.core.structures import registry
    from repro.data.synthetic import make_batch_np
    cfg, params = _tiny_state()
    mods = registry(cfg)
    hessians = {m.name: jnp.zeros((m.d_in, m.d_in), jnp.float32)
                for m in mods}
    counts = {m.name: jnp.zeros((), jnp.float32) for m in mods}
    tokens = jnp.asarray(make_batch_np(cfg, 8, 32, seed=0)["tokens"])
    # production donates the accumulators off-CPU (`hessian._donate`);
    # re-declare that donation here regardless of backend so the audit
    # checks the aliases statically even when CI runs on CPU
    body = _fused_step(cfg, False).__wrapped__
    jitted = jax.jit(body, donate_argnums=(0, 1))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # CPU donation no-op warnings
        return audit_jitted(
            "hessian.fused_step", jitted,
            (hessians, counts, params, tokens, None, jnp.float32(1.0)),
            donate_argnums=(0, 1))


def entry_obs_batched_step() -> EntryResult:
    from repro.core.obs import prune_structured_batched
    key = jax.random.key(1)
    L, d_in, d_out, gs = 2, 128, 64, 4
    W = jax.random.normal(key, (L, d_in, d_out), jnp.float32)
    X = jax.random.normal(jax.random.key(2), (L, 256, d_in), jnp.float32)
    H = jnp.einsum("lni,lnj->lij", X, X) + 1e-3 * jnp.eye(d_in)
    Hinv = jnp.linalg.pinv(H)
    return audit_jitted(
        "obs.batched_step", prune_structured_batched, (W, Hinv),
        kwargs=dict(group_size=gs, n_remove=d_in // gs // 2,
                    levels=(8, 16), use_kernel=False))


def entry_obs_batched_units() -> EntryResult:
    from repro.configs import smoke_config
    from repro.core.database import group_modules
    from repro.core.obs import build_hessian, prune_structured_batched
    from repro.core.structures import get_matrix, registry
    from repro.models import model_init
    cfg = smoke_config("hymba-1.5b").replace(dtype="float32")
    params = model_init(cfg, jax.random.key(0))[0]
    mods = registry(cfg)
    assert {"attn", "ssm", "ffn"} <= {m.kind for m in mods}
    rng = np.random.default_rng(0)
    metas, stacks = [], []
    for key, gmods in group_modules(cfg, params, mods):
        gs, _, _, levels = key
        Ws = jnp.stack([get_matrix(cfg, params, m).astype(jnp.float32)
                        for m in gmods])
        d_in = gmods[0].d_in
        X = rng.standard_normal((len(gmods), 2 * d_in + 16, d_in))
        Hraw = jnp.asarray(np.einsum("lni,lnj->lij", X, X) / X.shape[1],
                           jnp.float32)
        metas.append((gs, max(levels), levels))
        stacks.append((Ws, jnp.linalg.pinv(build_hessian(Hraw))))

    def mixed(groups):
        # every shape group of the mixed-kind registry in one program:
        # the device portion of database.build_database's batched path
        out = []
        for (gs, n_remove, levels), (Ws, Hinv) in zip(metas, groups):
            res = prune_structured_batched(
                Ws, Hinv, group_size=gs, n_remove=n_remove,
                levels=levels, use_kernel=False)
            out.append((res.snapshots.astype(jnp.float16), res.errors,
                        res.order))
        return out

    return audit_jitted("obs.batched_units", jax.jit(mixed), (stacks,))


def entry_obs_sharded_step() -> EntryResult:
    from repro.core.obs import _sharded_prune_jit
    from repro.distributed.sharding import make_mesh
    key = jax.random.key(1)
    L, d_in, d_out, gs = 2, 128, 64, 4
    W = jax.random.normal(key, (L, d_in, d_out), jnp.float32)
    X = jax.random.normal(jax.random.key(2), (L, 256, d_in), jnp.float32)
    H = jnp.einsum("lni,lnj->lij", X, X) + 1e-3 * jnp.eye(d_in)
    Hinv = jnp.linalg.pinv(H)
    mesh = make_mesh((jax.device_count(),), ("data",))
    jitted = _sharded_prune_jit(mesh, ("data",), gs, d_in // gs // 2,
                                (8, 16), False, None, False, 0.75, 64, 16)
    return audit_jitted("obs.sharded_step", jitted, (W, Hinv))


def entry_spdy_batched_eval() -> EntryResult:
    from repro.core.oneshot import batched_calib_loss_fn
    from repro.data.synthetic import calibration_batches
    cfg, params = _tiny_state()
    db, cache = _tiny_db()
    # 8 batches of (8, 128) tokens = 256 KiB stacked: a regression back
    # to closed-over calibration data trips the 16 KiB const threshold
    batches = calibration_batches(cfg, 64, 128, batch=8)
    loss_b = batched_calib_loss_fn(cfg, batches, cache.batch_axes(params))
    a = _half_heads_assignment(cfg, db)
    pb = cache.apply_batched(params, [a, dict(a)])
    return audit_jitted("spdy.batched_eval", loss_b._jitted,
                        (loss_b._stacked, pb))


def entry_shrink_stitched() -> EntryResult:
    from repro.core.shrink import shrink_from_stitched
    cfg, params = _tiny_state()
    db, cache = _tiny_db()
    a = _half_heads_assignment(cfg, db)
    stitched = cache.apply(params, a)

    def _shrink(st):
        pm = shrink_from_stitched(cfg, st, db, a)
        return pm.weights()

    return audit_jitted("shrink.stitched", jax.jit(_shrink), (stitched,))


def entry_serve_prefill() -> EntryResult:
    from repro.core.latency import build_costmodel_table
    from repro.core.structures import registry
    from repro.runtime.costmodel import TPU_V5E, InferenceEnv
    from repro.serve.engine import DenseServeModel, _bucket
    cfg, params = _tiny_state()
    model = DenseServeModel(cfg, params, max_len=64)
    s = 8
    bucket = _bucket(s, model.max_len)
    padded = jnp.asarray(np.zeros((1, bucket), np.int64))
    metrics, findings = audit_jitted(
        "serve.prefill", model._prefill,
        (model.weights, padded, jnp.asarray(s - 1, jnp.int32)))

    # third column of the latency loop: the LatencyTable prediction vs a
    # roofline over the audited HLO costs, same env, same hardware spec
    env = InferenceEnv(batch=1, seq=bucket, mode="prefill", hw=TPU_V5E)
    table = build_costmodel_table(cfg, env)
    predicted = float(table.dense_runtime(registry(cfg)))
    roofline = roofline_seconds(metrics["hlo_flops"], metrics["hlo_bytes"],
                                TPU_V5E)
    metrics["latency_table_s"] = predicted
    metrics["latency_roofline_s"] = float(roofline)
    metrics["latency_ratio"] = (float(predicted / roofline)
                                if roofline > 0 else None)
    return metrics, findings


def entry_serve_decode() -> EntryResult:
    from repro.serve.engine import DenseServeModel
    cfg, params = _tiny_state()
    model = DenseServeModel(cfg, params, max_len=64)
    cache = model.init_slots(4)
    toks = jnp.zeros((4, 1), jnp.int32)
    # the step donates the K/V off-CPU (`engine._donate_kv`); re-declare
    # it here so the aliases are checked statically on any backend
    step = jax.jit(model._step.__wrapped__, donate_argnums=model.kv_argnums)
    return audit_jitted("serve.decode", step,
                        (model.weights, cache["attn"], cache["pos"], toks,
                         {}), donate_argnums=model.kv_argnums)


def entry_serve_decode_gqa() -> EntryResult:
    from repro.configs import smoke_config
    from repro.core.magnitude import baseline_database
    from repro.core.shrink import shrink
    from repro.core.structures import drop_layer, registry
    from repro.models import model_init
    from repro.serve.engine import PrunedServeModel
    cfg = smoke_config("qwen2-72b").replace(num_kv_heads=2,
                                            dtype="float32")
    params = model_init(cfg, jax.random.key(0))[0]
    db = baseline_database(cfg, params, kind="magnitude")
    mods = registry(cfg)
    a = {m.name: (1 if m.kind == "attn" else 0) for m in mods}
    a = drop_layer(a, mods, 1)  # dropped layer serves as identity
    pm = shrink(cfg, params, db, a)
    model = PrunedServeModel(pm, max_len=64)
    cache = model.init_slots(4)
    toks = jnp.zeros((4, 1), jnp.int32)
    step = jax.jit(model._step.__wrapped__, donate_argnums=model.kv_argnums)
    return audit_jitted("serve.decode_gqa", step,
                        (model.weights, cache["attn"], cache["pos"], toks,
                         {}), donate_argnums=model.kv_argnums)


def entry_train_step() -> EntryResult:
    from repro.data.synthetic import make_batch_np
    from repro.train.train_step import make_train_state, make_train_step
    cfg, params = _tiny_state()
    tcfg = TrainConfig(warmup_steps=2, total_steps=10, microbatches=2)
    state = make_train_state(cfg, params, tcfg)
    batch = jax.tree.map(jnp.asarray, make_batch_np(cfg, 8, 32, seed=3))
    # single-device Trainer path jits without donation on CPU; declare
    # the off-CPU donation here so the aliases are checked statically
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return audit_jitted("train.step", step, (state, batch),
                            donate_argnums=(0,))


ENTRIES: Dict[str, Callable[[], EntryResult]] = {
    "hessian.fused_step": entry_hessian_fused_step,
    "obs.batched_step": entry_obs_batched_step,
    "obs.batched_units": entry_obs_batched_units,
    "obs.sharded_step": entry_obs_sharded_step,
    "spdy.batched_eval": entry_spdy_batched_eval,
    "shrink.stitched": entry_shrink_stitched,
    "serve.prefill": entry_serve_prefill,
    "serve.decode": entry_serve_decode,
    "serve.decode_gqa": entry_serve_decode_gqa,
    "train.step": entry_train_step,
}


def run_entries(only=None) -> Dict[str, EntryResult]:
    out = {}
    for name, fn in ENTRIES.items():
        if only and name not in only:
            continue
        out[name] = fn()
    return out
