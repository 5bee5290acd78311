"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus readable detail to
stderr-ish sections). CPU-sized models stand in for BERT/GPT2; the TPU-v5e
analytic cost model stands in for on-device latency tables where the paper
used V100/A100 measurements (DESIGN.md §3).

  table1  GPT2 pruning-for-throughput vs pruning-for-latency (§4.2)
  table2  one-shot ZipLM vs magnitude/Fisher baselines (§4.3)
  table3  MLP-size speedups on two device capabilities
  table4  calibration-size sensitivity
  table7  latency table (Appendix E)
  table8  target-vs-achieved speedup deviation (Appendix F)
  fig5    scaling law: loss vs speedup linear fit
  fig2    gradual pruning family (reduced)
  kernels Pallas kernel vs ref oracle timing/correctness
  roofline  reads results/dryrun/*.json (deliverable g)
  db_build  batched (grouped-vmap) database construction vs the serial
            per-module path on a CPU-scaled BERT-base; writes BENCH_db.json
  db_build_compact  live-set-compacted Algorithm 1 (shrinking working set)
            vs the PR-1 batched path; appended to BENCH_db.json
  spdy_eval device-resident SnapshotCache assignment stitching vs host
            per-module snapshot uploads; appended to BENCH_db.json
  spdy_search  population-batched multi-target SPDY search vs the frozen
            PR-3 serial loop at equal steps; appended to BENCH_db.json
  calib_shard  mesh-sharded collect_hessians vs single-device on a forced
            2-device CPU mesh (subprocess); appended to BENCH_db.json
  latency_cache  measured-table build cold vs warm (persistent cache hit);
            appended to BENCH_db.json
  chaos     robustness-layer cost: armed-but-fault-free family overhead vs
            clean, plus recovery overhead of a chaos run (NaN calibration
            batch, transient async-ckpt write failure, kill mid-finetune,
            corrupted db artifact rebuilt on resume); appended to
            BENCH_db.json
  serve     continuous-batching engine over a speedup-target family: warm
            tokens/s, prefill ms, decode ms/token, p50/p99 request latency
            for dense vs pruned members on the same Poisson stream, plus
            per-layer KV-cache byte accounting (pruned strictly < dense,
            asserted); appended to BENCH_db.json
  family_sharded  device-parallel family run (sharded db build + placed
            SPDY population + overlapped scheduler) vs the single-device
            serial schedule on a forced 2-device CPU mesh, bit-identity
            asserted; appended to BENCH_db.json

Run a subset with ``python benchmarks/run.py db_build spdy_eval``.
``--faults SITE:MODE[@N][xC][~D],...`` installs a deterministic
fault-injection plan (same grammar as ZIPLM_FAULTS) around whichever
benches run.
"""
from __future__ import annotations

import functools
import glob
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.checkpoint.manager import atomic_write_json
from repro.configs import BERT_BASE, GPT2_SMALL, smoke_config
from repro.configs.base import TrainConfig
from repro.core.database import (SnapshotCache, apply_assignment,
                                 build_database)
from repro.core.hessian import collect_hessians
from repro.core.latency import build_table
from repro.core.magnitude import baseline_database, uniform_assignment
from repro.core.oneshot import calib_loss_fn, oneshot_prune
from repro.core.pipeline import gradual_prune
from repro.core.shrink import shrink
from repro.core.structures import registry
from repro.data import calibration_batches, synthetic_stream
from repro.models import model_init
from repro.models.pruned import forward_pruned
from repro.models.transformer import forward
from repro.runtime.costmodel import InferenceEnv, ffn_time
from repro.train.train_step import make_train_state, make_train_step

ROWS = []

TINY = GPT2_SMALL.replace(
    name="gpt2-tiny", num_layers=4, d_model=96, d_ff=384, num_heads=6,
    num_kv_heads=6, head_dim=16, vocab_size=384, dtype="float32")
ENV = InferenceEnv(batch=16, seq=128, mode="prefill")

# persistent latency cache for the measured-backend benches: a re-run of
# the suite loads each (cfg, env) table instead of re-timing every level
LAT_CACHE = {"cache_dir": os.path.join(os.path.dirname(__file__), "..",
                                       "results", "latency_cache")}


def row(name, us, derived):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}")


def _timeit(f, *args, reps=3):
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


_STATE = {}


def trained_model():
    if "params" in _STATE:
        return _STATE["params"], _STATE["losses"]
    params, _ = model_init(TINY, jax.random.key(0))
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=10, total_steps=150)
    step = jax.jit(make_train_step(TINY, tcfg))
    state = make_train_state(TINY, params, tcfg)
    data = synthetic_stream(TINY, 16, 64, seed=7)
    losses = []
    t0 = time.perf_counter()
    for _ in range(150):
        state, m = step(state, next(data))
        losses.append(float(m["loss"]))
    us = (time.perf_counter() - t0) / 150 * 1e6
    row("train_step", us, f"loss {losses[0]:.3f}->{losses[-1]:.3f}")
    _STATE["params"] = state.params
    _STATE["losses"] = losses
    _STATE["calib"] = calibration_batches(TINY, 32, 64, batch=8)
    return state.params, losses


def bench_table7_latency_table():
    """Appendix E: the latency table itself (costmodel backend, v5e; plus a
    measured-on-CPU build to exercise the paper's own procedure)."""
    t0 = time.perf_counter()
    tab = build_table(GPT2_SMALL, InferenceEnv(batch=128, seq=384,
                                               mode="prefill"),
                      backend="costmodel")
    us = (time.perf_counter() - t0) * 1e6
    heads = [f"{int(g)}h={tab.module_time('attn', g)*1e6:.0f}us"
             for g in tab.grids["attn"][::4]]
    row("table7_latency_v5e", us, " ".join(heads[:4]))
    t0 = time.perf_counter()
    mtab = build_table(TINY, ENV, backend="measure", grid_subsample=8,
                       reps=2)
    us = (time.perf_counter() - t0) * 1e6
    row("table7_latency_measured_cpu", us,
        f"ffn_dense={mtab.module_time('ffn', 0)*1e6:.0f}us")


def bench_table3_mlp_speedups():
    """Table 3: identical sparsity, very different speedups on different
    device capabilities (v5e-1 vs v5e-TP4 standing in for V100 vs A100)."""
    sizes = [3072, 1814, 1322, 302, 130, 76, 33]
    env1 = InferenceEnv(batch=128, seq=128, mode="prefill", tp=1)
    env4 = InferenceEnv(batch=128, seq=128, mode="prefill", tp=4)
    cfg = GPT2_SMALL
    base1 = ffn_time(cfg, env1, 3072)
    base4 = ffn_time(cfg, env4, 3072)
    out = []
    for s in sizes:
        s1 = base1 / ffn_time(cfg, env1, s)
        s4 = base4 / ffn_time(cfg, env4, s)
        out.append(f"{s}:{s1:.1f}x/{s4:.1f}x")
    row("table3_mlp_speedup", 0.0, " ".join(out))


def bench_table2_oneshot():
    """Table 2: one-shot ZipLM vs magnitude & Fisher baselines at the same
    guaranteed speedups."""
    params, _ = trained_model()
    calib = _STATE["calib"]
    t0 = time.perf_counter()
    res = oneshot_prune(TINY, params, calib, ENV, targets=[1.5, 2.0],
                        search_steps=30, seed=0)
    us = (time.perf_counter() - t0) * 1e6
    tab = res.table
    loss = calib_loss_fn(TINY, calib[:1])
    hess = collect_hessians(TINY, params, calib)
    detail = [f"dense={res.dense_loss:.4f}"]
    for t in [1.5, 2.0]:
        parts = [f"zip={res.variants[t].calib_loss:.4f}"]
        for kind in ["magnitude", "fisher"]:
            bdb = baseline_database(TINY, params, hessians=hess, kind=kind)
            uni = uniform_assignment(TINY, tab, t)
            parts.append(
                f"{kind[:3]}={loss(apply_assignment(TINY, params, bdb, uni)):.4f}")
        detail.append(f"{t}x({' '.join(parts)})")
    row("table2_oneshot", us, " ".join(detail))
    _STATE["oneshot"] = res


def bench_table4_calibration():
    params, _ = trained_model()
    out = []
    for n in [4, 16, 64, 256]:
        calib = calibration_batches(TINY, n, 64, batch=8)
        t0 = time.perf_counter()
        res = oneshot_prune(TINY, params, calib, ENV, targets=[2.0],
                            search_steps=10, eval_with_loss=False, seed=1)
        out.append(f"{n}:{res.variants[2.0].calib_loss:.4f}")
    row("table4_calibration", 0.0, " ".join(out))


def bench_table1_throughput_vs_latency():
    """§4.2 depth-vs-width: the throughput env prunes width; the latency
    env must drop whole modules (depth) to win."""
    params, _ = trained_model()
    calib = _STATE["calib"]
    envs = {
        "throughput": InferenceEnv(batch=16, seq=1024, mode="prefill"),
        "latency": InferenceEnv(batch=1, seq=64, mode="decode"),
    }
    detail = []
    for name, env in envs.items():
        res = oneshot_prune(TINY, params, calib, env, targets=[2.5],
                            search_steps=40, seed=2)
        a = res.variants[2.5].assignment
        mods = {m.name: m for m in registry(TINY)}
        dropped = sum(1 for k, v in a.items()
                      if v == mods[k].n_structures)
        kept_frac = np.mean([1 - v / mods[k].n_structures
                             for k, v in a.items() if "ffn" in k])
        detail.append(f"{name}: dropped_modules={dropped} "
                      f"ffn_width_kept={kept_frac:.2f} "
                      f"loss={res.variants[2.5].calib_loss:.4f}")
    row("table1_thr_vs_lat", 0.0, " | ".join(detail))


def bench_table8_speedup_guarantee():
    """Appendix F: target vs ACHIEVED (wall-clock measured) speedup of the
    shrunk models, using the measured-on-CPU latency table."""
    params, _ = trained_model()
    calib = _STATE["calib"]
    env = InferenceEnv(batch=8, seq=64, mode="prefill")
    res = oneshot_prune(TINY, params, calib, env, targets=[1.5, 2.0],
                        latency_backend="measure", latency_kw=LAT_CACHE,
                        search_steps=20, seed=3)
    tokens = calib[0]["tokens"]
    f_dense = jax.jit(lambda t: forward(TINY, params, t)["logits"])
    t_dense = _timeit(f_dense, tokens, reps=5)
    detail = []
    for t, v in res.variants.items():
        pm = shrink(TINY, v.params, res.db, v.assignment)
        f_p = jax.jit(lambda tk, _pm=pm: forward_pruned(_pm, tk))
        t_p = _timeit(f_p, tokens, reps=5)
        achieved = t_dense / t_p
        dev = (achieved - t) / t * 100
        detail.append(f"target={t}x measured={achieved:.2f}x "
                      f"dev={dev:+.1f}%")
    row("table8_guarantee", t_dense, " | ".join(detail))


def bench_fig5_scaling_law():
    params, _ = trained_model()
    calib = _STATE["calib"]
    # measured backend: width scales CPU runtime, so deep targets stay
    # feasible (the analytic table's unprunable base caps tiny models ~4x)
    targets = [1.5, 2.0, 3.0, 4.0, 6.0]
    res = oneshot_prune(TINY, params, calib,
                        InferenceEnv(batch=8, seq=64, mode="prefill"),
                        targets=targets, latency_backend="measure",
                        latency_kw=LAT_CACHE, search_steps=15, seed=4)
    sp = np.array([res.variants[t].speedup for t in targets])
    ls = np.array([res.variants[t].calib_loss for t in targets])
    slope, intercept = np.polyfit(sp, ls, 1)
    row("fig5_scaling_law", 0.0,
        f"loss~{intercept:.3f}+{slope:.4f}*speedup  "
        + " ".join(f"{t}x:{l:.3f}" for t, l in zip(targets, ls)))


def bench_fig2_gradual():
    import tempfile
    params, _ = trained_model()
    calib = _STATE["calib"]
    data = synthetic_stream(TINY, 16, 64, seed=21)
    tcfg = TrainConfig(learning_rate=5e-4, warmup_steps=2, total_steps=15,
                       distill_logit=1.0, distill_token=0.5)
    t0 = time.perf_counter()
    variants = gradual_prune(TINY, params, ENV, [1.5, 2.0], data, calib,
                             tcfg=tcfg, finetune_steps=15, search_steps=10,
                             ckpt_dir=tempfile.mkdtemp(prefix="bench_grad"),
                             resume=False)
    us = (time.perf_counter() - t0) * 1e6
    detail = " | ".join(
        f"{v.target}x loss {v.loss_before_ft:.4f}->{v.loss_after_ft:.4f} "
        f"params={v.pruned.encoder_params()/1e3:.0f}k" for v in variants)
    row("fig2_gradual", us, detail)


def bench_kernels():
    from repro.kernels import ops, ref
    k = jax.random.key(0)
    q = jax.random.normal(k, (2, 256, 8, 64), jnp.float32)
    kv = jax.random.normal(k, (2, 256, 2, 64), jnp.float32)
    us = _timeit(lambda: ops.flash_attention(q, kv, kv, interpret=True))
    row("kernel_flash_attention", us, "interpret-mode, vs ref in tests")
    x = jax.random.normal(k, (2048, 256), jnp.float32)
    us = _timeit(lambda: ops.hessian_accum(x, interpret=True))
    err = float(jnp.max(jnp.abs(ops.hessian_accum(x, interpret=True)
                                - ref.hessian_ref(x))))
    row("kernel_hessian_accum", us, f"maxerr={err:.1e}")
    xs = jax.random.normal(k, (1, 128, 4, 32), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(k, (1, 128, 4)))
    A = -jnp.exp(jax.random.normal(k, (4,)) * 0.3)
    B = jax.random.normal(k, (1, 128, 16)) * 0.5
    us = _timeit(lambda: ops.ssd_chunked_kernel(xs, dt, A, B, B, chunk=64,
                                                interpret=True)[0])
    row("kernel_ssd_scan", us, "interpret-mode, vs recurrence in tests")


# CPU-scaled BERT-base: the paper's 12-layer encoder with widths shrunk so
# database construction finishes in benchmark time on CPU. The batching
# dimension that matters (12 attn + 12 ffn modules in 2 shape groups) is
# preserved at full scale.
BERT_BENCH = BERT_BASE.replace(
    name="bert-base-cpu", d_model=96, num_heads=6, num_kv_heads=6,
    head_dim=16, d_ff=384, vocab_size=512, max_position=128,
    dtype="float32")


# Frozen copy of the SEED database inner loop (commit 1f7c91d): one module
# at a time, all n diagonal blocks re-inverted with jnp.linalg.inv at every
# removal step, full snapshot-stack re-masked every step. Kept verbatim here
# as the db_build baseline so the engine speedup is tracked across PRs.
@functools.partial(jax.jit, static_argnames=("group_size", "n_remove",
                                             "levels"))
def _seed_prune_structured(W, Hinv, *, group_size, n_remove, levels):
    gs = group_size
    d_in, d_out = W.shape
    n = d_in // gs
    levels_arr = jnp.asarray(levels, jnp.int32)
    n_levels = len(levels)
    W = W.astype(jnp.float32)
    Hinv = Hinv.astype(jnp.float32)
    snaps0 = jnp.zeros((n_levels, d_in, d_out), jnp.float32)
    errs0 = jnp.zeros((n_levels,), jnp.float32)
    has0 = levels_arr == 0
    snaps0 = jnp.where(has0[:, None, None], W[None], snaps0)

    def body(i, carry):
        W, Hinv, removed, cum_err, snaps, errs, order = carry
        blocks = Hinv.reshape(n, gs, n, gs)[jnp.arange(n), :,
                                            jnp.arange(n), :]
        eye = jnp.eye(gs, dtype=jnp.float32)
        safe = jnp.where(removed[:, None, None], eye[None], blocks)
        K = jnp.linalg.inv(safe)
        Wb = W.reshape(n, gs, d_out)
        scores = jnp.einsum("gic,gij,gjc->g", Wb, K, Wb)
        scores = jnp.where(removed, jnp.inf, jnp.maximum(scores, 0.0))
        s = jnp.argmin(scores)
        rows = s * gs + jnp.arange(gs)
        HcolS = Hinv[:, rows]
        Ks = K[s]
        WS = W[rows, :]
        W_new = W - HcolS @ (Ks @ WS)
        Hinv_new = Hinv - HcolS @ (Ks @ HcolS.T)
        cum_err = cum_err + scores[s]
        removed = removed.at[s].set(True)
        order = order.at[i].set(s.astype(jnp.int32))
        row_keep = jnp.repeat(~removed, gs).astype(jnp.float32)
        W_new = W_new * row_keep[:, None]
        Hinv_new = Hinv_new * row_keep[:, None] * row_keep[None, :]
        match = levels_arr == (i + 1)
        snaps = jnp.where(match[:, None, None], W_new[None], snaps)
        errs = jnp.where(match, cum_err, errs)
        return (W_new, Hinv_new, removed, cum_err, snaps, errs, order)

    init = (W, Hinv, jnp.zeros((n,), bool), jnp.zeros((), jnp.float32),
            snaps0, errs0, jnp.zeros((n_remove,), jnp.int32))
    _, _, _, _, snaps, errs, order = jax.lax.fori_loop(0, n_remove, body,
                                                       init)
    return snaps, errs, order


def _seed_build_database(cfg, params, hessians):
    """Seed build_database: serial per-module Algorithm-1 runs."""
    from repro.core.obs import build_hessian, module_drop_error
    from repro.core.structures import get_matrix, level_grid
    out = {}
    for mod in registry(cfg):
        W = get_matrix(cfg, params, mod).astype(jnp.float32)
        H = build_hessian(hessians[mod.name], 1e-4)
        Hinv = jnp.linalg.inv(H)
        levels = level_grid(mod)
        snaps, errs, order = _seed_prune_structured(
            W, Hinv, group_size=mod.group_size, n_remove=max(levels),
            levels=tuple(levels))
        base = float(module_drop_error(W, hessians[mod.name]))
        out[mod.name] = (np.asarray(snaps, np.float16), np.asarray(errs),
                         np.asarray(order), base)
    return out


def _bench_db_setup():
    if "db_bench" in _STATE:
        return _STATE["db_bench"]
    from repro.core.structures import registry as _registry
    from repro.models import model_init as _model_init
    cfg = BERT_BENCH
    params, _ = _model_init(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    hess = {}
    for m in _registry(cfg):
        X = rng.standard_normal((2 * m.d_in + 64, m.d_in))
        hess[m.name] = jnp.asarray(X.T @ X / len(X), jnp.float32)
    _STATE["db_bench"] = (cfg, params, hess)
    return _STATE["db_bench"]


# Every top-level key any bench may write to BENCH_db.json. The
# analysis suite (ast.bench-key-drift) checks this two-way against the
# _write_bench_db call sites, so adding a bench means declaring its key
# here — drift is a reviewed diff, not a silent new record.
BENCH_KEYS = (
    "db_build", "db_build_compact", "spdy_eval", "spdy_search",
    "calib_shard", "latency_cache", "gradual_family",
    "gradual_family_smoke", "gradual_family_smoke_moe",
    "gradual_family_smoke_ssm", "gradual_family_smoke_gqa",
    "family_sharded", "family_sharded_smoke",
    "chaos", "chaos_smoke", "serve", "serve_smoke",
)


def _write_bench_db(update: dict):
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_db.json")
    rec = {}
    if os.path.exists(path):
        with open(path) as f:
            rec = json.load(f)
    rec.update(update)
    atomic_write_json(path, rec)


def bench_db_build():
    """Database construction wall-clock: the batched engine (grouped vmap,
    Cholesky block solves, fused downdate, slot snapshots) vs the frozen
    seed per-module path, plus the refactored serial path for reference.
    All warm (compile excluded); includes the host float16 conversion."""
    cfg, params, hess = _bench_db_setup()
    mods = registry(cfg)
    n_groups = len({(m.group_size, m.n_structures) for m in mods})

    def run_seed():
        return _seed_build_database(cfg, params, hess)

    def run_serial():
        return build_database(cfg, params, hess, batched=False)

    def run_batched():
        return build_database(cfg, params, hess, batched=True)

    run_batched()                       # warm (compile)
    t0 = time.perf_counter()
    db = run_batched()
    t_batched = time.perf_counter() - t0
    run_serial()                        # warm (compile)
    t0 = time.perf_counter()
    db_s = run_serial()
    t_serial = time.perf_counter() - t0
    run_seed()                          # warm (compile)
    t0 = time.perf_counter()
    db_seed = run_seed()
    t_seed = time.perf_counter() - t0
    _STATE["db_bench_db"] = db

    orders_equal = all(
        bool(np.all(db[m.name].order == db_s[m.name].order))
        and bool(np.all(db[m.name].order == db_seed[m.name][2]))
        for m in mods)
    snap_diff = max(
        float(np.max(np.abs(db[m.name].snapshots.astype(np.float32)
                            - db_seed[m.name][0].astype(np.float32))))
        for m in mods)
    speedup = t_seed / max(t_batched, 1e-12)
    _write_bench_db({"db_build": {
        "config": cfg.name, "modules": len(mods), "groups": n_groups,
        "seed_per_module_s": t_seed, "refactored_serial_s": t_serial,
        "batched_s": t_batched, "speedup_vs_seed": speedup,
        "speedup_vs_refactored_serial": t_serial / max(t_batched, 1e-12),
        "orders_equal": orders_equal, "max_snapshot_diff": snap_diff}})
    row("db_build", t_batched * 1e6,
        f"seed={t_seed*1e3:.0f}ms serial={t_serial*1e3:.0f}ms "
        f"batched={t_batched*1e3:.0f}ms speedup={speedup:.1f}x "
        f"orders_equal={orders_equal} snapdiff={snap_diff:.1e}")


# Wider twin of BERT_BENCH for the compaction bench: at d_ff=384 the
# (d, d) Hinv fits in L2 and the bandwidth win is muted; at d_ff=1024 it
# spills (4 MB/layer) and the shrinking working set pays off — closer to
# the real-model regime the engine targets.
BERT_BENCH_WIDE = BERT_BASE.replace(
    name="bert-wide-cpu", num_layers=4, d_model=128, num_heads=8,
    num_kv_heads=8, head_dim=16, d_ff=1024, vocab_size=512,
    max_position=128, dtype="float32")


def bench_db_build_compact():
    """Live-set-compacted database construction vs the PR-1 batched path:
    same grouped vmap, but Algorithm 1 compacts the surviving structures
    to a shrinking contiguous prefix so per-step downdate traffic tracks
    the live set instead of the dense (d_in, d_in) matrix. Warm timings;
    equivalence (identical orders, fp16 snapshots) checked in-line."""
    # best-of-3 per path: a 2-core container jitters per-run wall clock
    # far more than the engine difference we are measuring
    def best_of(fn, reps=3):
        fn()                            # warm (compile)
        best, out = float("inf"), None
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            best = min(best, time.perf_counter() - t0)
        return best, out

    rec = {}
    detail = []
    for tag, case in [("base", None), ("wide", BERT_BENCH_WIDE)]:
        if case is None:
            cfg, params, hess = _bench_db_setup()
        else:
            cfg = case
            params, _ = model_init(cfg, jax.random.key(0))
            rng = np.random.default_rng(0)
            hess = {}
            for m in registry(cfg):
                X = rng.standard_normal((2 * m.d_in + 64, m.d_in))
                hess[m.name] = jnp.asarray(X.T @ X / len(X), jnp.float32)
        mods = registry(cfg)

        t_compact, db_c = best_of(
            lambda: build_database(cfg, params, hess, batched=True,
                                   compact=True))
        t_batched, db_b = best_of(
            lambda: build_database(cfg, params, hess, batched=True))

        orders_equal = all(
            bool(np.all(db_c[m.name].order == db_b[m.name].order))
            for m in mods)
        snap_diff = max(
            float(np.max(np.abs(db_c[m.name].snapshots.astype(np.float32)
                                - db_b[m.name].snapshots
                                .astype(np.float32))))
            for m in mods)
        speedup = t_batched / max(t_compact, 1e-12)
        rec[tag] = {"config": cfg.name, "modules": len(mods),
                    "d_ff": cfg.d_ff, "batched_s": t_batched,
                    "compact_s": t_compact, "speedup_vs_batched": speedup,
                    "orders_equal": orders_equal,
                    "max_snapshot_diff": snap_diff}
        detail.append(f"{tag}(d_ff={cfg.d_ff}): {t_batched*1e3:.0f}ms->"
                      f"{t_compact*1e3:.0f}ms {speedup:.2f}x "
                      f"orders_equal={orders_equal} "
                      f"snapdiff={snap_diff:.1e}")
    _write_bench_db({"db_build_compact": rec})
    row("db_build_compact", rec["wide"]["compact_s"] * 1e6,
        " | ".join(detail))


def bench_spdy_eval():
    """Per-candidate assignment stitching: device-resident SnapshotCache
    gather vs ~|modules| host snapshot uploads (the SPDY eval hot path)."""
    from repro.core.structures import level_grid
    cfg, params, hess = _bench_db_setup()
    db = _STATE.get("db_bench_db")
    if db is None:
        db = build_database(cfg, params, hess)
    cache = SnapshotCache(cfg, db)
    mods = registry(cfg)
    rng = np.random.default_rng(1)
    cands = [{m.name: int(rng.choice(level_grid(m))) for m in mods}
             for _ in range(32)]

    def run_host():
        for a in cands:
            jax.block_until_ready(
                apply_assignment(cfg, params, db, a)["layers"]["ffn"]["wd"])

    def run_device():
        for a in cands:
            jax.block_until_ready(
                apply_assignment(cfg, params, db, a,
                                 cache=cache)["layers"]["ffn"]["wd"])

    run_device()  # warm
    t0 = time.perf_counter()
    run_device()
    t_dev = (time.perf_counter() - t0) / len(cands)
    run_host()
    t0 = time.perf_counter()
    run_host()
    t_host = (time.perf_counter() - t0) / len(cands)
    speedup = t_host / max(t_dev, 1e-12)
    _write_bench_db({"spdy_eval": {
        "config": cfg.name, "candidates": len(cands),
        "host_us_per_candidate": t_host * 1e6,
        "device_us_per_candidate": t_dev * 1e6, "speedup": speedup}})
    row("spdy_eval", t_dev * 1e6,
        f"host={t_host*1e6:.0f}us device={t_dev*1e6:.0f}us "
        f"speedup={speedup:.1f}x")


# Frozen copy of the PR-3 SPDY search loop (commit 89ae7cf): one strictly
# serial host step per candidate — scalar DP, fresh stitch + loss + blocking
# float() sync every step, no score memo, run from scratch per target. Kept
# verbatim as the spdy_search baseline so the engine speedup is tracked
# across PRs.
def _pr3_search(db, table, target_speedup, *, steps, mutate_frac=0.1,
                nbins=1024, eval_fn=None, seed=0):
    from repro.core.spdy import SearchResult, dp_select
    rng = np.random.default_rng(seed)
    names = list(db.keys())
    priors = [db[n].priors.astype(np.float64) for n in names]
    times = [table.level_times(db[n].mod).astype(np.float64) for n in names]
    dense = table.base + sum(t[0] for t in times)
    budget = dense / target_speedup - table.base

    def assemble(choices):
        return {n: int(db[n].levels[c]) for n, c in zip(names, choices)}

    def runtime(choices):
        return table.base + sum(t[c] for t, c in zip(times, choices))

    coeffs = np.ones(len(names))
    best = None
    for step in range(steps):
        if step == 0:
            cand_coeffs = coeffs
        else:
            cand_coeffs = coeffs.copy()
            mask = rng.random(len(names)) < mutate_frac
            if not mask.any():
                mask[rng.integers(len(names))] = True
            cand_coeffs[mask] *= np.exp(rng.normal(0, 0.6, mask.sum()))
        costs = [c * p for c, p in zip(cand_coeffs, priors)]
        choices, _ = dp_select(costs, times, budget, nbins)
        if choices is None:
            continue
        assignment = assemble(choices)
        score = (eval_fn(assignment) if eval_fn is not None
                 else float(sum(p[c] ** 2 for p, c in zip(priors, choices))))
        if best is None or score < best.score:
            rt = runtime(choices)
            best = SearchResult(assignment=assignment, runtime=rt,
                                speedup=dense / rt, score=score,
                                coeffs=cand_coeffs.copy())
            coeffs = cand_coeffs
    return best


# Deeper tiny GPT2 for the search bench: 16 prunable modules make the DP
# and the per-candidate stitch+eval the dominant cost, as in real models.
SEARCH_CFG = GPT2_SMALL.replace(
    name="gpt2-search-bench", num_layers=8, d_model=96, d_ff=384,
    num_heads=6, num_kv_heads=6, head_dim=16, vocab_size=384,
    dtype="float32")


def bench_spdy_search():
    """Population-batched SPDY search vs the frozen PR-3 serial loop at
    equal steps, single-target and 4-target family, with the stitched-model
    calibration loss as the candidate score (the oneshot hot path).  Also
    times full ``oneshot_prune`` both ways and records engine serial-vs-
    batched equivalence."""
    from repro.core.oneshot import make_batched_eval
    from repro.core.spdy import search, search_family

    cfg = SEARCH_CFG
    params, _ = model_init(cfg, jax.random.key(0))
    calib = calibration_batches(cfg, 16, 64, batch=8)
    env = InferenceEnv(batch=8, seq=64, mode="prefill")
    # measured-on-CPU table: width moves runtime at these dims, so the DP
    # is coefficient-sensitive (the analytic v5e table saturates here)
    table = build_table(cfg, env, backend="measure", grid_subsample=6,
                        reps=2, **LAT_CACHE)
    hess = collect_hessians(cfg, params, calib)
    db = build_database(cfg, params, hess)
    cache = SnapshotCache(cfg, db)
    loss = calib_loss_fn(cfg, calib[:1])

    def ev(a):
        return loss(apply_assignment(cfg, params, db, a, cache=cache))

    evb = make_batched_eval(cfg, params, cache, calib[:1])
    # a realistic target family: the whole point of the amortized engine
    targets = [1.3, 1.5, 2.0, 3.0]
    steps, pop = 160, 32

    # warm every path (jit compiles: stitch, loss, and every power-of-two
    # vmapped-loss bucket the chunked scorer can hit)
    _pr3_search(db, table, 2.0, steps=2, eval_fn=ev)
    mods = registry(cfg)
    rngw = np.random.default_rng(9)
    from repro.core.structures import level_grid as _lg
    dummy = [{m.name: int(rngw.choice(_lg(m))) for m in mods}
             for _ in range(32)]
    for k in [1, 2, 4, 8, 16, 32]:
        evb(dummy[:k])
    search(db, table, 2.0, steps=4, pop=pop, batched=False, eval_fn=ev,
           seed=1)

    rec = {"config": cfg.name, "modules": len(mods),
           "steps_per_target": steps, "pop": pop, "targets": targets}

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        return time.perf_counter() - t0, out

    # single target
    t_pr3, _ = timed(lambda: _pr3_search(db, table, 2.0, steps=steps,
                                         eval_fn=ev, seed=0))
    t_ser, r_ser = timed(lambda: search(
        db, table, 2.0, steps=steps, pop=pop, batched=False, eval_fn=ev,
        seed=0))
    t_bat, r_bat = timed(lambda: search(
        db, table, 2.0, steps=steps, pop=pop, batched=True, eval_fn=ev,
        eval_batched=evb, seed=0))
    rec["single"] = {
        "pr3_serial_s": t_pr3, "engine_serial_s": t_ser,
        "engine_batched_s": t_bat,
        "speedup_vs_pr3": t_pr3 / max(t_bat, 1e-12),
        "speedup_vs_engine_serial": t_ser / max(t_bat, 1e-12),
        "pr3_steps_per_s": steps / max(t_pr3, 1e-12),
        "batched_steps_per_s": steps / max(t_bat, 1e-12),
        "assignments_equal": r_ser.assignment == r_bat.assignment,
        "unique_evals": r_bat.n_evals}

    # 4-target family at equal steps: serial = one PR-3 search per target
    # (the old oneshot loop), batched = one shared-pool family pass
    t_pr3f, _ = timed(lambda: [
        _pr3_search(db, table, t, steps=steps, eval_fn=ev, seed=0)
        for t in targets])
    t_serf, f_ser = timed(lambda: search_family(
        db, table, targets, steps=steps, pop=pop, batched=False,
        eval_fn=ev, seed=0))
    t_batf, f_bat = timed(lambda: search_family(
        db, table, targets, steps=steps, pop=pop, batched=True,
        eval_fn=ev, eval_batched=evb, seed=0))
    rec["family"] = {
        "pr3_serial_s": t_pr3f, "engine_serial_s": t_serf,
        "engine_batched_s": t_batf,
        "speedup_vs_pr3": t_pr3f / max(t_batf, 1e-12),
        "speedup_vs_engine_serial": t_serf / max(t_batf, 1e-12),
        "pr3_steps_per_s": len(targets) * steps / max(t_pr3f, 1e-12),
        "batched_steps_per_s": len(targets) * steps / max(t_batf, 1e-12),
        "assignments_equal": all(
            f_ser[t].assignment == f_bat[t].assignment for t in targets),
        "scores_equal": all(
            abs(f_ser[t].score - f_bat[t].score) < 1e-9 for t in targets),
        "unique_evals": f_bat[targets[0]].n_evals}

    # end-to-end oneshot_prune (hessians + db + table + family search)
    kw = dict(targets=targets, latency_backend="measure",
              latency_kw={**LAT_CACHE, "grid_subsample": 6, "reps": 2},
              search_steps=steps, search_pop=pop, seed=0)
    t_os_s, _ = timed(lambda: oneshot_prune(cfg, params, calib, env,
                                            search_batched=False, **kw))
    t_os_b, _ = timed(lambda: oneshot_prune(cfg, params, calib, env,
                                            search_batched=True, **kw))
    rec["oneshot"] = {"engine_serial_s": t_os_s, "engine_batched_s": t_os_b,
                      "speedup": t_os_s / max(t_os_b, 1e-12)}

    _write_bench_db({"spdy_search": rec})
    row("spdy_search", t_batf * 1e6,
        f"family: pr3={t_pr3f:.1f}s serial={t_serf:.1f}s "
        f"batched={t_batf:.1f}s speedup={rec['family']['speedup_vs_pr3']:.1f}x "
        f"({rec['family']['batched_steps_per_s']:.0f} steps/s) "
        f"single: {rec['single']['speedup_vs_pr3']:.1f}x "
        f"equal={rec['family']['assignments_equal']}")


_CALIB_SHARD_SCRIPT = r"""
import json, time
import jax
from repro.configs import GPT2_SMALL
from repro.core.hessian import collect_hessians
from repro.data import calibration_batches
from repro.distributed.sharding import make_mesh
from repro.models import model_init

CFG = GPT2_SMALL.replace(
    name="gpt2-calib-bench", num_layers=4, d_model=128, d_ff=512,
    num_heads=8, num_kv_heads=8, head_dim=16, vocab_size=512,
    dtype="float32")
params, _ = model_init(CFG, jax.random.key(0))
calib = calibration_batches(CFG, 64, 128, batch=16)
mesh = make_mesh((2,), ("data",))

def timed(**kw):
    collect_hessians(CFG, params, calib[:1], **kw)   # compile warm-up
    t0 = time.perf_counter()
    h = collect_hessians(CFG, params, calib, **kw)
    return time.perf_counter() - t0, h

t_single, h1 = timed()
t_shard, h2 = timed(mesh=mesh)
import jax.numpy as jnp
rel = max(float(jnp.max(jnp.abs(h2[k]-h1[k]))
                / (jnp.max(jnp.abs(h1[k])) + 1e-30)) for k in h1)
print("RESULT" + json.dumps({
    "devices": jax.device_count(), "samples": 64, "batch": 16, "seq": 128,
    "single_device_s": t_single, "sharded_s": t_shard,
    "speedup": t_single / max(t_shard, 1e-12), "hessian_rel_err": rel}))
"""


def bench_calib_shard():
    """Data-parallel calibration speedup on a forced 2-device CPU mesh
    (subprocess: the device count is fixed at jax import)."""
    from repro.launch.subproc import run_forced_devices
    try:
        rec = run_forced_devices(_CALIB_SHARD_SCRIPT, 2)
    except RuntimeError as e:
        row("calib_shard", 0.0, "FAILED: " + str(e)[-200:])
        return
    _write_bench_db({"calib_shard": rec})
    row("calib_shard", rec["sharded_s"] * 1e6,
        f"CPU run: single={rec['single_device_s']*1e3:.0f}ms "
        f"sharded={rec['sharded_s']*1e3:.0f}ms "
        f"speedup={rec['speedup']:.2f}x relerr={rec['hessian_rel_err']:.1e}")


def bench_latency_cache():
    """Measured-table build: cold (every level timed) vs warm (one cache
    read) — the per-environment cost the persistent cache amortizes."""
    import shutil
    import tempfile
    from repro.core import latency as lat
    from repro.core.latency import build_table
    d = tempfile.mkdtemp(prefix="ziplm_latbench_")
    try:
        kw = dict(grid_subsample=4, reps=3)
        t0 = time.perf_counter()
        build_table(TINY, ENV, backend="measure", cache_dir=d, **kw)
        t_cold = time.perf_counter() - t0
        before = dict(lat.TIMING_STATS)
        t0 = time.perf_counter()
        build_table(TINY, ENV, backend="measure", cache_dir=d, **kw)
        t_warm = time.perf_counter() - t0
        reps_on_hit = lat.TIMING_STATS["reps"] - before["reps"]
    finally:
        shutil.rmtree(d, ignore_errors=True)
    rec = {"config": TINY.name, "cold_s": t_cold, "warm_s": t_warm,
           "speedup": t_cold / max(t_warm, 1e-12),
           "timing_reps_on_hit": reps_on_hit}
    _write_bench_db({"latency_cache": rec})
    row("latency_cache", t_warm * 1e6,
        f"cold={t_cold*1e3:.0f}ms warm={t_warm*1e3:.1f}ms "
        f"speedup={rec['speedup']:.0f}x reps_on_hit={reps_on_hit}")


# forced 2-device mesh-sharded vs single-device trainer step throughput
# (the distillation-finetune hot path of the family engine)
_SHARD_STEP_SCRIPT = r"""
import json, tempfile, time
import jax
from repro.configs import GPT2_SMALL
from repro.configs.base import TrainConfig
from repro.data import synthetic_stream
from repro.distributed.sharding import make_mesh, mesh_config_for
from repro.models import model_init
from repro.train.trainer import Trainer

N = __STEPS__
# NOTE: on this 2-core container single-device XLA already saturates both
# cores via intra-op threading, so the forced 2-device split can only
# break even at best here (~0.9x measured); the number tracks the mesh
# path's overhead — the speedup needs devices that add hardware
CFG = GPT2_SMALL.replace(
    name="gpt2-tiny", num_layers=4, d_model=96, d_ff=384, num_heads=6,
    num_kv_heads=6, head_dim=16, vocab_size=384, dtype="float32")
params, specs = model_init(CFG, jax.random.key(0))
teacher, _ = model_init(CFG, jax.random.key(1))
mesh = make_mesh((2,), ("data",))
mc = mesh_config_for(mesh)

def steps_per_s(use_mesh):
    tcfg = TrainConfig(learning_rate=1e-3, total_steps=N + 2,
                       warmup_steps=2, distill_logit=1.0, distill_token=0.5)
    tr = Trainer(CFG, tcfg, ckpt_dir=tempfile.mkdtemp(), ckpt_every=10**6,
                 teacher_params=teacher,
                 mesh=mesh if use_mesh else None,
                 mc=mc if use_mesh else None,
                 specs=specs if use_mesh else None)
    st = tr.init_or_restore(params)
    data = synthetic_stream(CFG, 16, 64, seed=1)
    st = tr.fit(st, data, steps=2)                 # warm (compile)
    t0 = time.perf_counter()
    tr.fit(st, data, steps=N + 2)
    return N / (time.perf_counter() - t0)

single = steps_per_s(False)
shard = steps_per_s(True)
print("RESULT" + json.dumps({
    "devices": jax.device_count(), "steps": N,
    "single_steps_per_s": single, "sharded_steps_per_s": shard,
    "speedup": shard / single}))
"""


def _stage_breakdown(base, targets, seed=0):
    """Per-stage wall-time sums (seconds) from a family manifest's
    ``stage_times`` records: {"hessians": ..., "db": ..., "search": ...,
    "finetune": ..., "export": ...} summed over targets."""
    from repro.core.pipeline import family_run_dir
    path = os.path.join(family_run_dir(TINY, targets, seed, base),
                        "family.json")
    with open(path) as f:
        doc = json.load(f)
    out = {}
    for t in doc["targets"].values():
        for stage, secs in t.get("stage_times", {}).items():
            out[stage] = out.get(stage, 0.0) + secs
    return out


def bench_gradual_family():
    """Stage-checkpointed family engine: end-to-end family wall-time
    under the overlapped vs serial schedule (with the per-stage
    hessians/db/search/finetune/export breakdown from the manifest's
    ``stage_times`` records, and a bit-identity check between the two
    schedules), resume overhead after a mid-target kill (only the
    in-flight stage re-executes; results stay bit-identical), and
    mesh-sharded vs single-device distillation-step throughput on a
    forced 2-device CPU mesh. ``--smoke`` shrinks every knob to a
    CI-sized end-to-end pass."""
    import tempfile

    from repro.core.pipeline import FamilyPreempted
    from repro.launch.subproc import run_forced_devices

    if _SMOKE:
        params, _ = model_init(TINY, jax.random.key(0))
        ft, search, pop, kill, every, shard_steps = 6, 3, 4, 4, 2, 6
    else:
        params, _ = trained_model()
        ft, search, pop, kill, every, shard_steps = 15, 10, 8, 10, 5, 24
    calib = calibration_batches(TINY, 16, 64, batch=8)
    targets = [1.5, 2.0]
    tcfg = TrainConfig(learning_rate=5e-4, warmup_steps=2, total_steps=ft,
                       distill_logit=1.0, distill_token=0.5)
    data = lambda step: synthetic_stream(TINY, 16, 64, seed=21,
                                         start_step=step)
    kw = dict(tcfg=tcfg, finetune_steps=ft, search_steps=search,
              search_pop=pop, ckpt_every=every, seed=0)

    def run(base, **extra):
        t0 = time.perf_counter()
        try:
            v = gradual_prune(TINY, params, ENV, targets, data, calib,
                              ckpt_dir=base, **kw, **extra)
        except FamilyPreempted:
            v = None
        return time.perf_counter() - t0, v

    # warm every jit path with a throwaway family first: the timed runs
    # must compare warm-vs-warm or the compile cost of whichever run goes
    # first drowns the resume overhead being measured
    run(tempfile.mkdtemp(prefix="bench_family_warm"))
    base_full = tempfile.mkdtemp(prefix="bench_family_full")
    t_full, v_full = run(base_full)                  # overlapped (default)
    base_serial = tempfile.mkdtemp(prefix="bench_family_serial")
    t_serial, v_serial = run(base_serial, overlap=False)
    base_kill = tempfile.mkdtemp(prefix="bench_family_kill")
    t_kill, _ = run(base_kill, stop_after=(1, "finetune", kill))
    t_resume, v_res = run(base_kill)

    assignments_equal = all(a.assignment == b.assignment
                            for a, b in zip(v_full, v_res))
    params_equal = all(
        bool(np.all(np.asarray(x) == np.asarray(y)))
        for x, y in zip(jax.tree.leaves(v_full[-1].params),
                        jax.tree.leaves(v_res[-1].params)))
    overlap_bit_identical = all(
        a.assignment == b.assignment and all(
            bool(np.all(np.asarray(x) == np.asarray(y)))
            for x, y in zip(jax.tree.leaves(a.params),
                            jax.tree.leaves(b.params)))
        for a, b in zip(v_full, v_serial))
    overhead = t_kill + t_resume - t_full

    try:
        shard = run_forced_devices(
            _SHARD_STEP_SCRIPT.replace("__STEPS__", str(shard_steps)), 2)
    except RuntimeError as e:
        shard = {"error": str(e)[-200:]}

    rec = {"config": TINY.name, "targets": targets, "finetune_steps": ft,
           "search_steps": search, "smoke": _SMOKE,
           "family_wall_s": t_full, "serial_wall_s": t_serial,
           "overlap_speedup": t_serial / max(t_full, 1e-12),
           "overlap_bit_identical": overlap_bit_identical,
           "stage_breakdown": {
               "overlapped": _stage_breakdown(base_full, targets),
               "serial": _stage_breakdown(base_serial, targets)},
           "killed_run_s": t_kill,
           "resume_s": t_resume, "resume_overhead_s": overhead,
           "resume_overhead_frac": overhead / max(t_full, 1e-12),
           "assignments_equal": assignments_equal,
           "params_bit_identical": params_equal,
           "sharded_step_throughput": shard}
    # the CI smoke pass must not clobber the measured numbers the docs cite
    _write_bench_db(
        {("gradual_family_smoke" if _SMOKE else "gradual_family"): rec})
    sp = shard.get("speedup")
    shard_txt = f"shard_speedup(CPU run)={sp:.2f}x" if sp is not None \
        else "shard FAILED"
    row("gradual_family", t_full * 1e6,
        f"overlap={t_full:.1f}s serial={t_serial:.1f}s "
        f"({rec['overlap_speedup']:.2f}x bitident="
        f"{overlap_bit_identical}) kill+resume={t_kill:.1f}+"
        f"{t_resume:.1f}s overhead={overhead:.1f}s "
        f"equal={assignments_equal}/{params_equal} {shard_txt}")


def _gradual_family_arch(cfg, targets):
    """Shared driver for the per-arch-class family benches: one gradual
    family end-to-end (hessians -> db -> SPDY search -> shrink) on a
    non-GPT2-shaped arch, asserting every member hits its latency-table
    speedup target, and recording how many whole layers SPDY dropped."""
    import tempfile

    from repro.core.shrink import layer_drop_plan

    params, _ = model_init(cfg, jax.random.key(0))
    ft, search, pop = (4, 3, 4) if _SMOKE else (15, 10, 8)
    calib = calibration_batches(cfg, 8, 48, batch=8)
    tcfg = TrainConfig(learning_rate=5e-4, warmup_steps=2, total_steps=ft,
                       distill_logit=1.0, distill_token=0.5)
    data = lambda step: synthetic_stream(cfg, 8, 48, seed=21,
                                         start_step=step)
    t0 = time.perf_counter()
    variants = gradual_prune(
        cfg, params, ENV, targets, data, calib, tcfg=tcfg,
        finetune_steps=ft, search_steps=search, search_pop=pop,
        ckpt_every=2, seed=0,
        ckpt_dir=tempfile.mkdtemp(prefix=f"bench_gf_{cfg.family}"))
    wall = time.perf_counter() - t0
    dense_params = int(sum(x.size for x in jax.tree.leaves(params)))
    rec = {"config": cfg.name, "targets": targets, "smoke": _SMOKE,
           "wall_s": wall, "dense_params": dense_params, "members": {}}
    for v in variants:
        if v.achieved < v.target:
            raise RuntimeError(
                f"{cfg.name}: member {v.target:g}x achieved only "
                f"{v.achieved:.2f}x against its latency table")
        rec["members"][f"{v.target:g}x"] = {
            "achieved_speedup": v.achieved,
            "loss_before_ft": v.loss_before_ft,
            "loss_after_ft": v.loss_after_ft,
            "pruned_params": v.pruned.num_params(),
            "layers_dropped": int(sum(layer_drop_plan(cfg, v.assignment)))}
    return rec


def _row_gradual_family_arch(name, rec):
    last = rec["members"][f"{rec['targets'][-1]:g}x"]
    row(name, rec["wall_s"] * 1e6,
        f"achieved={last['achieved_speedup']:.2f}x "
        f"params={rec['dense_params']}->{last['pruned_params']} "
        f"dropped_layers={last['layers_dropped']} "
        f"loss={last['loss_before_ft']:.3f}->{last['loss_after_ft']:.3f}")


def bench_gradual_family_moe():
    """MoE arch class: per-expert modules at whole-expert (keep-or-drop)
    granularity, router kept full."""
    cfg = smoke_config("phi3.5-moe-42b-a6.6b").replace(
        dtype="float32", moe_prune_unit="expert")
    rec = _gradual_family_arch(cfg, [1.3, 1.6])
    _write_bench_db({"gradual_family_smoke_moe": rec})
    _row_gradual_family_arch("gradual_family_moe", rec)


def bench_gradual_family_ssm():
    """SSM arch class: SSD-head pruning through ssd_scan (attention-free
    mamba2, so the whole prunable surface is SSM heads)."""
    cfg = smoke_config("mamba2-2.7b").replace(dtype="float32")
    rec = _gradual_family_arch(cfg, [1.3, 1.6])
    _write_bench_db({"gradual_family_smoke_ssm": rec})
    _row_gradual_family_arch("gradual_family_ssm", rec)


def bench_gradual_family_gqa():
    """GQA arch class: KV heads pruned with their query-head groups (4
    query / 2 KV heads), shrinking real KV-cache bytes."""
    cfg = smoke_config("qwen2-72b").replace(num_kv_heads=2,
                                            dtype="float32")
    rec = _gradual_family_arch(cfg, [1.3, 1.6])
    _write_bench_db({"gradual_family_smoke_gqa": rec})
    _row_gradual_family_arch("gradual_family_gqa", rec)


# forced 2-device device-parallel family run (sharded Algorithm-1 db
# build + placed SPDY population + overlapped schedule) vs the
# single-device serial reference, bit-identity asserted
_FAMILY_SHARD_SCRIPT = r"""
import json, os, tempfile, time
import jax
import numpy as np

from repro.configs import GPT2_SMALL
from repro.configs.base import TrainConfig
from repro.core.pipeline import family_run_dir, gradual_prune
from repro.data import calibration_batches, synthetic_stream
from repro.distributed.sharding import make_mesh
from repro.models import model_init
from repro.runtime.costmodel import InferenceEnv

SMOKE = __SMOKE__
CFG = GPT2_SMALL.replace(
    name="gpt2-tiny", num_layers=4, d_model=96, d_ff=384, num_heads=6,
    num_kv_heads=6, head_dim=16, vocab_size=384, dtype="float32")
ENV = InferenceEnv(batch=16, seq=128, mode="prefill")
ft, search, pop = (6, 3, 4) if SMOKE else (15, 10, 8)
targets = [1.5, 2.0]
params, _ = model_init(CFG, jax.random.key(0))
# batch=7: per-batch size NOT divisible by the 2 forced devices, so
# Hessian collection takes its documented bit-exact single-device
# fallback — every device-parallel transformation that remains (the
# shard_map'ed Algorithm-1 db build, placed SPDY populations, the
# overlapped schedule, async artifact streaming) is a bit-exact
# rearrangement, making end-to-end bit-identity assertable. The
# fp32-reassociation tolerance of *sharded* Hessian collection is
# covered separately (calib_shard bench, test_sharded_calibration).
calib = calibration_batches(CFG, 21, 64, batch=7)
tcfg = TrainConfig(learning_rate=5e-4, warmup_steps=2, total_steps=ft,
                   distill_logit=1.0, distill_token=0.5)
data = lambda step: synthetic_stream(CFG, 16, 64, seed=21,
                                     start_step=step)
mesh = make_mesh((2,), ("data",))


def run(mesh_, overlap):
    base = tempfile.mkdtemp(prefix="bench_family_sharded")
    t0 = time.perf_counter()
    v = gradual_prune(CFG, params, ENV, targets, data, calib,
                      ckpt_dir=base, seed=0, tcfg=tcfg,
                      finetune_steps=ft, search_steps=search,
                      search_pop=pop, ckpt_every=max(ft // 2, 1),
                      mesh=mesh_, overlap=overlap)
    return time.perf_counter() - t0, v, base


def breakdown(base):
    path = os.path.join(family_run_dir(CFG, targets, 0, base),
                        "family.json")
    with open(path) as f:
        doc = json.load(f)
    out = {}
    for t in doc["targets"].values():
        for stage, secs in t.get("stage_times", {}).items():
            out[stage] = out.get(stage, 0.0) + secs
    return out


run(mesh, True)                                # warm the sharded jits
run(None, False)                               # warm the unsharded jits
t_ref, v_ref, b_ref = run(None, False)         # single-device serial
t_par, v_par, b_par = run(mesh, True)          # device-parallel overlap

bit_identical = all(
    a.assignment == b.assignment
    and a.loss_before_ft == b.loss_before_ft
    and a.loss_after_ft == b.loss_after_ft
    and all(bool(np.all(np.asarray(x) == np.asarray(y)))
            for x, y in zip(jax.tree.leaves(a.params),
                            jax.tree.leaves(b.params)))
    for a, b in zip(v_ref, v_par))
print("RESULT" + json.dumps({
    "devices": jax.device_count(), "smoke": SMOKE,
    "finetune_steps": ft, "search_steps": search,
    "serial_single_device_s": t_ref, "parallel_overlap_s": t_par,
    "speedup": t_ref / max(t_par, 1e-12),
    "bit_identical": bit_identical,
    "stage_breakdown": {"serial": breakdown(b_ref),
                        "parallel": breakdown(b_par)}}))
"""


def bench_family_sharded():
    """Device-parallel family run on a forced 2-device CPU mesh
    (subprocess): sharded db build + placed SPDY population + overlapped
    scheduler vs the single-device serial schedule, with bit-identical
    assignments/scores/params asserted and the per-stage breakdown
    recorded. NOTE: on this 2-core container single-device XLA already
    saturates both cores via intra-op threading, so the measured speedup
    tracks the schedule overlap plus sharding overhead — the sharding
    term needs devices that add hardware."""
    from repro.launch.subproc import run_forced_devices
    try:
        out = run_forced_devices(
            _FAMILY_SHARD_SCRIPT.replace("__SMOKE__", str(_SMOKE)), 2,
            timeout=1800)
    except RuntimeError as e:
        out = {"error": str(e)[-300:]}
    assert out.get("bit_identical", True), \
        f"device-parallel family diverged from serial reference: {out}"
    _write_bench_db(
        {("family_sharded_smoke" if _SMOKE else "family_sharded"): out})
    if "error" in out:
        row("family_sharded", 0.0, f"FAILED {out['error'][-80:]}")
        return
    row("family_sharded", out["parallel_overlap_s"] * 1e6,
        f"CPU run: serial={out['serial_single_device_s']:.1f}s "
        f"parallel={out['parallel_overlap_s']:.1f}s "
        f"speedup={out['speedup']:.2f}x "
        f"bitident={out['bit_identical']}")


def bench_chaos():
    """Robustness-layer economics, three numbers: (1) the cost of running
    fault-free with the full layer armed (a plan whose rules never reach
    their Nth hit) — must be noise, and the outputs bit-identical to the
    clean run; (2) the wall-clock of a genuinely faulty family run (NaN
    calibration batch + transient async-checkpoint write failure + kill
    mid-finetune); (3) the recovery overhead of resuming that run with a
    corrupted db artifact (quarantine + rebuild).  ``--smoke`` shrinks it
    to the CI scenario and writes the ``chaos_smoke`` key."""
    import tempfile

    from repro.core.pipeline import FamilyPreempted, family_run_dir
    from repro.robustness import (FaultPlan, RobustnessReport,
                                  corrupt_bytes, install)

    if _SMOKE:
        ft, search, pop, kill, every = 6, 3, 4, 4, 2
    else:
        ft, search, pop, kill, every = 15, 10, 8, 10, 5
    params, _ = model_init(TINY, jax.random.key(0))
    calib = calibration_batches(TINY, 24, 64, batch=8)   # 3 batches
    targets = [1.5, 2.0]
    tcfg = TrainConfig(learning_rate=5e-4, warmup_steps=2, total_steps=ft,
                       distill_logit=1.0, distill_token=0.5)
    data = lambda step: synthetic_stream(TINY, 16, 64, seed=21,
                                         start_step=step)
    kw = dict(tcfg=tcfg, finetune_steps=ft, search_steps=search,
              search_pop=pop, ckpt_every=every, seed=0)

    def run(base, **extra):
        t0 = time.perf_counter()
        try:
            v = gradual_prune(TINY, params, ENV, targets, data, calib,
                              ckpt_dir=base, **kw, **extra)
        except FamilyPreempted:
            v = None
        return time.perf_counter() - t0, v

    run(tempfile.mkdtemp(prefix="bench_chaos_warm"))     # compile warmup
    t_clean, v_clean = run(tempfile.mkdtemp(prefix="bench_chaos_clean"))

    # (1) armed but fault-free: every site carries a rule that never fires
    armed = FaultPlan.parse(",".join(
        f"{s}:raise@1000000" for s in
        ("calib.batch", "obs.cholesky", "db.artifact_write",
         "ckpt.async_write", "spdy.batched_eval")))
    with install(armed):
        t_armed, v_armed = run(tempfile.mkdtemp(prefix="bench_chaos_armed"))
    identical = (
        all(a.assignment == b.assignment
            for a, b in zip(v_clean, v_armed))
        and all(bool(np.all(np.asarray(x) == np.asarray(y)))
                for x, y in zip(jax.tree.leaves(v_clean[-1].params),
                                jax.tree.leaves(v_armed[-1].params))))

    # (2) chaos run: poisoned calib batch + transient ckpt write failure,
    # killed mid-finetune of the second target
    rep = RobustnessReport()
    base = tempfile.mkdtemp(prefix="bench_chaos_faulty")
    plan = FaultPlan.parse("calib.batch:nan@1,ckpt.async_write:oserror@0")
    with install(plan):
        t_chaos, _ = run(base, report=rep,
                         stop_after=(1, "finetune", kill))

    # (3) corrupt the second target's db artifact, then resume fault-free:
    # quarantine + rebuild + finish the family
    dpath = os.path.join(family_run_dir(TINY, targets, 0, base),
                         "t2", "db.npz")
    corrupt_bytes(dpath, seed=3)
    t_recover, v_rec = run(base, report=rep)
    recovered = (v_rec is not None
                 and os.path.exists(dpath + ".corrupt")
                 and all(np.isfinite(np.asarray(l)).all()
                         for l in jax.tree.leaves(v_rec[-1].params)))
    overhead = t_chaos + t_recover - t_clean

    rec = {"config": TINY.name, "targets": targets, "smoke": _SMOKE,
           "clean_s": t_clean, "armed_fault_free_s": t_armed,
           "armed_overhead_frac": t_armed / max(t_clean, 1e-12) - 1.0,
           "fault_free_bit_identical": identical,
           "chaos_killed_run_s": t_chaos, "chaos_resume_s": t_recover,
           "recovery_overhead_s": overhead,
           "recovery_overhead_frac": overhead / max(t_clean, 1e-12),
           "recovered": recovered,
           "robustness": rep.as_dict()}
    _write_bench_db({("chaos_smoke" if _SMOKE else "chaos"): rec})
    row("chaos", t_clean * 1e6,
        f"clean={t_clean:.1f}s armed={t_armed:.1f}s "
        f"identical={identical} chaos={t_chaos:.1f}+{t_recover:.1f}s "
        f"overhead={overhead:.1f}s recovered={recovered} "
        f"detected={sum(rep.counts['detected'].values())}")


def bench_serve():
    """Continuous-batching serving over a ZipLM family: one resident
    snapshot stack hosts dense + pruned members; every member serves the
    SAME seeded Poisson stream (warm — compiles excluded by warmup) so
    tokens/s, prefill ms, decode ms/token and p50/p99 request latency are
    directly comparable, then a routed mixed-class run exercises the
    latency-class router. Per-layer KV-cache accounting is checked
    in-line: each pruned member's cache bytes must equal the shrunk
    per-layer plan and be strictly below dense."""
    from repro.core.shrink import kv_cache_plan
    from repro.models.layers import compute_dtype
    from repro.serve import DENSE_TARGET, FamilyServer, synthetic_requests

    cfg = TINY
    params, _ = model_init(cfg, jax.random.key(0))
    db = baseline_database(cfg, params, kind="magnitude")
    env = InferenceEnv(batch=4, seq=64, mode="prefill")
    table = build_table(cfg, env, backend="measure", grid_subsample=6,
                        reps=2, **LAT_CACHE)
    targets = [1.5, 2.0]
    assignments = {t: uniform_assignment(cfg, table, t) for t in targets}
    max_len, nslots = 48, 4
    n_req = 8 if _SMOKE else 32
    server = FamilyServer(cfg, params, db, assignments, max_len=max_len,
                          num_slots=nslots)
    server.warmup((8, 16))
    reqs = synthetic_requests(cfg, n_req, seed=0, rate=200.0,
                              prompt_lens=(8, 12, 16),
                              steps_range=(4, 12))

    itemsize = compute_dtype(cfg).itemsize
    members = {}
    for t, eng in sorted(server.members.items()):
        rep = eng.run(reqs)           # same stream through every member
        m = rep.as_dict()
        plan = ([cfg.num_kv_heads] * cfg.num_layers if t == DENSE_TARGET
                else kv_cache_plan(cfg, db, assignments[t]))
        expect = sum(2 * nslots * max_len * h * cfg.head_dim * itemsize
                     for h in plan)
        if m["kv_cache_bytes"] != expect:
            raise RuntimeError(
                f"member {t}x KV bytes {m['kv_cache_bytes']} != per-layer "
                f"plan {expect} (kv heads {plan})")
        m["kv_heads_per_layer"] = plan
        members[f"{t:g}x"] = m
    dense_bytes = members[f"{DENSE_TARGET:g}x"]["kv_cache_bytes"]
    for key, m in members.items():
        if key != f"{DENSE_TARGET:g}x" and m["kv_cache_bytes"] >= dense_bytes:
            raise RuntimeError(
                f"pruned member {key} KV cache ({m['kv_cache_bytes']} B) "
                f"not strictly below dense ({dense_bytes} B)")

    routed = {f"{t:g}x": r.as_dict()
              for t, r in server.run(reqs).items()}

    # GQA-pruned member: KV heads pruned with their query-head groups, so
    # the serve-side cache bytes must strictly shrink on every layer
    from repro.models.pruned import kv_cache_bytes_per_layer
    from repro.serve import PrunedServeModel, ServeEngine

    gcfg = smoke_config("qwen2-72b").replace(num_kv_heads=2,
                                             dtype="float32")
    gparams, _ = model_init(gcfg, jax.random.key(0))
    gdb = baseline_database(gcfg, gparams, kind="magnitude")
    gmods = registry(gcfg)
    ga = {m.name: (1 if m.kind == "attn" else 0) for m in gmods}
    dense_pm = shrink(gcfg, gparams, gdb, {m.name: 0 for m in gmods})
    gpm = shrink(gcfg, gparams, gdb, ga)
    dense_pl = kv_cache_bytes_per_layer(dense_pm, nslots, max_len)
    pruned_pl = kv_cache_bytes_per_layer(gpm, nslots, max_len)
    for l, (d, p) in enumerate(zip(dense_pl, pruned_pl)):
        if p >= d:
            raise RuntimeError(
                f"GQA member: layer {l} cache bytes {p} not strictly "
                f"below dense {d}")
    geng = ServeEngine(PrunedServeModel(gpm, max_len), num_slots=nslots)
    if geng.kv_cache_bytes != sum(pruned_pl):
        raise RuntimeError("GQA member: engine KV bytes disagree with "
                           "per-layer plan")
    geng.warmup((8,))
    greqs = synthetic_requests(gcfg, n_req, seed=0, rate=200.0,
                               prompt_lens=(8, 12, 16),
                               steps_range=(4, 12))
    gqa_member = geng.run(greqs).as_dict()
    gqa_member["kv_heads_per_layer"] = kv_cache_plan(gcfg, gdb, ga)
    gqa_member["dense_kv_cache_bytes"] = sum(dense_pl)

    rec = {"config": cfg.name, "targets": targets, "smoke": _SMOKE,
           "max_len": max_len, "num_slots": nslots, "requests": n_req,
           "members": members, "routed": routed, "gqa_member": gqa_member}
    _write_bench_db({("serve_smoke" if _SMOKE else "serve"): rec})
    d = members[f"{DENSE_TARGET:g}x"]
    detail = [f"dense {d['tokens_per_s']:.0f} tok/s "
              f"kv={d['kv_cache_bytes']//1024}KiB"]
    for t in targets:
        m = members[f"{t:g}x"]
        detail.append(f"{t:g}x {m['tokens_per_s']:.0f} tok/s "
                      f"decode={m['decode_ms_per_token_mean']:.2f}ms "
                      f"kv={m['kv_cache_bytes']//1024}KiB")
    detail.append(f"gqa {gqa_member['tokens_per_s']:.0f} tok/s "
                  f"kv={gqa_member['kv_cache_bytes']//1024}KiB"
                  f"/{gqa_member['dense_kv_cache_bytes']//1024}KiB")
    row("serve", d["decode_ms_per_token_mean"] * 1e3, " | ".join(detail))


def bench_roofline():
    files = sorted(glob.glob(os.path.join(
        os.path.dirname(__file__), "..", "results", "dryrun", "*.json")))
    if not files:
        row("roofline", 0.0, "no dry-run results; run repro.launch.dryrun")
        return
    ok = fail = 0
    worst = (None, 1.0)
    for f in files:
        rec = json.load(open(f))
        if rec.get("status") != "ok":
            fail += 1
            continue
        ok += 1
        if rec["mfu"] < worst[1]:
            worst = (os.path.basename(f), rec["mfu"])
    row("roofline_cells", 0.0,
        f"ok={ok} fail={fail} worst_mfu={worst[1]:.4f}@{worst[0]}")


BENCHES = {
    "table7": bench_table7_latency_table,
    "table3": bench_table3_mlp_speedups,
    "table2": bench_table2_oneshot,
    "table4": bench_table4_calibration,
    "table1": bench_table1_throughput_vs_latency,
    "table8": bench_table8_speedup_guarantee,
    "fig5": bench_fig5_scaling_law,
    "fig2": bench_fig2_gradual,
    "gradual_family": bench_gradual_family,
    "gradual_family_moe": bench_gradual_family_moe,
    "gradual_family_ssm": bench_gradual_family_ssm,
    "gradual_family_gqa": bench_gradual_family_gqa,
    "family_sharded": bench_family_sharded,
    "kernels": bench_kernels,
    "db_build": bench_db_build,
    "db_build_compact": bench_db_build_compact,
    "spdy_eval": bench_spdy_eval,
    "spdy_search": bench_spdy_search,
    "calib_shard": bench_calib_shard,
    "latency_cache": bench_latency_cache,
    "chaos": bench_chaos,
    "serve": bench_serve,
    "roofline": bench_roofline,
}

# benches that run on synthetic weights/hessians; no tiny-GPT2 training
_NO_TRAIN = {"table7", "table3", "kernels", "db_build", "db_build_compact",
             "spdy_eval", "spdy_search", "calib_shard", "latency_cache",
             "roofline", "gradual_family", "gradual_family_moe",
             "gradual_family_ssm", "gradual_family_gqa", "family_sharded",
             "chaos", "serve"}

# --smoke: shrink bench shapes/steps for the CI end-to-end pass
# (currently honored by gradual_family; harmless elsewhere)
_SMOKE = False


def main(argv=None) -> None:
    global _SMOKE
    args = list(argv if argv is not None else sys.argv[1:])
    if "--smoke" in args:
        _SMOKE = True
        args = [a for a in args if a != "--smoke"]
    faults_spec = None
    if "--faults" in args:
        i = args.index("--faults")
        if i + 1 >= len(args):
            raise SystemExit("--faults needs a spec: "
                             "site:mode[@nth][xCOUNT][~DELAY],...")
        faults_spec = args[i + 1]
        del args[i:i + 2]
    flags = [a for a in args if a.startswith("-")]
    if flags:
        raise SystemExit(f"unrecognized option(s) {flags}; "
                         f"usage: run.py [--smoke] [--faults SPEC] "
                         f"[{' | '.join(sorted(BENCHES))}]")
    names = args
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        raise SystemExit(f"unknown benchmark(s) {unknown}; "
                         f"available: {sorted(BENCHES)}")
    selected = names or list(BENCHES)
    from repro.runtime.device import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    import contextlib

    from repro.robustness import FaultPlan, install
    ctx = (install(FaultPlan.parse(
               faults_spec,
               seed=int(os.environ.get("ZIPLM_FAULT_SEED", "0"))))
           if faults_spec else contextlib.nullcontext())
    with ctx:
        if any(n not in _NO_TRAIN for n in selected):
            trained_model()
        for n in selected:
            BENCHES[n]()


if __name__ == "__main__":
    main()
