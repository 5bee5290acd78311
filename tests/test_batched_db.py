"""Equivalence tests for the batched database-construction engine:
grouped-vmap build_database vs the serial per-module path, the fused
obs_downdate Pallas kernel vs its jnp twin, the device-resident
SnapshotCache vs host-side apply_assignment, and the single-dispatch
Hessian collection vs a per-module reference loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.database import (SnapshotCache, apply_assignment,
                                 build_database, chunk_size, group_modules)
from repro.core.hessian import collect_hessians, xtx
from repro.core.structures import get_capture, level_grid, registry
from repro.kernels import ops, ref


def _rand_hessians(cfg, seed=0):
    rng = np.random.default_rng(seed)
    out = {}
    for m in registry(cfg):
        X = rng.standard_normal((3 * m.d_in + 16, m.d_in))
        out[m.name] = jnp.asarray(X.T @ X / len(X), jnp.float32)
    return out


def test_grouping_covers_registry(tiny_cfg, tiny_params):
    mods = registry(tiny_cfg)
    groups = group_modules(tiny_cfg, tiny_params, mods)
    grouped = [m.name for _, gmods in groups for m in gmods]
    assert sorted(grouped) == sorted(m.name for m in mods)
    # tiny GPT2: one attn group + one ffn group, each with all layers
    assert len(groups) == 2
    assert all(len(gmods) == tiny_cfg.num_layers for _, gmods in groups)


@pytest.mark.parametrize("n_mods,per,max_batch,shards,free,want", [
    # DEVICE_MEM_FRACTION (0.8) of ``free`` is what a chunk may take
    (12, 100, 16, 1, 500, 4),     # 4 fit: three chunks of 4
    (12, 100, 16, 1, 700, 4),     # 5 fit: three chunks, spread evenly
    (12, 100, 16, 1, 10**9, 12),  # all fit: max_batch binds
    (12, 100, 4, 1, 10**9, 4),    # max_batch stays an upper bound
    (12, 100, 16, 4, 300, 6),     # 2 fit per device, 4 devices: 6 + 6
    (12, 100, 16, 1, 50, 1),      # none fit: one module at a time
])
def test_chunk_size_from_device_memory(n_mods, per, max_batch, shards, free,
                                       want):
    assert chunk_size(n_mods, per, max_batch, shards, free=free) == want


def test_chunk_size_without_device_limit():
    """The CPU backend reports no memory limit: only max_batch binds."""
    assert jax.devices()[0].platform == "cpu"
    assert chunk_size(12, 100, 16) == 12
    assert chunk_size(12, 100, 5) == 4


@pytest.mark.parametrize("max_batch", [16, 1])
def test_batched_matches_per_module(tiny_cfg, tiny_params, max_batch):
    hess = _rand_hessians(tiny_cfg)
    db_b = build_database(tiny_cfg, tiny_params, hess, batched=True,
                          max_batch=max_batch)
    db_s = build_database(tiny_cfg, tiny_params, hess, batched=False)
    assert list(db_b) == list(db_s)  # registry order preserved
    for name in db_s:
        a, b = db_s[name], db_b[name]
        np.testing.assert_array_equal(a.levels, b.levels)
        # identical pruning decisions
        np.testing.assert_array_equal(a.order, b.order, err_msg=name)
        np.testing.assert_allclose(a.errors, b.errors, rtol=1e-4,
                                   atol=1e-5, err_msg=name)
        np.testing.assert_allclose(a.priors, b.priors, rtol=1e-4,
                                   atol=1e-5, err_msg=name)
        # snapshots are float16-quantized; compare at that resolution
        np.testing.assert_allclose(
            a.snapshots.astype(np.float32), b.snapshots.astype(np.float32),
            atol=2e-3, rtol=2e-3, err_msg=name)
        assert np.isclose(a.base_norm, b.base_norm, rtol=1e-5)


@pytest.mark.parametrize("compact_serial", [False, True])
def test_compact_db_matches_batched(tiny_cfg, tiny_params, compact_serial):
    """The live-set-compacted engine (batched and serial routes) builds
    the same database as the PR-1 batched path: identical pruning orders,
    fp16-tolerance snapshots."""
    hess = _rand_hessians(tiny_cfg, seed=4)
    db_ref = build_database(tiny_cfg, tiny_params, hess, batched=True)
    db_c = build_database(tiny_cfg, tiny_params, hess,
                          batched=not compact_serial, compact=True)
    assert list(db_ref) == list(db_c)
    for name in db_ref:
        a, b = db_ref[name], db_c[name]
        np.testing.assert_array_equal(a.levels, b.levels)
        np.testing.assert_array_equal(a.order, b.order, err_msg=name)
        np.testing.assert_allclose(a.errors, b.errors, rtol=1e-4,
                                   atol=1e-5, err_msg=name)
        np.testing.assert_allclose(a.priors, b.priors, rtol=1e-4,
                                   atol=1e-5, err_msg=name)
        np.testing.assert_allclose(
            a.snapshots.astype(np.float32), b.snapshots.astype(np.float32),
            atol=2e-3, rtol=2e-3, err_msg=name)


@pytest.mark.parametrize("shape", [(16, 8, 2, 8), (96, 64, 16, 32),
                                   (33, 7, 1, 16), (130, 12, 5, 64)])
def test_obs_downdate_kernel_matches_ref(shape):
    d_in, d_out, gs, block_d = shape
    rng = np.random.default_rng(d_in)
    W = jnp.asarray(rng.standard_normal((d_in, d_out)), jnp.float32)
    H = rng.standard_normal((d_in, d_in))
    Hinv = jnp.asarray(H @ H.T, jnp.float32)
    HcolS = jnp.asarray(rng.standard_normal((d_in, gs)), jnp.float32)
    KsWS = jnp.asarray(rng.standard_normal((gs, d_out)), jnp.float32)
    KsHcolT = jnp.asarray(rng.standard_normal((gs, d_in)), jnp.float32)
    keep = jnp.asarray(rng.random(d_in) > 0.3, jnp.float32)
    w_k, h_k = ops.obs_downdate(W, Hinv, HcolS, KsWS, KsHcolT, keep,
                                block_d=block_d, interpret=True)
    w_r, h_r = ref.obs_downdate_ref(W, Hinv, HcolS, KsWS, KsHcolT, keep)
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_r),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("shape", [(96, 24, 4, 64, 32), (130, 12, 1, 96, 64),
                                   (64, 16, 8, 32, 16)])
def test_obs_downdate_d_live_prefix(shape):
    """With dead (zero) rows/cols beyond d_live, the prefix-restricted
    downdate equals the full one — on the ref oracle and the kernel."""
    d_in, d_out, gs, d_live, block_d = shape
    rng = np.random.default_rng(d_live)

    def dead_tail(a, rows=True, cols=False):
        a = np.asarray(a)
        if rows:
            a[d_live:] = 0.0
        if cols and a.ndim == 2:
            a[..., d_live:] = 0.0
        return jnp.asarray(a, jnp.float32)

    W = dead_tail(rng.standard_normal((d_in, d_out)))
    H = rng.standard_normal((d_in, d_in))
    Hinv = dead_tail(H @ H.T, cols=True)
    HcolS = dead_tail(rng.standard_normal((d_in, gs)))
    KsWS = jnp.asarray(rng.standard_normal((gs, d_out)), jnp.float32)
    KsHcolT = dead_tail(rng.standard_normal((gs, d_in)).T).T
    keep = dead_tail(rng.random(d_in) > 0.3)

    w_f, h_f = ref.obs_downdate_ref(W, Hinv, HcolS, KsWS, KsHcolT, keep)
    w_r, h_r = ref.obs_downdate_ref(W, Hinv, HcolS, KsWS, KsHcolT, keep,
                                    d_live=d_live)
    w_k, h_k = ops.obs_downdate(W, Hinv, HcolS, KsWS, KsHcolT, keep,
                                d_live=d_live, block_d=block_d,
                                interpret=True)
    for got_w, got_h in [(w_r, h_r), (w_k, h_k)]:
        np.testing.assert_allclose(np.asarray(got_w), np.asarray(w_f),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got_h), np.asarray(h_f),
                                   atol=1e-5, rtol=1e-5)


def test_snapshot_cache_matches_host_apply(tiny_cfg, tiny_params):
    hess = _rand_hessians(tiny_cfg, seed=1)
    db = build_database(tiny_cfg, tiny_params, hess)
    cache = SnapshotCache(tiny_cfg, db)
    rng = np.random.default_rng(2)
    for trial in range(3):
        assignment = {m.name: int(rng.choice(level_grid(m)))
                      for m in registry(tiny_cfg)}
        p_host = apply_assignment(tiny_cfg, tiny_params, db, assignment)
        p_dev = apply_assignment(tiny_cfg, tiny_params, db, assignment,
                                 cache=cache)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            p_host, p_dev)


def test_snapshot_cache_partial_assignment_falls_back(tiny_cfg,
                                                      tiny_params):
    """A partial assignment must not go through the cache stitch."""
    hess = _rand_hessians(tiny_cfg, seed=3)
    db = build_database(tiny_cfg, tiny_params, hess)
    cache = SnapshotCache(tiny_cfg, db)
    name = registry(tiny_cfg)[0].name
    partial = {name: int(db[name].levels[1])}
    assert not cache.covers(partial)
    p = apply_assignment(tiny_cfg, tiny_params, db, partial, cache=cache)
    w = np.asarray(db[name].weights_at(partial[name]), np.float32)
    got = np.asarray(p["layers"]["attn"]["wo"][0])
    np.testing.assert_array_equal(got, w)


def test_snapshot_cache_heterogeneous_grids(tiny_cfg, tiny_params):
    """Modules of one kind with *different* level grids: each must be
    stitched against its own grid — a single shared grid per kind maps
    some assignments to the wrong snapshot index."""
    from repro.core.database import ModuleDB
    from repro.core.structures import PrunableModule

    d_in, d_out = tiny_cfg.d_ff, tiny_cfg.d_model
    rng = np.random.default_rng(7)

    def mk(layer, levels):
        mod = PrunableModule(name=f"L{layer}.ffn", kind="ffn", layer=layer,
                             weight_key="wd", capture_key="wd_in",
                             group_size=1, n_structures=d_in)
        snaps = rng.standard_normal(
            (len(levels), d_in, d_out)).astype(np.float16)
        return ModuleDB(mod=mod, levels=np.asarray(levels),
                        snapshots=snaps,
                        errors=np.linspace(0.0, 1.0, len(levels)),
                        priors=np.linspace(0.0, 1.0, len(levels)),
                        base_norm=1.0,
                        order=np.arange(d_in, dtype=np.int32))

    # same grid length (so a naive shared stack still builds) but
    # different values: level 32 is index 2 on L1's grid, index 1 on L0's
    db = {"L0.ffn": mk(0, [0, 64, 96, 128]),
          "L1.ffn": mk(1, [0, 16, 32, 128])}
    cache = SnapshotCache(tiny_cfg, db)
    assignment = {"L0.ffn": 96, "L1.ffn": 32}
    assert cache.covers(assignment)
    p_host = apply_assignment(tiny_cfg, tiny_params, db, assignment)
    p_dev = apply_assignment(tiny_cfg, tiny_params, db, assignment,
                             cache=cache)
    np.testing.assert_array_equal(
        np.asarray(p_host["layers"]["ffn"]["wd"]),
        np.asarray(p_dev["layers"]["ffn"]["wd"]))


def test_fused_hessian_collect_matches_reference(tiny_cfg, tiny_params,
                                                 tiny_calib):
    """The single-dispatch step equals the seed's per-module loop."""
    from repro.models.transformer import forward

    got = collect_hessians(tiny_cfg, tiny_params, tiny_calib)

    mods = registry(tiny_cfg)
    want, counts = {}, {}

    @jax.jit
    def captured(params, tokens, frontend):
        return forward(tiny_cfg, params, tokens, frontend_embeds=frontend,
                       capture=True)["captures"]

    for batch in tiny_calib:
        caps = captured(tiny_params, batch["tokens"],
                        batch.get("frontend"))
        for mod in mods:
            x, valid = get_capture(caps, mod)
            h = xtx(x, valid)
            want[mod.name] = want.get(mod.name, 0.0) + h
            n = (float(x.shape[0]) if valid is None
                 else float(jnp.sum(valid)))
            counts[mod.name] = counts.get(mod.name, 0.0) + n
    for k in want:
        want[k] = want[k] / max(counts[k], 1.0)

    assert sorted(got) == sorted(want)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   atol=1e-4, rtol=1e-4, err_msg=k)
