"""Unit tests for the structured-OBS core (Algorithm 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.obs import (_compaction_schedule, build_hessian,
                            module_drop_error, optimal_update_bruteforce,
                            prune_structured, prune_structured_compact)


def _setup(d_in=24, d_out=12, gs=4, n=300, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d_in))
    W = rng.standard_normal((d_in, d_out))
    h_raw = jnp.asarray(X.T @ X / n, jnp.float32)
    H = build_hessian(h_raw, 1e-6)
    return W, X, h_raw, H, jnp.linalg.inv(H)


@pytest.mark.parametrize("gs", [1, 2, 4, 8])
def test_single_removal_matches_bruteforce(gs):
    W, X, h_raw, H, Hinv = _setup(gs=gs)
    res = prune_structured(jnp.asarray(W, jnp.float32), Hinv, group_size=gs,
                           n_remove=1, levels=(0, 1))
    g = int(res.order[0])
    rows = np.arange(g * gs, (g + 1) * gs)
    ref = optimal_update_bruteforce(W, np.asarray(H), rows)
    np.testing.assert_allclose(res.snapshots[1], ref, atol=2e-3, rtol=1e-3)


def test_selected_structure_is_min_score():
    """Greedy picks the structure whose optimal removal error is smallest."""
    gs = 4
    W, X, h_raw, H, Hinv = _setup(gs=gs)
    n = W.shape[0] // gs
    errs = []
    for g in range(n):
        rows = np.arange(g * gs, (g + 1) * gs)
        Wg = optimal_update_bruteforce(W, np.asarray(H), rows)
        d = np.asarray(Wg) - W
        errs.append(np.einsum("ic,ij,jc->", d, np.asarray(H), d))
    res = prune_structured(jnp.asarray(W, jnp.float32), Hinv, group_size=gs,
                           n_remove=1, levels=(1,))
    assert int(res.order[0]) == int(np.argmin(errs))
    np.testing.assert_allclose(float(res.errors[0]), min(errs), rtol=1e-3)


def test_full_removal_is_clean_and_monotone():
    gs = 2
    W, X, h_raw, H, Hinv = _setup(d_in=16, d_out=8, gs=gs)
    n = W.shape[0] // gs
    levels = tuple(range(n + 1))
    res = prune_structured(jnp.asarray(W, jnp.float32), Hinv, group_size=gs,
                           n_remove=n, levels=levels)
    # last snapshot fully zero
    assert float(jnp.max(jnp.abs(res.snapshots[-1]))) == 0.0
    # errors nondecreasing
    errs = np.asarray(res.errors)
    assert np.all(np.diff(errs) >= -1e-4)
    # every level-k snapshot has exactly k zero groups
    for i, lvl in enumerate(levels):
        snap = np.asarray(res.snapshots[i]).reshape(n, gs, -1)
        zero_groups = int((np.abs(snap).sum((1, 2)) == 0).sum())
        assert zero_groups == lvl


def test_hinv_downdate_matches_fresh_inverse():
    """After removing S, the live block of Hinv equals inv(H[keep,keep])."""
    gs = 3
    W, X, h_raw, H, Hinv = _setup(d_in=15, d_out=6, gs=gs)
    res = prune_structured(jnp.asarray(W, jnp.float32), Hinv, group_size=gs,
                           n_remove=1, levels=(1,))
    g = int(res.order[0])
    rows = np.arange(g * gs, (g + 1) * gs)
    keep = np.setdiff1d(np.arange(15), rows)
    # recompute the downdate manually
    Hi = np.asarray(Hinv, np.float64)
    K = np.linalg.inv(Hi[np.ix_(rows, rows)])
    down = Hi - Hi[:, rows] @ K @ Hi[rows, :]
    fresh = np.linalg.inv(np.asarray(H, np.float64)[np.ix_(keep, keep)])
    np.testing.assert_allclose(down[np.ix_(keep, keep)], fresh,
                               rtol=1e-4, atol=1e-6)


def test_module_drop_error_is_norm():
    W, X, h_raw, H, Hinv = _setup()
    base = float(module_drop_error(jnp.asarray(W, jnp.float32), h_raw))
    direct = float(np.sum((X @ W) ** 2) / X.shape[0])
    np.testing.assert_allclose(base, direct, rtol=1e-4)


def _ffn_levels(n):
    """The production FFN level grid (via structures.level_grid, not a
    re-hardcoded copy) for a synthetic n-row single-row-group module."""
    from repro.core.structures import PrunableModule, level_grid
    mod = PrunableModule(name="t.ffn", kind="ffn", layer=0, group_size=1,
                         n_structures=n)
    return tuple(level_grid(mod))


def test_compaction_schedule_is_static_and_covers_run():
    n, gs, nr = 96, 1, 96
    levels = _ffn_levels(n)
    segs = _compaction_schedule(n, gs, nr, levels, min_rows=16, pad_rows=8)
    assert len(segs) > 1  # actually compacts on this grid
    assert segs[0][0] == 0 and segs[-1][1] == nr
    for (s0, e0, w0, l0), (s1, e1, w1, l1) in zip(segs, segs[1:]):
        assert e0 == s1          # contiguous
        assert w1 < w0           # working set strictly shrinks
        assert l1 <= w1          # live fits in the working slots
        assert s1 in levels      # boundaries sit on level boundaries
    # working arrays always hold the live set
    for s0, e0, w0, l0 in segs:
        assert l0 == n - s0


@pytest.mark.parametrize("gs,d_in,d_out", [(1, 96, 40), (4, 96, 32)])
def test_compact_matches_plain(gs, d_in, d_out):
    """The live-set-compacted run makes identical pruning decisions and
    produces layout-identical snapshots/errors vs the plain core."""
    W, X, h_raw, H, Hinv = _setup(d_in=d_in, d_out=d_out, gs=gs)
    n = d_in // gs
    levels = _ffn_levels(n) if gs == 1 else tuple(range(n + 1))
    nr = max(levels)
    kw = dict(group_size=gs, n_remove=nr, levels=levels)
    segs = _compaction_schedule(n, gs, nr, levels, min_rows=16, pad_rows=8)
    assert len(segs) > 1  # guard: the compact path is actually exercised
    a = prune_structured(jnp.asarray(W, jnp.float32), Hinv, **kw)
    b = prune_structured_compact(jnp.asarray(W, jnp.float32), Hinv,
                                 min_rows=16, pad_rows=8, **kw)
    np.testing.assert_array_equal(np.asarray(a.order), np.asarray(b.order))
    np.testing.assert_allclose(np.asarray(a.errors), np.asarray(b.errors),
                               rtol=1e-5, atol=1e-6)
    # issue tolerance is fp16; the shared per-step math is in fact
    # bit-identical on this backend, but don't over-constrain
    np.testing.assert_allclose(np.asarray(a.snapshots),
                               np.asarray(b.snapshots), atol=2e-3,
                               rtol=2e-3)
    # (order equality above transitively validates the carried perm for
    # every removed structure — a full-removal run removes all of them;
    # test_compact_partial_run_keeps_live_perm covers the live remainder)


def test_compact_partial_run_keeps_live_perm():
    """Stop before full removal: perm maps every live compact slot to the
    right original structure (snapshots already verify the scatter)."""
    W, X, h_raw, H, Hinv = _setup(d_in=64, d_out=16, gs=1)
    levels = (0, 8, 16, 24, 32)
    res = prune_structured_compact(jnp.asarray(W, jnp.float32), Hinv,
                                   group_size=1, n_remove=32,
                                   levels=levels, min_rows=8, pad_rows=8,
                                   ratio=0.9)
    gone = set(np.asarray(res.order).tolist())
    assert len(gone) == 32
    perm = np.asarray(res.perm)
    live = [g for g in range(64) if g not in gone]
    # the live structures all appear among the compact slots, and the
    # final snapshot's nonzero rows sit exactly at the live originals
    assert set(live) <= set(perm.tolist())
    snap = np.asarray(res.snapshots[-1])
    nonzero = np.flatnonzero(np.abs(snap).sum(1))
    assert set(nonzero.tolist()) <= set(live)


def test_correlated_structures_not_both_removed():
    """Paper's S1/S2 example: two duplicated structures — after removing
    one and updating, the twin must carry the weight (not be free to prune).
    """
    rng = np.random.default_rng(3)
    d_in, gs = 8, 2
    X = rng.standard_normal((500, d_in))
    X[:, 2:4] = X[:, 0:2]  # features of group 1 duplicate group 0
    W = rng.standard_normal((d_in, 4))
    h_raw = jnp.asarray(X.T @ X / 500, jnp.float32)
    Hinv = jnp.linalg.inv(build_hessian(h_raw, 1e-4))
    res = prune_structured(jnp.asarray(W, jnp.float32), Hinv, group_size=gs,
                           n_remove=1, levels=(1,))
    first = int(res.order[0])
    assert first in (0, 1)  # one of the duplicated pair goes first (free)
    assert float(res.errors[0]) < 1e-2
    # after the update, the twin now carries both weights
    twin = 1 - first
    snap = np.asarray(res.snapshots[0])
    expect = np.asarray(W)[2 * twin:2 * twin + 2] \
        + np.asarray(W)[2 * first:2 * first + 2]
    np.testing.assert_allclose(snap[2 * twin:2 * twin + 2], expect,
                               atol=0.05, rtol=0.05)


@pytest.mark.parametrize("variant", ["plain", "compact", "batched",
                                     "batched_compact", "sharded"])
def test_algorithm1_executables_have_stable_names(variant):
    """Every Algorithm-1 executable is named ``jit_prune_obs...``, the name
    a profiler trace gives its executions."""
    from repro.core import obs
    W, _, _, _, Hinv = _setup()
    W = jnp.asarray(W, jnp.float32)
    kw = dict(group_size=4, n_remove=2, levels=(0, 2))
    if variant == "sharded":
        mesh = jax.make_mesh((1,), ("data",))
        fn = obs._sharded_prune_jit(mesh, ("data",), 4, 2, (0, 2), False,
                                    None, False, 0.75, 64, 16)
        args, kw = (W[None], Hinv[None]), {}
    else:
        fn = {"plain": obs.prune_structured,
              "compact": obs.prune_structured_compact,
              "batched": obs.prune_structured_batched,
              "batched_compact": obs.prune_structured_batched_compact
              }[variant]
        args = (W[None], Hinv[None]) if "batched" in variant else (W, Hinv)
    name = fn.lower(*args, **kw).as_text().split()[1]
    want = "obs" if variant == "plain" else f"obs_{variant}"
    assert name == f"@jit_prune_{want}"
