"""Population-batched SPDY engine: batched-DP/search equivalence vs the
serial reference, score memoization, per-target RNG fold-in, family pool
sharing, and the batched stitch+loss used for population scoring."""
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.database import (ModuleDB, SnapshotCache, apply_assignment,
                                 build_database)
from repro.core.hessian import collect_hessians
from repro.core.latency import LatencyTable, build_table
from repro.core.oneshot import (batched_calib_loss_fn, calib_loss_fn,
                                make_batched_eval, oneshot_prune)
from repro.core.spdy import (_spawn_rngs, dp_select, dp_select_batched,
                             quantize_times, search, search_family)
from repro.core.structures import PrunableModule, level_grid, registry
from repro.runtime.costmodel import InferenceEnv

ENV = InferenceEnv(batch=16, seq=128, mode="prefill")


# ----------------------------------------------------------------------
# synthetic search problem: coefficient-sensitive DP, no jax involved
# ----------------------------------------------------------------------

def synth_problem(m=6, n=8, seed=3):
    """m ffn-like modules with n structures each, random decreasing times
    and random ascending priors — the DP solution moves with the
    sensitivity coefficients, unlike saturated tiny costmodel tables."""
    rng = np.random.default_rng(seed)
    db = {}
    grid = np.arange(n + 1)
    for i in range(m):
        mod = PrunableModule(name=f"m{i}", kind="ffn", layer=i,
                             weight_key="wd", capture_key="wd_in",
                             group_size=1, n_structures=n)
        pr = np.sort(rng.random(n + 1))
        pr[0], pr[-1] = 0.0, 1.0
        db[mod.name] = ModuleDB(
            mod=mod, levels=grid.copy(),
            snapshots=np.zeros((n + 1, n, 4), np.float16),
            errors=pr ** 2, priors=pr, base_norm=1.0,
            order=np.arange(n))
    tab = LatencyTable(env=ENV)
    base_t = rng.random() * 2 + 1.0
    tab.grids["ffn"] = grid.astype(np.float64)
    # strictly decreasing, irregular level times
    tab.times["ffn"] = np.sort(rng.random(n + 1) * base_t)[::-1].copy()
    tab.times["ffn"][-1] = 0.0
    tab.base = 0.1
    return db, tab


def test_dp_select_batched_matches_scalar_property():
    """Property test over random costs/times/budgets: every row of the
    batched DP must reproduce the scalar reference exactly, including
    infeasible rows."""
    rng = np.random.default_rng(0)
    for trial in range(8):
        m = int(rng.integers(2, 7))
        P = int(rng.integers(1, 9))
        nbins = int(rng.choice([64, 256, 1024]))
        Ls = rng.integers(2, 9, m)
        times = [np.sort(rng.random(L) * 3)[::-1].copy() for L in Ls]
        # sometimes prunable-to-zero, sometimes not
        if trial % 2 == 0:
            for t in times:
                t[-1] = 0.0
        costs = [rng.random((P, L)) * 10 for L in Ls]
        # budgets from infeasible to slack
        frac = [0.2, 0.6, 1.1, 2.0][trial % 4]
        budget = frac * sum(float(t[-1]) for t in times) + \
            frac * 0.3 * sum(float(t[0]) for t in times)
        chb, totb = dp_select_batched(costs, times=times, budget=budget,
                                      nbins=nbins)
        for p in range(P):
            cs, ts = dp_select([c[p] for c in costs], times, budget, nbins)
            if cs is None:
                assert chb[p, 0] == -1 and not np.isfinite(totb[p])
            else:
                assert np.array_equal(cs, chb[p]), (trial, p)
                assert ts == totb[p]


def test_dp_select_batched_prequantized_times():
    """Quantizing times once per (budget, nbins) and passing ``tq`` must
    match the quantize-inside call bit for bit."""
    rng = np.random.default_rng(1)
    times = [np.sort(rng.random(5) * 2)[::-1].copy() for _ in range(4)]
    costs = [rng.random((6, 5)) for _ in range(4)]
    budget = 0.7 * sum(t[0] for t in times)
    tq = quantize_times(times, budget, 512)
    ch_a, tot_a = dp_select_batched(costs, times=times, budget=budget,
                                    nbins=512)
    ch_b, tot_b = dp_select_batched(costs, tq=tq, nbins=512)
    assert np.array_equal(ch_a, ch_b)
    assert np.array_equal(tot_a, tot_b)


def test_search_batched_matches_serial_exact():
    """Same seed ⇒ the population-batched search and the serial reference
    return identical best assignments, scores, and step histories
    (analytic prior scoring: bit-exact)."""
    db, tab = synth_problem()
    for pop in [1, 4, 16]:
        r_s = search(db, tab, 2.0, steps=60, pop=pop, batched=False, seed=7)
        r_b = search(db, tab, 2.0, steps=60, pop=pop, batched=True, seed=7)
        assert r_s.assignment == r_b.assignment
        assert r_s.score == r_b.score
        assert r_s.history == r_b.history
        assert r_s.runtime == r_b.runtime
        np.testing.assert_array_equal(r_s.coeffs, r_b.coeffs)
        assert r_b.speedup >= 2.0 - 1e-6


def test_batched_eval_out_of_memory_fails_the_search():
    """A device out-of-memory error in the batched population eval is a
    sizing fault: it fails the search instead of demoting to the serial
    path, which would hide it behind a slower run."""
    from repro.robustness.report import RobustnessReport, report_scope
    db, tab = synth_problem()

    def eval_batched(assigns):
        raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying "
                           "to allocate 4.39G")

    rep = RobustnessReport()
    with report_scope(rep), pytest.raises(RuntimeError,
                                          match="RESOURCE_EXHAUSTED"):
        search(db, tab, 2.0, steps=8, pop=4, batched=True, seed=0,
               eval_fn=lambda a: float(sum(a.values())),
               eval_batched=eval_batched)
    assert not rep.counts["demotions"]
    assert not rep.breaker_open("spdy.batched_eval")


def test_search_memoizes_candidate_scores():
    """Duplicate DP solutions must not be re-evaluated: every eval_fn call
    sees a never-before-scored assignment, and the total is well below the
    step count."""
    db, tab = synth_problem()
    for batched in [False, True]:
        seen = set()

        def ev(a):
            key = tuple(sorted(a.items()))
            assert key not in seen, "memoized assignment re-evaluated"
            seen.add(key)
            return float(sum(a.values()))

        res = search(db, tab, 2.0, steps=80, batched=batched, seed=0,
                     eval_fn=ev)
        assert res.n_evals == len(seen)
        assert len(seen) < 80, "mutation steps should repeat DP solutions"
        assert len(res.history) > len(seen)


def test_per_target_rng_streams_fold_in():
    """Targets derive independent mutation streams from one seed — they no
    longer replay the same candidate sequence."""
    r0, r1 = _spawn_rngs(0, 2)
    a, b = r0.random(16), r1.random(16)
    assert not np.array_equal(a, b)
    # deterministic: same fold-in, same stream
    r0b = _spawn_rngs(0, 2)[0]
    np.testing.assert_array_equal(a, r0b.random(16))

    db, tab = synth_problem()
    names = list(db)
    times = [tab.level_times(db[n].mod) for n in names]
    t1, t2 = 2.0, 2.0 + 1e-9      # same budget after quantization
    dense = tab.base + sum(t[0] for t in times)
    tq1 = quantize_times(times, dense / t1 - tab.base)
    tq2 = quantize_times(times, dense / t2 - tab.base)
    assert all(np.array_equal(x, y) for x, y in zip(tq1, tq2))
    fam = search_family(db, tab, [t1, t2], steps=60, seed=0,
                        share_pool=False)
    assert fam[t1].history != fam[t2].history, \
        "equal-budget targets replayed one RNG stream"


def test_family_shares_candidate_pool():
    """Target index 0 of a family sees exactly its own single-target
    candidate stream; cross-target harvesting can only improve a target's
    best score, and every family member keeps its speedup guarantee."""
    db, tab = synth_problem()
    targets = [1.5, 2.5]
    single = search(db, tab, 1.5, steps=60, seed=4)
    fam = search_family(db, tab, targets, steps=60, seed=4)
    assert fam[1.5].history == single.history
    assert fam[1.5].score <= single.score
    for t in targets:
        assert fam[t].speedup >= t - 1e-6
    # harvested assignments still honor the adopting target's budget
    no_share = search_family(db, tab, targets, steps=60, seed=4,
                             share_pool=False)
    for t in targets:
        assert fam[t].score <= no_share[t].score


# ----------------------------------------------------------------------
# batched stitch + vmapped loss on a real tiny model
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_db(tiny_cfg, tiny_params, tiny_calib):
    hess = collect_hessians(tiny_cfg, tiny_params, tiny_calib)
    db = build_database(tiny_cfg, tiny_params, hess)
    return db, SnapshotCache(tiny_cfg, db)


def _random_assignments(cfg, n, seed):
    rng = np.random.default_rng(seed)
    mods = registry(cfg)
    return [{m.name: int(rng.choice(level_grid(m))) for m in mods}
            for _ in range(n)]


def test_apply_batched_matches_apply(tiny_cfg, tiny_params, tiny_db):
    db, cache = tiny_db
    cands = _random_assignments(tiny_cfg, 4, seed=0)
    batched = cache.apply_batched(tiny_params, cands)
    axes = cache.batch_axes(tiny_params)
    flat_b, tree_b = jax.tree_util.tree_flatten(batched)
    flat_p, tree_p = jax.tree_util.tree_flatten(tiny_params)
    flat_a, _ = jax.tree_util.tree_flatten(
        axes, is_leaf=lambda x: x is None)
    assert tree_b == tree_p
    n_stitched = 0
    for leaf_b, leaf_p, ax in zip(flat_b, flat_p, flat_a):
        if ax is None:
            # untouched leaves broadcast: same array, no population axis
            assert leaf_b.shape == leaf_p.shape
        else:
            assert leaf_b.shape == (len(cands),) + leaf_p.shape
            n_stitched += 1
    assert n_stitched >= 1
    for p, a in enumerate(cands):
        one = cache.apply(tiny_params, a)
        flat_o, _ = jax.tree_util.tree_flatten(one)
        for leaf_b, leaf_o, ax in zip(flat_b, flat_o, flat_a):
            got = leaf_b[p] if ax == 0 else leaf_b
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(leaf_o))


def test_batched_loss_matches_serial(tiny_cfg, tiny_params, tiny_calib,
                                     tiny_db):
    db, cache = tiny_db
    cands = _random_assignments(tiny_cfg, 5, seed=1)
    loss = calib_loss_fn(tiny_cfg, tiny_calib[:2])
    want = np.asarray([loss(cache.apply(tiny_params, a)) for a in cands])
    loss_b = batched_calib_loss_fn(tiny_cfg, tiny_calib[:2],
                                   cache.batch_axes(tiny_params))
    got = np.asarray(loss_b(cache.apply_batched(tiny_params, cands)))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # the make_batched_eval wrapper (pads to power-of-two) agrees too
    evb = make_batched_eval(tiny_cfg, tiny_params, cache, tiny_calib[:2])
    np.testing.assert_allclose(evb(cands), want, rtol=1e-6, atol=1e-6)


def test_calib_loss_trace_size_constant(tiny_cfg, tiny_params, tiny_calib):
    """Stacked+scanned calibration loss: adding same-shape eval batches
    must not grow the jitted trace (the old list unroll did), and the
    value stays the mean of per-batch losses."""
    assert len(tiny_calib) >= 2

    def inner_eqns(fn):
        # unwrap the jit: make_jaxpr of a jitted fn is always one pjit
        # eqn, so count the traced body's equations instead
        jp = jax.make_jaxpr(fn)(tiny_params).jaxpr
        if len(jp.eqns) == 1 and jp.eqns[0].primitive.name == "pjit":
            jp = jp.eqns[0].params["jaxpr"].jaxpr
        return len(jp.eqns)

    f2 = calib_loss_fn(tiny_cfg, tiny_calib[:1])
    f8 = calib_loss_fn(tiny_cfg, tiny_calib)
    n2 = inner_eqns(f2._jitted)
    n8 = inner_eqns(f8._jitted)
    assert n8 == n2, (n2, n8)
    per = [calib_loss_fn(tiny_cfg, [b])(tiny_params) for b in tiny_calib]
    assert f8(tiny_params) == pytest.approx(float(np.mean(per)), rel=1e-6)


def test_search_with_loss_serial_vs_batched(tiny_cfg, tiny_params,
                                            tiny_calib, tiny_db):
    """End-to-end equivalence with the real stitched-model loss: the
    population-batched search (vmapped eval, one sync per round) finds the
    same best assignment as the serial per-candidate path."""
    db, cache = tiny_db
    tab = build_table(tiny_cfg, ENV, backend="costmodel")
    loss = calib_loss_fn(tiny_cfg, tiny_calib[:1])

    def ev(a):
        return loss(apply_assignment(tiny_cfg, tiny_params, db, a,
                                     cache=cache))

    evb = make_batched_eval(tiny_cfg, tiny_params, cache, tiny_calib[:1])
    r_s = search(db, tab, 2.0, steps=24, batched=False, seed=0, eval_fn=ev)
    r_b = search(db, tab, 2.0, steps=24, batched=True, seed=0, eval_fn=ev,
                 eval_batched=evb)
    # the two eval paths are separately compiled, so scores may differ at
    # ULP level and near-ties can pick a twin assignment; the invariant is
    # equally good results (bit-exact equivalence is proven under the
    # deterministic analytic score above)
    assert r_b.score == pytest.approx(r_s.score, rel=1e-6)
    assert r_b.speedup >= 2.0 - 1e-6 and r_s.speedup >= 2.0 - 1e-6


def test_oneshot_family_batched_matches_serial(tiny_cfg, tiny_params,
                                               tiny_calib):
    """`oneshot_prune` through the batched family engine returns the same
    assignments as the serial reference engine (analytic scoring:
    bit-exact), with every target's guarantee intact."""
    targets = [1.5, 2.0]
    kw = dict(search_steps=12, eval_with_loss=False, seed=0)
    # generator targets: oneshot must normalize the iterable it consumes
    # twice (family search, then per-target variants)
    res_b = oneshot_prune(tiny_cfg, tiny_params, tiny_calib, ENV,
                          targets=(t for t in targets),
                          search_batched=True, **kw)
    res_s = oneshot_prune(tiny_cfg, tiny_params, tiny_calib, ENV,
                          targets=targets, search_batched=False, **kw)
    assert set(res_b.variants) == set(targets)
    for t in targets:
        vb, vs = res_b.variants[t], res_s.variants[t]
        assert vb.assignment == vs.assignment
        assert vb.search.score == vs.search.score
        assert vb.speedup >= t - 1e-6
