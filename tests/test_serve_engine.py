"""Continuous-batching serving engine: exactness vs the sequential
oracle, cache-sizing contract, pruned KV accounting, family routing,
metric attribution, and fault recovery at ``serve.step``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.latency import build_table
from repro.core.magnitude import baseline_database, uniform_assignment
from repro.core.shrink import kv_cache_plan, shrink, shrink_from_stitched
from repro.data import synthetic_stream
from repro.models import generate
from repro.models.pruned import (decode_step_pruned, kv_cache_bytes,
                                 prefill_pruned)
from repro.robustness import (FaultPlan, RobustnessReport, install,
                              report_scope)
from repro.runtime.costmodel import InferenceEnv
from repro.serve import (CLASS_SPEEDUP, DenseServeModel, FamilyServer,
                         PrunedServeModel, Request, ServeEngine,
                         synthetic_requests)
from repro.serve import engine as engine_mod

MAX_LEN = 48


def _requests(cfg, n=6, seed=3, steps_range=(3, 8)):
    return synthetic_requests(cfg, n, seed=seed, rate=300.0,
                              prompt_lens=(5, 9, 13),
                              steps_range=steps_range)


@pytest.fixture(scope="module")
def dense_engine(tiny_cfg, tiny_params):
    eng = ServeEngine(DenseServeModel(tiny_cfg, tiny_params, MAX_LEN),
                      num_slots=2)
    eng.warmup((8, 16))
    return eng


@pytest.fixture(scope="module")
def mag_db(tiny_cfg, tiny_params):
    return baseline_database(tiny_cfg, tiny_params, kind="magnitude")


def _half_heads_assignment(tiny_cfg, mag_db):
    """Keep half the KV heads in every attention module, full FFN."""
    a = {}
    for l in range(tiny_cfg.num_layers):
        name = f"L{l}.attn"
        levels = mag_db[name].levels
        want = tiny_cfg.num_kv_heads // 2      # remove half the groups
        assert want in levels, (want, levels)
        a[name] = int(want)
        a[f"L{l}.ffn"] = 0
    return a


# ----------------------------------------------------------------------
# engine == sequential generate (the no-leakage / no-corruption oracle)
# ----------------------------------------------------------------------

def test_engine_matches_sequential_generate(tiny_cfg, tiny_params,
                                            dense_engine):
    """Staggered arrivals, mixed prompt lengths, and slot reuse (6
    requests through 2 slots) produce exactly the tokens each request
    would get alone through ``generate``."""
    reqs = _requests(tiny_cfg)
    assert len({r.prompt_len for r in reqs}) > 1
    report = dense_engine.run(reqs)
    assert report.steps > 0
    for req, rec in zip(reqs, report.records):
        ref = generate(tiny_cfg, tiny_params, req.tokens[None, :],
                       steps=req.steps, max_len=MAX_LEN)
        assert rec.tokens == list(np.asarray(ref[0])), f"rid={req.rid}"
        assert 0 <= rec.t_admit < rec.t_first <= rec.t_done
        assert rec.latency_s >= rec.ttft_s > 0


def test_engine_rejects_cache_overflow(tiny_cfg, dense_engine):
    bad = Request(rid=0, tokens=np.zeros(40, np.int64),
                  steps=MAX_LEN - 40 + 1, arrival=0.0)
    with pytest.raises(RuntimeError, match="overflows the KV cache"):
        dense_engine.run([bad])


# ----------------------------------------------------------------------
# satellite: generate cache sizing (pre-fix: silent write-index clamp)
# ----------------------------------------------------------------------

def test_generate_default_cache_fits_generation(tiny_cfg, tiny_params):
    """Pre-fix, ``serve_prefill``'s ``2*s`` default sized the cache at 8
    for a 4-token prompt, so step 5+ silently clamped the write index and
    corrupted every later token. The default must fit s + steps."""
    prompt = next(synthetic_stream(tiny_cfg, 1, 4))["tokens"]
    out_default = generate(tiny_cfg, tiny_params, prompt, steps=20)
    out_roomy = generate(tiny_cfg, tiny_params, prompt, steps=20,
                         max_len=64)
    np.testing.assert_array_equal(out_default, out_roomy)


def test_generate_raises_on_explicit_overflow(tiny_cfg, tiny_params):
    prompt = next(synthetic_stream(tiny_cfg, 1, 4))["tokens"]
    with pytest.raises(RuntimeError, match="overflows the KV cache"):
        generate(tiny_cfg, tiny_params, prompt, steps=20, max_len=8)


# ----------------------------------------------------------------------
# satellite: sampling (pre-fix: key= was accepted and ignored)
# ----------------------------------------------------------------------

def test_generate_sampling_uses_the_key(tiny_cfg, tiny_params):
    prompt = next(synthetic_stream(tiny_cfg, 2, 8))["tokens"]
    greedy = generate(tiny_cfg, tiny_params, prompt, steps=8)
    k0 = jax.random.key(0)
    s0a = generate(tiny_cfg, tiny_params, prompt, steps=8, key=k0,
                   temperature=2.0)
    s0b = generate(tiny_cfg, tiny_params, prompt, steps=8, key=k0,
                   temperature=2.0)
    s1 = generate(tiny_cfg, tiny_params, prompt, steps=8,
                  key=jax.random.key(1), temperature=2.0)
    np.testing.assert_array_equal(s0a, s0b)    # same key reproduces
    assert not np.array_equal(s0a, s1)         # different key differs
    assert not np.array_equal(s0a, greedy)     # pre-fix: all were greedy


def test_generate_topk1_is_greedy(tiny_cfg, tiny_params):
    prompt = next(synthetic_stream(tiny_cfg, 2, 8))["tokens"]
    greedy = generate(tiny_cfg, tiny_params, prompt, steps=6)
    topk1 = generate(tiny_cfg, tiny_params, prompt, steps=6,
                     key=jax.random.key(7), top_k=1)
    np.testing.assert_array_equal(greedy, topk1)


# ----------------------------------------------------------------------
# pruned members: stitched shrink, decode oracle, KV byte accounting
# ----------------------------------------------------------------------

def test_shrink_from_stitched_matches_shrink(tiny_cfg, tiny_params,
                                             mag_db):
    from repro.core.database import SnapshotCache
    a = _half_heads_assignment(tiny_cfg, mag_db)
    ref = shrink(tiny_cfg, tiny_params, mag_db, a)
    stitched = SnapshotCache(tiny_cfg, mag_db).apply(tiny_params, a)
    dev = shrink_from_stitched(tiny_cfg, stitched, mag_db, a)
    for lr, ld in zip(ref.layers, dev.layers):
        assert lr.kv_groups == ld.kv_groups and lr.d_ff == ld.d_ff
        for (pr, pd) in zip(jax.tree.leaves(lr.params),
                            jax.tree.leaves(ld.params)):
            np.testing.assert_array_equal(np.asarray(pr), np.asarray(pd))
    for gr, gd in zip(jax.tree.leaves(ref.globals_),
                      jax.tree.leaves(dev.globals_)):
        np.testing.assert_array_equal(np.asarray(gr), np.asarray(gd))


def test_pruned_engine_matches_sequential_decode(tiny_cfg, tiny_params,
                                                 mag_db):
    a = _half_heads_assignment(tiny_cfg, mag_db)
    pm = shrink(tiny_cfg, tiny_params, mag_db, a)
    eng = ServeEngine(PrunedServeModel(pm, MAX_LEN), num_slots=2)
    eng.warmup((8, 16))
    reqs = _requests(tiny_cfg, n=4, seed=11)
    report = eng.run(reqs)
    for req, rec in zip(reqs, report.records):
        logits, cache = prefill_pruned(pm, jnp.asarray(req.tokens[None]),
                                       MAX_LEN)
        toks = [int(jnp.argmax(logits[0, -1]))]
        for _ in range(req.steps - 1):
            logits, cache = decode_step_pruned(
                pm, cache, jnp.asarray([[toks[-1]]], jnp.int32))
            toks.append(int(jnp.argmax(logits[0, -1])))
        assert rec.tokens == toks, f"rid={req.rid}"


def _identity_member(cfg, params):
    """A ``PrunedModel`` that removes nothing: each layer's params sliced
    from the dense stack."""
    from repro.models.pruned import PrunedLayer, PrunedModel
    layers = [PrunedLayer(kv_groups=cfg.num_kv_heads, d_ff=cfg.d_ff,
                          params=jax.tree.map(lambda a: a[i],
                                              params["layers"]))
              for i in range(cfg.num_layers)]
    return PrunedModel(cfg, layers, {k: v for k, v in params.items()
                                     if k != "layers"})


def _yarn_decoder():
    from repro.configs import smoke_config
    from repro.configs.base import Yarn
    from repro.models import model_init
    cfg = smoke_config("qwen2-72b").replace(
        dtype="float32", full_rope_scaling=Yarn(
            factor=4.0, original_max_position=16, attention_factor=1.3))
    assert cfg.attention == "full" and cfg.pos_emb == "rope"
    assert cfg.resolved_head_dim == 32
    return cfg, model_init(cfg, jax.random.key(1))[0]


@pytest.mark.parametrize("model", ["gpt2", "yarn"])
def test_identity_member_serves_the_dense_tokens(tiny_cfg, tiny_params,
                                                 model):
    """A member that removes nothing, served by ``PrunedServeModel``, gives
    the tokens ``DenseServeModel`` gives on the same backlog, and the same
    prefill and step logits to float32 rounding: under YaRN on full layers
    too, where the cached prompt keys must be rotated (and scaled by its
    attention factor) as the keys decoded against them are."""
    cfg, params = ((tiny_cfg, tiny_params) if model == "gpt2"
                   else _yarn_decoder())
    dense = DenseServeModel(cfg, params, MAX_LEN)
    member = PrunedServeModel(_identity_member(cfg, params), MAX_LEN)
    reqs = _requests(cfg, n=4, seed=19)
    served = []
    for m in (dense, member):
        eng = ServeEngine(m, num_slots=2)
        eng.warmup((8, 16))
        served.append([r.tokens for r in eng.run(reqs).records])
    assert served[0] == served[1]
    for req, toks in zip(reqs, served[0]):
        caches = []
        for m in (dense, member):
            logits, row = m.prefill(req.tokens)
            caches.append([m.insert(m.init_slots(2), row, 1,
                                    req.prompt_len), logits])
        np.testing.assert_allclose(caches[1][1], caches[0][1], rtol=1e-5,
                                   atol=1e-5)
        for tok in toks[:-1]:
            feed = jnp.zeros((2, 1), jnp.int32).at[1, 0].set(tok)
            for c, m in zip(caches, (dense, member)):
                c[1], c[0] = m.step(c[0], feed)
            np.testing.assert_allclose(caches[1][1][1], caches[0][1][1],
                                       rtol=1e-5, atol=1e-5)


def test_pruned_cache_bytes_match_shrunk_structure(tiny_cfg, tiny_params,
                                                   mag_db, dense_engine):
    a = _half_heads_assignment(tiny_cfg, mag_db)
    pm = shrink(tiny_cfg, tiny_params, mag_db, a)
    eng = ServeEngine(PrunedServeModel(pm, MAX_LEN), num_slots=2)
    plan = kv_cache_plan(tiny_cfg, mag_db, a)
    assert plan == [tiny_cfg.num_kv_heads // 2] * tiny_cfg.num_layers
    itemsize = jnp.dtype(jnp.float32).itemsize
    expect = sum(2 * 2 * MAX_LEN * h * tiny_cfg.head_dim * itemsize
                 for h in plan)
    assert eng.kv_cache_bytes == expect
    assert eng.kv_cache_bytes == kv_cache_bytes(pm, 2, MAX_LEN)
    assert eng.kv_cache_bytes < dense_engine.kv_cache_bytes
    assert eng.kv_cache_bytes == dense_engine.kv_cache_bytes // 2


# ----------------------------------------------------------------------
# GQA KV-head pruning + layer drop: per-layer cache-byte accounting
# ----------------------------------------------------------------------

def test_gqa_kv_head_prune_shrinks_cache_bytes_per_layer():
    """GQA levels remove KV heads with their query-head groups, so every
    layer's cache bytes must *strictly* shrink — and a whole-layer drop
    must allocate zero bytes for that layer."""
    from repro.configs import smoke_config
    from repro.core.structures import drop_layer, registry
    from repro.models import model_init
    from repro.models.pruned import kv_cache_bytes_per_layer
    from repro.runtime import costmodel as cm

    cfg = smoke_config("qwen2-72b").replace(num_kv_heads=2, dtype="float32")
    assert cfg.q_per_kv == 2  # real grouping
    params, _ = model_init(cfg, jax.random.key(0))
    db = baseline_database(cfg, params, kind="magnitude")
    mods = registry(cfg)
    a = {m.name: (1 if m.kind == "attn" else 0) for m in mods}
    a = drop_layer(a, mods, 1)  # layer 1 gone entirely

    dense_pm = shrink(cfg, params, db, {m.name: 0 for m in mods})
    pm = shrink(cfg, params, db, a)
    nslots = 2
    dense_bytes = kv_cache_bytes_per_layer(dense_pm, nslots, MAX_LEN)
    pruned_bytes = kv_cache_bytes_per_layer(pm, nslots, MAX_LEN)
    assert len(pruned_bytes) == cfg.num_layers
    for l, (d, p) in enumerate(zip(dense_bytes, pruned_bytes)):
        assert p < d, f"layer {l} cache bytes did not shrink"
    assert pruned_bytes[0] == dense_bytes[0] // 2  # 1 of 2 KV heads kept
    assert pruned_bytes[1] == 0                    # dropped layer: no cache

    # three accountings agree: engine == pruned model == costmodel plan
    eng = ServeEngine(PrunedServeModel(pm, MAX_LEN), num_slots=nslots)
    plan = kv_cache_plan(cfg, db, a)
    assert plan == [1, 0]
    itemsize = jnp.dtype(jnp.float32).itemsize
    assert eng.kv_cache_bytes == sum(pruned_bytes)
    assert eng.kv_cache_bytes == cm.kv_cache_bytes(
        cfg, plan, nslots, MAX_LEN, bytes_per_el=itemsize)

    # and the engine actually serves through the dropped layer
    eng.warmup((8,))
    reqs = synthetic_requests(cfg, 3, seed=5, rate=300.0,
                              prompt_lens=(5, 9), steps_range=(2, 5))
    report = eng.run(reqs)
    assert len(report.records) == len(reqs)
    assert all(len(r.tokens) > 0 for r in report.records)


# ----------------------------------------------------------------------
# family server: routing + partitioned serving
# ----------------------------------------------------------------------

def test_family_routing_and_run(tiny_cfg, tiny_params, mag_db):
    table = build_table(tiny_cfg, InferenceEnv(batch=2, seq=32,
                                               mode="prefill"),
                        backend="costmodel")
    assignments = {t: uniform_assignment(tiny_cfg, table, t)
                   for t in (1.5, 2.0)}
    srv = FamilyServer(tiny_cfg, tiny_params, mag_db, assignments,
                       max_len=32, num_slots=2)
    assert srv.route("relaxed") == 1.0   # dense: best quality qualifies
    assert srv.route("standard") == 1.5  # smallest target meeting 1.5x
    assert srv.route("strict") == 2.0
    srv.warmup((8,))
    reqs = synthetic_requests(tiny_cfg, 6, seed=2, rate=300.0,
                              prompt_lens=(5, 9), steps_range=(2, 5))
    reports = srv.run(reqs)
    assert sum(len(r.records) for r in reports.values()) == len(reqs)
    for target, rep in reports.items():
        for rec in rep.records:
            assert srv.route(rec.latency_class) == target


# ----------------------------------------------------------------------
# metric attribution (injected clock) + fault recovery (serve.step)
# ----------------------------------------------------------------------

def test_metrics_attribute_prefill_and_decode_separately(tiny_cfg,
                                                         tiny_params):
    """With a scripted clock ticking 1 ms per reading, every prefill and
    every decode step must account exactly one tick — compile time and
    host bookkeeping never leak into either number."""
    ticks = iter(range(10**6))

    def clock():
        return next(ticks) * 1e-3

    eng = ServeEngine(DenseServeModel(tiny_cfg, tiny_params, MAX_LEN),
                      num_slots=2, clock=clock)
    eng.warmup((8, 16))
    report = eng.run(_requests(tiny_cfg, n=3, seed=5))
    for rec in report.records:
        assert rec.prefill_ms == pytest.approx(1.0)
        for dms in rec.decode_step_ms:
            assert dms == pytest.approx(1.0)


def test_stamps_are_the_engine_clock_readings(tiny_cfg, tiny_params):
    """With a scripted clock ticking 1 ms per reading (the run's start is
    tick 0, then one pair per admission or decode step), each request's
    first token is stamped at the end of its admission's pair, each decode
    step at the end of its own, and a request's later tokens at the steps
    it took part in."""
    ticks = iter(range(10**6))

    def clock():
        return next(ticks) * 1e-3

    eng = ServeEngine(DenseServeModel(tiny_cfg, tiny_params, MAX_LEN),
                      num_slots=2, clock=clock)
    eng.warmup((8, 16))
    reqs = _requests(tiny_cfg, n=4, seed=5)
    report = eng.run(reqs)
    assert len(report.step_end) == report.steps > 0
    ends = sorted([r.t_first for r in report.records] + report.step_end)
    assert ends == pytest.approx([2e-3 * (i + 1) for i in range(len(ends))])
    for req, rec in zip(reqs, report.records):
        assert rec.t_first - rec.t_admit == pytest.approx(1e-3)
        times = report.token_times(rec)
        assert len(times) == len(rec.tokens) == req.steps
        assert np.all(np.diff(times) >= 2e-3 - 1e-9)
        assert rec.t_done == times[-1]
        assert rec.last_step - rec.first_step == req.steps - 2
        due = min(rec.arrival, rec.t_admit)
        assert rec.latency_s == pytest.approx(rec.t_done - due)
        assert rec.ttft_s == pytest.approx(rec.t_first - due)
    m = report.as_dict()
    assert m["itl_p50_ms"] >= 2.0 - 1e-6
    assert m["ttft_p50_ms"] <= m["p50_ms"]


def test_executables_have_stable_names(tiny_cfg, tiny_params, mag_db,
                                       dense_engine):
    """Both adapters' decode, prefill and insert, and the engine's sampler,
    compile to modules named jit_serve_decode, jit_serve_prefill,
    jit_serve_insert and jit_serve_sample, the names a profiler trace gives
    their executions."""
    def module(jitted, *args):
        return jitted.lower(*args).as_text().split()[1]

    pm = shrink(tiny_cfg, tiny_params, mag_db,
                _half_heads_assignment(tiny_cfg, mag_db))
    pruned = ServeEngine(PrunedServeModel(pm, MAX_LEN), num_slots=2)
    for eng in (dense_engine, pruned):
        model = eng.model
        _, row = model.prefill(np.zeros((5,), np.int64))
        i32 = jnp.asarray(0, jnp.int32)
        toks = jnp.zeros((2, 1), jnp.int32)
        padded = jnp.zeros((1, 8), jnp.int32)
        assert module(model._step, model.weights, eng.cache["attn"],
                      eng.cache["pos"], toks, {}) == "@jit_serve_decode"
        assert module(model._prefill, model.weights, padded, i32) \
            == "@jit_serve_prefill"
        assert module(model._insert, eng.cache, row, i32, i32) \
            == "@jit_serve_insert"
    logits = jnp.zeros((2, 1, tiny_cfg.vocab_size), jnp.float32)
    assert module(engine_mod._sample, logits) == "@jit_serve_sample"


# ----------------------------------------------------------------------
# greedy sampling and the finite check on the device (serve_sample)
# ----------------------------------------------------------------------

_LO = np.finfo(np.float32).min
_ULP = float(np.finfo(np.float32).eps)


def _rows(*rows):
    return np.asarray(rows, np.float32)[:, None, :]


def _vocab_width():
    """64 random rows of GPT-2's vocabulary, each with its largest value
    planted twice: tied, or one and two float32 steps apart; three rows
    not finite."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 1, 50257)).astype(np.float32)
    top = np.float32(8)
    up1 = np.nextafter(top, np.float32(9))
    up2 = np.nextafter(up1, np.float32(9))
    want = []
    for r in range(64):
        p, q = np.sort(rng.choice(50257, 2, replace=False))
        x[r, 0, p], x[r, 0, q] = [(top, top), (up1, top), (up2, up1),
                                  (up1, up2)][r % 4]
        want.append(int(q if r % 4 == 3 else p))
    x[3, 0, 9], x[10, 0, 0], x[20, 0, 50256] = np.nan, np.inf, -np.inf
    want[3] = want[10] = want[20] = -1
    return x, want


@pytest.mark.parametrize("case", [
    # exact ties: the first index of the largest value wins
    lambda: (_rows([1, 3, 3, 0, 3], [0, 0, 0, 0, 0], [2, -1, 2, 2, 1]),
             [1, 0, 0]),
    # the lowest finite value everywhere but one entry, and everywhere
    lambda: (_rows([_LO, _LO, _LO, -7, _LO], [_LO] * 5), [3, 0]),
    # -inf everywhere but one entry is not finite, like any -inf
    lambda: (_rows([-np.inf, -np.inf, 0.5, -np.inf, -np.inf],
                   [0, 1, 2, 3, 4]), [-1, 4]),
    # a nan, a +inf or a -inf anywhere in a row
    lambda: (_rows([0, np.nan, 1, 2, 3], [5, 4, 3, 2, 1],
                   [0, 1, np.inf, 2, 9], [9, 1, 2, -np.inf, 3],
                   [np.nan] * 5), [-1, 0, -1, -1, -1]),
    # float32 values that differ in their last bits (one bfloat16 value)
    lambda: (_rows([0, 0, 1 + 2 * _ULP, 0, 1 + _ULP],
                   [0, 0, 1 + _ULP, 0, 1 + 2 * _ULP],
                   [1 + _ULP, 1, 1, 1, 1 + _ULP]), [2, 4, 0]),
    _vocab_width,
], ids=["ties", "lowest-finite", "neg-inf-but-one", "non-finite",
        "last-bit", "vocab-width"])
def test_sampler_equals_host_argmax(case):
    """``serve_sample`` gives each row's ``np.argmax`` over the host
    logits, first index on ties, and -1 for a row with a nan or an inf."""
    logits, want = case()
    ids = engine_mod._sample(jnp.asarray(logits))
    assert ids.dtype == jnp.int32 and ids.shape == (logits.shape[0],)
    host = np.where(np.isfinite(logits[:, 0]).all(-1),
                    np.argmax(logits[:, 0], -1), -1)
    assert np.asarray(ids).tolist() == host.tolist() == want


@pytest.mark.parametrize("kind", ["dense", "member"])
def test_engine_serves_host_argmax_of_step_logits(tiny_cfg, tiny_params,
                                                  mag_db, kind):
    """Each adapter's engine, sampling on the device, serves the tokens
    that a plain loop over the same adapter's ``model.step`` gets from
    ``np.argmax`` of the host logits."""
    model = (DenseServeModel(tiny_cfg, tiny_params, MAX_LEN)
             if kind == "dense" else
             PrunedServeModel(shrink(tiny_cfg, tiny_params, mag_db,
                                     _half_heads_assignment(tiny_cfg,
                                                            mag_db)),
                              MAX_LEN))
    eng = ServeEngine(model, num_slots=2)
    eng.warmup((8, 16))
    reqs = _requests(tiny_cfg, n=4, seed=17)
    report = eng.run(reqs)
    for req, rec in zip(reqs, report.records):
        logits, row = model.prefill(req.tokens)
        toks = [int(np.argmax(np.asarray(logits)[0, 0]))]
        cache = model.insert(model.init_slots(2), row, 1, req.prompt_len)
        for _ in range(req.steps - 1):
            feed = np.zeros((2, 1), np.int32)
            feed[1, 0] = toks[-1]
            logits, cache = model.step(cache, jnp.asarray(feed))
            toks.append(int(np.argmax(np.asarray(logits)[1, 0])))
        assert rec.tokens == toks, f"rid={req.rid}"


def test_step_pulls_num_slots_token_ids(tmp_path, tiny_cfg, dense_engine):
    """Traced, each decode step's ``serve.pull`` span says it copied
    ``4 * num_slots`` bytes to the host: the step's int32 token ids, not its
    logits."""
    from jax.profiler import ProfileData
    reqs = _requests(tiny_cfg, n=3, seed=7)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        report = dense_engine.run(reqs)
    path, = tmp_path.glob("**/*.xplane.pb")
    pulls = [dict(ev.stats)
             for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events
             if ev.name == "serve.pull"]
    assert len(pulls) == report.steps > 0
    assert {p["bytes"] for p in pulls} == {4 * dense_engine.num_slots}


# ----------------------------------------------------------------------
# the decode step's K/V: a per-slot masked write, donated off the CPU
# ----------------------------------------------------------------------

@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.bfloat16])
def test_per_slot_write_equals_scatter(tiny_cfg, tiny_params, cache_dtype):
    """The per-slot K/V write of a vector-``pos`` decode equals a scatter
    of each slot's new K/V row at ``min(pos, sc - 1)``, over the whole
    returned cache, for mixed positions with one slot at ``sc - 1`` and one
    past it (the clamp); attention then reads the same values."""
    from repro.models import attention as attn_mod
    cfg = tiny_cfg
    p = jax.tree.map(lambda a: a[0], tiny_params["layers"])["attn"]
    sc, dh, h = 16, cfg.resolved_head_dim, cfg.num_kv_heads
    pos = jnp.asarray([0, 5, sc - 1, sc + 3], jnp.int32)
    b = pos.shape[0]
    ks = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(ks[0], (b, 1, cfg.d_model), jnp.float32)
    cache = {n: jax.random.normal(k, (b, sc, h, dh)).astype(cache_dtype)
             for n, k in zip("kv", ks[1:])}
    out, got = attn_mod.self_attention(cfg, p, x, cache=cache,
                                       cache_pos=pos)
    _, k, v = attn_mod._project_qkv(cfg, p, x, x)
    row = jnp.minimum(pos, sc - 1)
    want = {"k": cache["k"].at[jnp.arange(b), row].set(
                k[:, 0].astype(cache_dtype)),
            "v": cache["v"].at[jnp.arange(b), row].set(
                v[:, 0].astype(cache_dtype))}
    for n in "kv":
        assert got[n].dtype == cache_dtype
        np.testing.assert_array_equal(np.asarray(got[n]),
                                      np.asarray(want[n]))
    out_want, _ = attn_mod.self_attention(cfg, p, x, cache=want,
                                          cache_pos=pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_want))


@pytest.mark.parametrize("donate", [False, True])
@pytest.mark.parametrize("kind", ["dense", "member"])
def test_engine_under_kv_donation(monkeypatch, tiny_cfg, tiny_params,
                                  mag_db, kind, donate):
    """With the decode step's K/V donated (forced on the CPU) or not, each
    adapter serves the tokens of an undonated engine, a ``serve.step`` nan
    recovers bit-identically from the failed attempt's K/V, ``warmup``
    leaves the engine's cache alive, and ``donated_steps`` counts every
    decode step that consumed its K/V in place."""
    from repro.serve import engine as engine_mod
    pm = shrink(tiny_cfg, tiny_params, mag_db,
                _half_heads_assignment(tiny_cfg, mag_db))

    def new_engine():
        model = (DenseServeModel(tiny_cfg, tiny_params, MAX_LEN)
                 if kind == "dense" else PrunedServeModel(pm, MAX_LEN))
        eng = ServeEngine(model, num_slots=2)
        eng.warmup((8, 16))
        return eng

    reqs = _requests(tiny_cfg, n=4, seed=13)
    ref = new_engine().run(reqs)
    assert ref.donated_steps == 0
    monkeypatch.setattr(engine_mod, "_donate_kv", lambda: donate)
    eng = new_engine()
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng.cache))
    out = eng.run(reqs)
    assert out.as_dict()["donated_steps"] == out.donated_steps
    rep = RobustnessReport()
    with install(FaultPlan.parse("serve.step:nan@2")), report_scope(rep):
        healed = new_engine().run(reqs)
    assert rep.counts["recovered"].get("serve.step", 0) == 1
    # arrivals are admitted on a clock of measured times, so the number of
    # steps may differ from run to run; every step donates or none does
    for r in (out, healed):
        assert r.steps > 0
        assert r.donated_steps == (r.steps if donate else 0)
    for a, b, c in zip(ref.records, out.records, healed.records):
        assert a.tokens == b.tokens == c.tokens, f"rid={a.rid}"


@pytest.mark.chaos
def test_serve_step_faults_recover_bit_identical(tiny_cfg, tiny_params):
    reqs = _requests(tiny_cfg, n=3, seed=9)
    clean = ServeEngine(DenseServeModel(tiny_cfg, tiny_params, MAX_LEN),
                        num_slots=2)
    clean.warmup((8, 16))
    ref = clean.run(reqs)

    faulty = ServeEngine(DenseServeModel(tiny_cfg, tiny_params, MAX_LEN),
                         num_slots=2)
    faulty.warmup((8, 16))
    rep = RobustnessReport()
    plan = FaultPlan.parse("serve.step:raise@0,serve.step:nan@2")
    with install(plan), report_scope(rep):
        out = faulty.run(reqs)
    for a, b in zip(ref.records, out.records):
        assert a.tokens == b.tokens
    assert rep.counts["detected"].get("serve.step", 0) == 2
    assert rep.counts["retries"].get("serve.step", 0) == 2
    assert rep.counts["recovered"].get("serve.step", 0) == 2


@pytest.mark.chaos
def test_serve_step_persistent_fault_raises(tiny_cfg, tiny_params):
    eng = ServeEngine(DenseServeModel(tiny_cfg, tiny_params, MAX_LEN),
                      num_slots=2)
    eng.warmup((8,))
    plan = FaultPlan.parse("serve.step:nan@0x100")
    with install(plan), report_scope(RobustnessReport()):
        with pytest.raises(RuntimeError, match="not transient"):
            eng.run(_requests(tiny_cfg, n=2, seed=1))
