"""Golden tests for the repro.analysis static-analysis suite.

Every rule gets a *firing* case (a minimal function/source that exhibits
the hazard — each was written to fail before the corresponding repo fix
or rule landed) and a *passing twin* (the corrected form), so the rules
are pinned from both sides. The e2e tests run the suite sections against
the committed budgets under ``results/analysis/`` and assert the report
schema is stable. The forced-2-device collectives compile is tier-2; the
tier-1 collective-schedule goldens use an in-process 1-device mesh whose
psum still lowers to a real all-reduce instruction.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis import astlint, cli, pallas_audit
from repro.analysis.collectives_audit import (check_against_budget,
                                              collective_schedule,
                                              schedule_diff)
from repro.analysis.findings import (AnalysisReport, Finding,
                                     compare_to_budget)
from repro.analysis.jaxpr_audit import (audit_jitted, audit_traced,
                                        count_hlo_aliases)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _rules(findings, rule):
    return [f for f in findings if f.rule == rule]


# ======================================================================
# jaxpr rules
# ======================================================================

def _audit_fn(fn, *args, **kw):
    return audit_jitted("golden", jax.jit(fn), args, **kw)


def test_host_callback_in_loop_fires_and_hoisted_twin_passes():
    spec = jax.ShapeDtypeStruct((), jnp.float32)

    def firing(xs):
        def body(c, x):
            v = jax.pure_callback(lambda a: np.asarray(a), spec, c + x)
            return v, v
        return jax.lax.scan(body, jnp.float32(0.0), xs)

    m, fs = _audit_fn(firing, jnp.ones((5,), jnp.float32))
    errs = _rules(fs, "jaxpr.host-callback")
    assert errs and errs[0].severity == "error"
    assert "hoist" in errs[0].message          # actionable
    assert m["host_callbacks_in_loop"] == 5    # trip-weighted

    def twin(xs):                              # hoisted out of the loop
        def body(c, x):
            return c + x, c + x
        tot, ys = jax.lax.scan(body, jnp.float32(0.0), xs)
        return jax.pure_callback(lambda a: np.asarray(a), spec, tot), ys

    m, fs = _audit_fn(twin, jnp.ones((5,), jnp.float32))
    assert m["host_callbacks_in_loop"] == 0
    warns = _rules(fs, "jaxpr.host-callback")
    assert warns and warns[0].severity == "warning"   # outside loop


def test_large_const_fires_and_arg_twin_passes():
    big = jnp.ones((128, 128), jnp.float32)    # 64 KiB > 16 KiB threshold

    m, fs = _audit_fn(lambda x: x @ big, jnp.ones((4, 128)))
    errs = _rules(fs, "jaxpr.large-const")
    assert errs and "argument" in errs[0].message
    assert m["large_const_bytes"] >= big.nbytes

    m, fs = _audit_fn(lambda x, w: x @ w, jnp.ones((4, 128)), big)
    assert m["large_consts"] == 0
    assert not _rules(fs, "jaxpr.large-const")


def test_undonated_fires_and_aliasable_twin_passes():
    x = jnp.ones((16, 16), jnp.float32)

    # output shape differs from the donated input -> alias impossible
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        m, fs = audit_jitted(
            "golden", jax.jit(lambda a: a.sum(axis=0), donate_argnums=(0,)),
            (x,), donate_argnums=(0,))
    errs = _rules(fs, "jaxpr.undonated")
    assert errs and m["donated_unconsumed"] == 1

    m, fs = audit_jitted(
        "golden", jax.jit(lambda a: a + 1, donate_argnums=(0,)),
        (x,), donate_argnums=(0,))
    assert m["donated_consumed"] == 1 and m["donated_unconsumed"] == 0
    assert not _rules(fs, "jaxpr.undonated")


def test_weak_type_fires_and_typed_twin_passes():
    f = jax.jit(lambda x: x * 2)
    m, fs = audit_jitted("golden", f, (1.0,))     # python float leaks
    assert m["weak_invars"] >= 1
    assert _rules(fs, "jaxpr.weak-type")

    m, fs = audit_jitted("golden", f, (jnp.float32(1.0),))
    assert m["weak_invars"] == 0
    assert not _rules(fs, "jaxpr.weak-type")


def test_flop_cross_check_matches_hlo():
    w = jnp.ones((64, 32), jnp.float32)
    m, _ = _audit_fn(lambda x, v: x @ v, jnp.ones((8, 64)), w)
    assert m["dot_flops"] == 2 * 8 * 64 * 32
    assert m["flops_ratio"] == pytest.approx(1.0, rel=0.2)


def test_count_hlo_aliases_parses_nested_braces():
    text = ("HloModule m, input_output_alias={ {0}: (0, {}, may-alias), "
            "{1}: (1, {}, may-alias) }, entry_computation_layout={()->()}")
    assert count_hlo_aliases(text) == 2          # pre-fix regex saw 1
    assert count_hlo_aliases("HloModule m") == 0


# ======================================================================
# budget comparison semantics
# ======================================================================

def test_budget_semantics():
    b = {"n": 3, "hz": 1, "r_lo": 0.5, "r_hi": 2.0}
    assert _rules(compare_to_budget("e", {"n": 4}, b, exact_keys=("n",)),
                  "budget.exact")
    assert _rules(compare_to_budget("e", {"hz": 2}, b, max_keys=("hz",)),
                  "budget.regression")
    stale = compare_to_budget("e", {"hz": 0}, b, max_keys=("hz",))
    assert stale and stale[0].severity == "warning"
    assert _rules(compare_to_budget("e", {"r": 3.0}, b, band_keys=("r",)),
                  "budget.band")
    assert not compare_to_budget(
        "e", {"n": 3, "hz": 1, "r": 1.0}, b,
        exact_keys=("n",), max_keys=("hz",), band_keys=("r",))
    missing = compare_to_budget("e", {}, None)
    assert missing and "--update-budgets" in missing[0].message


def test_host_sync_added_to_spdy_eval_fails_gate():
    """The ISSUE's acceptance demo: a per-candidate host pull inside the
    batched SPDY eval loop trips both the rule and the committed budget
    with an actionable message."""
    spec = jax.ShapeDtypeStruct((), jnp.float32)

    def bad_eval(stacked, pb):                   # one sync PER candidate
        def score(p):
            v = jnp.mean(stacked * p)
            return jax.pure_callback(lambda a: np.asarray(a), spec, v)
        return jax.lax.map(score, pb)

    m, fs = _audit_fn(bad_eval, jnp.ones((4, 8)), jnp.ones((6, 1)))
    assert m["host_callbacks_in_loop"] >= 1
    assert any("sync" in f.message for f in _rules(fs, "jaxpr.host-callback"))

    with open(os.path.join(ROOT, "results/analysis/jaxpr_budget.json")) as f:
        ent = json.load(f)["entries"]["spdy.batched_eval"]
    assert ent["host_callbacks_in_loop"] == 0    # committed budget is clean
    viol = compare_to_budget("spdy.batched_eval", m, ent,
                             max_keys=cli.JAXPR_MAX_KEYS)
    reg = _rules(viol, "budget.regression")
    assert reg and "new hazard" in reg[0].message


# ======================================================================
# collectives (in-process 1-device goldens; subprocess path is tier-2)
# ======================================================================

def _mesh1():
    from repro.distributed.sharding import make_mesh
    return make_mesh((1,), ("data",))


def test_extra_all_reduce_fails_schedule_budget():
    mesh = _mesh1()

    def body(x):
        return jax.lax.psum(x, "data")

    bad = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                out_specs=P()))
    text = bad.trace(jnp.ones((4,), jnp.float32)) \
              .lower().compile().as_text()
    counts, sched = collective_schedule(text, 1)
    assert counts.get("all-reduce", 0) >= 1      # survives 1-device lowering

    metrics = {f"train_step_fsdp.{k}": v for k, v in counts.items()}
    metrics["train_step_fsdp.n_collectives"] = sum(counts.values())
    budget = {"metrics": {"train_step_fsdp.n_collectives": 0},
              "schedules": {"train_step_fsdp": []}}
    fs = check_against_budget(metrics, {"train_step_fsdp": sched}, budget)
    assert fs and fs[0].rule == "collectives.schedule"
    assert "all-reduce" in fs[0].message         # the diff names the op
    assert "--update-budgets" in fs[0].message   # and the remedy

    # passing twin: no collective, matching zero budget
    good = jax.jit(lambda x: x * 2)
    text = good.trace(jnp.ones((4,), jnp.float32)) \
               .lower().compile().as_text()
    counts, sched = collective_schedule(text, 1)
    assert counts == {}
    assert not check_against_budget(
        {"train_step_fsdp.n_collectives": 0},
        {"train_step_fsdp": sched}, budget)


def test_schedule_diff_marks_insertion():
    want = [["all-reduce", "f32[8]"]]
    got = [["all-gather", "f32[64,64]"], ["all-reduce", "f32[8]"]]
    d = schedule_diff(want, got)
    assert "+" in d and "all-gather" in d


@pytest.mark.tier2
@pytest.mark.slow
def test_collectives_audit_matches_committed_budget():
    from repro.analysis.collectives_audit import audit_collectives
    metrics, schedules = audit_collectives()
    with open(os.path.join(ROOT,
                           "results/analysis/collectives_budget.json")) as f:
        budget = json.load(f)
    assert not check_against_budget(metrics, schedules, budget)
    assert metrics["spdy_batched_eval.n_collectives"] == 0
    assert metrics["hessian_step_sharded.all-reduce"] > 0


# ======================================================================
# pallas rules
# ======================================================================

def test_twin_registry_drift_fires_both_ways():
    reg = pallas_audit.build_registry()
    src = "def f():\n    _run_guarded('brand_new_op', k, r)\n"
    fs = pallas_audit.check_twin_registry(src, reg)
    assert _rules(fs, "pallas.twin-drift")       # guarded, not audited

    real_ops = os.path.join(ROOT, "src/repro/kernels/ops.py")
    with open(real_ops) as f:
        real_src = f.read()
    fs = pallas_audit.check_twin_registry(real_src, {})
    assert _rules(fs, "pallas.twin-drift")       # nothing audited

    extra = dict(reg)
    extra["ghost_op"] = reg["flash_attention"]
    fs = pallas_audit.check_twin_registry(real_src, extra)
    assert _rules(fs, "pallas.twin-missing")     # audited, not guarded

    assert not pallas_audit.check_twin_registry(real_src, reg)  # twin


def _spec(op="golden", kernel=None, ref=None, make_args=None, **kw):
    return pallas_audit.KernelSpec(
        op=op, kernel=kernel, ref=ref,
        make_args=make_args or (lambda: (jnp.ones((8, 128)),)), **kw)


def test_signature_drift_fires_and_twin_passes():
    def kernel(a, b, *, interpret=None):
        return a + b

    def bad_ref(b, a):                           # operands swapped
        return a + b

    def good_ref(a, b, scale=None):              # defaulted extras allowed
        return a + b

    args = lambda: (jnp.ones((4,)), jnp.ones((4,)))
    fs = pallas_audit.check_signature(
        _spec(kernel=kernel, ref=bad_ref, make_args=args))
    assert _rules(fs, "pallas.signature")
    assert not pallas_audit.check_signature(
        _spec(kernel=kernel, ref=good_ref, make_args=args))


def test_abstract_mismatch_fires_and_twin_passes():
    def kernel(a, *, interpret=None):
        return a * 2

    fs = pallas_audit.check_abstract(
        _spec(kernel=kernel, ref=lambda a: a.sum(axis=0)))
    assert _rules(fs, "pallas.abstract-mismatch")
    assert not pallas_audit.check_abstract(
        _spec(kernel=kernel, ref=lambda a: a + a))


def _pallas_kernel(block, index_map, shape=(16, 128)):
    from jax.experimental import pallas as pl

    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def kernel(x, *, interpret=None):
        return pl.pallas_call(
            body,
            grid=(shape[0] // block[0],),
            in_specs=[pl.BlockSpec(block, index_map)],
            out_specs=pl.BlockSpec(block, index_map),
            out_shape=jax.ShapeDtypeStruct(shape, x.dtype),
            interpret=True)(x)

    return kernel, (lambda: (jnp.ones(shape, jnp.float32),))


def test_tile_alignment_fires_and_aligned_twin_passes():
    kernel, args = _pallas_kernel((2, 64), lambda i: (i, 0))
    _, fs = pallas_audit.check_grid(
        _spec(kernel=kernel, ref=lambda x: x * 2, make_args=args))
    assert _rules(fs, "pallas.tile-alignment")

    kernel, args = _pallas_kernel((8, 128), lambda i: (i, 0))
    _, fs = pallas_audit.check_grid(
        _spec(kernel=kernel, ref=lambda x: x * 2, make_args=args))
    assert not fs


def test_grid_coverage_gap_fires():
    # index_map pinned to block 0: rows 8..15 are never computed
    kernel, args = _pallas_kernel((8, 128), lambda i: (0, 0))
    _, fs = pallas_audit.check_grid(
        _spec(kernel=kernel, ref=lambda x: x * 2, make_args=args))
    assert _rules(fs, "pallas.grid-coverage")


def test_interpret_literal_fires_and_threaded_twin_passes():
    firing = ("import jax.experimental.pallas as pl\n"
              "def k(x, interpret=None):\n"
              "    a = pl.pallas_call(b, interpret=True)(x)\n"
              "    c = pl.pallas_call(b)(x)\n"
              "    return a + c\n")
    fs = pallas_audit.check_interpret_literals({"kernels/fake.py": firing})
    assert len(_rules(fs, "pallas.interpret-hardcoded")) == 2

    twin = ("import jax.experimental.pallas as pl\n"
            "def k(x, interpret=None):\n"
            "    common = dict(interpret=interpret)\n"
            "    a = pl.pallas_call(b, interpret=interpret)(x)\n"
            "    c = pl.pallas_call(b, **common)(x)\n"
            "    return a + c\n")
    assert not pallas_audit.check_interpret_literals({"kernels/f.py": twin})


# ======================================================================
# ast rules
# ======================================================================

def test_host_sync_in_loop_fires_and_annotated_twin_passes():
    firing = ("def f(xs):\n"
              "    out = []\n"
              "    for x in xs:\n"
              "        out.append(float(x.sum()))\n"
              "    return out\n")
    fs = astlint.lint_source("src/repro/core/fake.py", firing)
    errs = _rules(fs, "ast.host-sync-in-loop")
    assert errs and "# sync:" in errs[0].message

    annotated = firing.replace(
        "        out.append(float(x.sum()))",
        "        # sync: test twin — reviewed per-item pull\n"
        "        out.append(float(x.sum()))")
    assert not astlint.lint_source("src/repro/core/fake.py", annotated)

    # same source outside a hot dir: rule does not apply
    assert not astlint.lint_source("src/repro/launch/fake.py", firing)


def test_linalg_inv_fires_and_cholesky_twin_passes():
    firing = "def f(H):\n    return jnp.linalg.inv(H)\n"
    fs = astlint.lint_source("src/repro/core/fake.py", firing)
    assert _rules(fs, "ast.linalg-inv")
    twin = ("def f(H, b):\n"
            "    L = jnp.linalg.cholesky(H)\n"
            "    return jax.scipy.linalg.cho_solve((L, True), b)\n")
    assert not astlint.lint_source("src/repro/core/fake.py", twin)


def test_tmp_literal_fires_and_tempfile_twin_passes():
    fs = astlint.lint_source("src/repro/launch/fake.py",
                             "OUT = '/tmp/run_out'\n")
    assert _rules(fs, "ast.tmp-literal")
    twin = "import tempfile\nOUT = tempfile.mkdtemp(prefix='run_out_')\n"
    assert not astlint.lint_source("src/repro/launch/fake.py", twin)


def test_atomic_writer_fires_and_twin_passes():
    firing = ("import json\n"
              "def save(p, rec):\n"
              "    with open(p, 'w') as f:\n"
              "        json.dump(rec, f)\n")
    fs = astlint.lint_source("src/repro/launch/fake.py", firing)
    assert _rules(fs, "ast.atomic-writer")

    twin = ("from repro.checkpoint.manager import atomic_write_json\n"
            "def save(p, rec):\n"
            "    atomic_write_json(p, rec)\n")
    assert not astlint.lint_source("src/repro/launch/fake.py", twin)

    # the atomic writer itself is exempt by path
    assert not astlint.lint_source("src/repro/checkpoint/manager.py",
                                   firing)


def test_fault_site_drift_fires_both_ways_and_repo_is_clean():
    from repro.robustness import faults
    used = {"src/repro/core/fake.py":
            "def f():\n    _faults.hit('ghost.site')\n"}
    fs = astlint.check_fault_sites(used, faults.SITES)
    msgs = _rules(fs, "ast.fault-site-drift")
    # 'ghost.site' undeclared + every declared site unused
    assert any("not declared" in f.message for f in msgs)
    assert any("no injection point" in f.message for f in msgs)

    # passing twin: synthetic files exactly covering a declared set
    twin = {"src/repro/core/fake.py":
            "def f():\n    _faults.hit('a.b')\n"
            "    _faults.poison_scalar('c.d')\n"}
    assert not astlint.check_fault_sites(twin, ("a.b", "c.d"))

    # and the real repo matches the real registry (the drift this suite
    # was introduced to prevent)
    files = {rel: open(p).read()
             for rel, p in astlint._iter_py(ROOT, "src/repro")}
    assert not astlint.check_fault_sites(files, faults.SITES)


def test_bench_key_drift_fires_and_declared_twin_passes():
    # pre-fix state of benchmarks/run.py: keys written, none declared
    firing = "def bench():\n    _write_bench_db({'serve': 1})\n"
    fs = astlint.check_bench_keys("benchmarks/run.py", firing)
    assert _rules(fs, "ast.bench-key-drift")

    partial = ("BENCH_KEYS = ('serve',)\n"
               "def bench(smoke):\n"
               "    _write_bench_db({('chaos_smoke' if smoke else 'chaos')"
               ": 1})\n")
    fs = astlint.check_bench_keys("benchmarks/run.py", partial)
    keys = {f.detail.get("key") for f in fs}
    assert "chaos" in keys and "chaos_smoke" in keys   # IfExp keys seen
    assert "serve" in keys                             # stale declaration

    twin = ("BENCH_KEYS = ('serve', 'chaos', 'chaos_smoke')\n"
            "def bench(smoke):\n"
            "    _write_bench_db({('chaos_smoke' if smoke else 'chaos')"
            ": 1, 'serve': 2})\n")
    assert not astlint.check_bench_keys("benchmarks/run.py", twin)


# ======================================================================
# e2e: suite sections against committed budgets, stable report schema
# ======================================================================

def test_ast_and_pallas_sections_clean_against_committed_budgets(tmp_path):
    report = cli.run_suite(["ast", "pallas"])
    assert not report.errors, [str(f) for f in report.errors]
    assert "ast_budget.json" in report.budgets_checked
    assert "pallas_budget.json" in report.budgets_checked
    assert len(report.metrics["pallas"]["ops_audited"]) == 4

    out = tmp_path / "report.json"
    cli.write_report(report, str(out))
    with open(out) as f:
        payload = json.load(f)
    assert sorted(payload) == ["budgets_checked", "findings", "metrics",
                               "n_errors", "schema_version",
                               "triage_notes"]
    assert payload["schema_version"] == 1
    assert payload["n_errors"] == 0
    assert any(n["rule"] == "jaxpr.large-const"
               for n in payload["triage_notes"])


def test_jaxpr_entry_clean_against_committed_budget():
    report = cli.run_suite(["jaxpr"], entries=["obs.batched_step"])
    assert not report.errors, [str(f) for f in report.errors]
    m = report.metrics["obs.batched_step"]
    assert m["host_callbacks"] == 0 and m["large_consts"] == 0


def test_finding_severity_validated():
    with pytest.raises(ValueError):
        Finding(rule="r", severity="fatal", where="w", message="m")
    r = AnalysisReport()
    r.extend([Finding(rule="r", severity="error", where="w", message="m")])
    assert r.as_dict()["n_errors"] == 1
