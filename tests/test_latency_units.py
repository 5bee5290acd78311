"""Latency-table regressions per PruneUnit kind: grids must come from the
unit's own level grid, every kind must price its full-drop level to
exactly 0 (so SPDY can buy module and whole-layer drops), and
``runtime_of`` must accept mixed-kind assignments — including the
restricted whole-expert grid."""
import numpy as np
import pytest

from repro.configs import GPT2_SMALL, smoke_config
from repro.core.latency import (_grid_for, _kinds_for, build_costmodel_table,
                                build_measured_table)
from repro.core.structures import UNITS, level_grid, registry
from repro.runtime.costmodel import (TPU_V5E, InferenceEnv, hardware_for,
                                     kv_cache_bytes)

ENV = InferenceEnv(batch=8, seq=128, mode="prefill")

CFGS = {
    "mha": GPT2_SMALL.replace(num_layers=2, d_model=64, d_ff=128,
                              num_heads=4, num_kv_heads=4, head_dim=16,
                              vocab_size=256, dtype="float32"),
    "gqa": smoke_config("qwen2-72b").replace(num_kv_heads=2,
                                             dtype="float32"),
    "ssm": smoke_config("mamba2-2.7b").replace(dtype="float32"),
    "moe": smoke_config("phi3.5-moe-42b-a6.6b").replace(dtype="float32"),
    "moe-expert": smoke_config("phi3.5-moe-42b-a6.6b").replace(
        dtype="float32", moe_prune_unit="expert"),
    "hybrid": smoke_config("hymba-1.5b").replace(dtype="float32"),
}


@pytest.mark.parametrize("name", sorted(CFGS))
def test_costmodel_table_per_kind(name):
    cfg = CFGS[name]
    tab = build_costmodel_table(cfg, ENV)
    kinds = _kinds_for(cfg)
    assert set(tab.grids) == set(kinds) and kinds
    for kind in kinds:
        g, t = tab.grids[kind], tab.times[kind]
        # the table's grid is the unit's own level grid, verbatim
        mod = next(m for m in registry(cfg) if m.kind == kind)
        np.testing.assert_array_equal(g, np.asarray(level_grid(mod)))
        assert g[-1] == mod.n_structures
        # full drop prices to exactly 0 and times never increase with
        # more structures removed
        assert t[-1] == 0.0
        assert np.all(np.diff(t) <= 1e-12), (kind, t)
        assert np.all(t >= 0.0)


@pytest.mark.parametrize("name", sorted(CFGS))
def test_layer_drop_prices_to_base(name):
    """Dropping every module of every layer leaves exactly the base
    (embeddings/norms/logits) runtime — the pricing that lets SPDY buy
    whole-layer drops at aggressive targets."""
    cfg = CFGS[name]
    tab = build_costmodel_table(cfg, ENV)
    mods = registry(cfg)
    full_drop = {m.name: m.n_structures for m in mods}
    assert tab.runtime_of(full_drop, mods=mods) == pytest.approx(tab.base)
    assert tab.dense_runtime(mods) > tab.base


def test_expert_mode_grid_is_restricted():
    cfg = CFGS["moe-expert"]
    g = _grid_for(cfg, "moe")
    np.testing.assert_array_equal(g, [0, cfg.d_ff])
    tab = build_costmodel_table(cfg, ENV)
    np.testing.assert_array_equal(tab.grids["moe"], [0, cfg.d_ff])
    # width mode keeps the fine-grained 0.9^i grid
    assert len(_grid_for(CFGS["moe"], "moe")) > 2


def test_mixed_kind_runtime_of():
    cfg = CFGS["hybrid"]
    tab = build_costmodel_table(cfg, ENV)
    mods = registry(cfg)
    assert {"attn", "ssm", "ffn"} <= {m.kind for m in mods}
    a = {m.name: (m.n_structures if m.layer == 1 else 0) for m in mods}
    rt = tab.runtime_of(a, cfg=cfg)
    # layer 1 fully dropped: runtime is base + layer 0's dense modules
    per_l0 = sum(tab.module_time(m.kind, 0) for m in mods if m.layer == 0)
    assert rt == pytest.approx(tab.base + per_l0)


def test_measured_table_ssm_smoke():
    """The measured backend walks the SSM unit's timing_spec: finite,
    non-negative wall-clock times and an exactly-zero full-drop level."""
    cfg = CFGS["ssm"]
    tab = build_measured_table(cfg, ENV, grid_subsample=8, reps=1)
    assert set(tab.grids) == {"ssm"}
    t = tab.times["ssm"]
    assert np.isfinite(t).all() and np.all(t >= 0.0)
    assert t[-1] == 0.0
    assert tab.base > 0.0


def test_costmodel_kv_cache_bytes_plan():
    cfg = CFGS["gqa"]
    dh = cfg.resolved_head_dim
    dense = kv_cache_bytes(cfg, [2, 2], batch=4, max_len=32)
    assert dense == 2 * (2 * 4 * 32 * 2 * dh * 2)
    pruned = kv_cache_bytes(cfg, [1, 0], batch=4, max_len=32)
    assert pruned == 2 * 4 * 32 * 1 * dh * 2  # dropped layer costs zero
    assert pruned < dense


def test_units_cover_every_registry_kind():
    """Every kind the registry can emit has a PruneUnit with the full
    latency contract (cost_time + timing_spec at live and drop levels)."""
    for name, cfg in CFGS.items():
        for m in registry(cfg):
            u = UNITS[m.kind]
            assert u.cost_time(cfg, ENV, 0) > 0.0
            assert u.cost_time(cfg, ENV, m.n_structures) == 0.0
            assert u.timing_spec(cfg, ENV, 0) is not None
            assert u.timing_spec(cfg, ENV, m.n_structures) is None


def test_hardware_for_unknown_kind_is_an_error():
    """Peaks are looked up by the device kind JAX reports; a kind without
    an entry fails instead of being priced as some other chip."""
    assert hardware_for("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError, match="no peak entry"):
        hardware_for("cpu")
