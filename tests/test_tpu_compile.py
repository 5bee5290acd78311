"""Compile the main path's Pallas kernels and the GPT2-small database chunk
for a described TPU v5e, without a chip.

The TPU compiler refuses what interpret mode accepts: blocks off the
(8, 128) tiling, kernels that need more scoped VMEM than granted, programs
larger than HBM. Each case lowers and compiles at real widths for one chip
of a ``v5e:2x2`` topology and checks that the kernel is really there
(``tpu_custom_call``). The topology is described in a fixture, never at
import: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import GPT2_SMALL, MELLUM2_12B, get_config
from repro.core.database import chunk_size, module_bytes
from repro.core.obs import prune_structured_batched
from repro.core.structures import level_grid, registry
from repro.kernels import ops
from repro.runtime.costmodel import TPU_V5E


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip cannot read back what the persistent cache holds
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shardings, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=shardings) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.mark.parametrize("d_in,d_out,gs", [(3072, 768, 1), (768, 768, 64),
                                           (4096, 4096, 1)])
def test_obs_downdate_compiles(one_chip, d_in, d_out, gs):
    c = _compile(lambda *a: ops._obs_downdate_impl(*a, interpret=False),
                 one_chip, ((d_in, d_out), F32), ((d_in, d_in), F32),
                 ((d_in, gs), F32), ((gs, d_out), F32), ((gs, d_in), F32),
                 ((d_in,), F32))
    assert "tpu_custom_call" in c.as_text()


def test_hessian_accum_compiles(one_chip):
    c = _compile(lambda x: ops._hessian_accum_impl(x, interpret=False),
                 one_chip, ((1024, 3072), F32))
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_compiles(one_chip):
    qkv = ((1, 1024, 12, 64), BF16)  # GPT2-small: 12 heads x 64
    c = _compile(lambda q, k, v: ops._flash_attention_impl(
        q, k, v, causal=True, interpret=False), one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-2.7b")
    b, s = 1, 4 * cfg.ssm_chunk
    h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    c = _compile(lambda x, dt, A, B, C: ops._ssd_chunked_impl(
        x, dt, A, B, C, chunk=cfg.ssm_chunk, interpret=False), one_chip,
        ((b, s, h, p), BF16), ((b, s, h), F32), ((h,), F32),
        ((b, s, n), BF16), ((b, s, n), BF16))
    assert "tpu_custom_call" in c.as_text()


def test_gpt2_ffn_db_chunk_fits_one_v5e(one_chip):
    """The chunk `build_database` picks for GPT2-small's 12 FFN modules on
    an empty v5e fits its HBM, and `module_bytes` bounds the compiler's
    own count from above."""
    mods = [m for m in registry(GPT2_SMALL) if m.kind == "ffn"]
    levels = tuple(level_grid(mods[0]))
    d_in, d_out = mods[0].d_in, GPT2_SMALL.d_model
    per = module_bytes(d_in, d_out, len(levels))
    k = chunk_size(len(mods), per, 16, free=int(TPU_V5E.hbm_bytes))
    assert 1 <= k < len(mods)
    c = _compile(lambda W, H: prune_structured_batched(
        W, H, group_size=1, n_remove=max(levels), levels=levels),
        one_chip, ((k, d_in, d_out), F32), ((k, d_in, d_in), F32))
    ma = c.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used <= k * per
    assert used < TPU_V5E.hbm_bytes


SLOTS, SLOT_LEN = 64, 1024          # the serving cells' slot cache


def _on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def _dense_decode(cfg):
    from repro.models import model_init
    from repro.serve.engine import DenseServeModel
    params = jax.eval_shape(lambda k: model_init(cfg, k)[0],
                            jax.random.key(0))
    return DenseServeModel(cfg, params, SLOT_LEN)


def _member_decode(cfg, heads):
    """A member of ``cfg`` whose layer i keeps ``heads[i]`` heads and its
    MLP, as ``jax.eval_shape`` structs."""
    from repro.models import model_init
    from repro.models.pruned import PrunedLayer, PrunedModel
    from repro.serve.engine import PrunedServeModel
    dh = cfg.resolved_head_dim

    def member(key):
        p = model_init(cfg, key)[0]
        lps = []
        for i, h in enumerate(heads):
            lp = jax.tree.map(lambda a: a[i], p["layers"])
            a = lp["attn"]
            lp["attn"] = {"wq": a["wq"][:, :h * dh], "wk": a["wk"][:, :h * dh],
                          "wv": a["wv"][:, :h * dh], "wo": a["wo"][:h * dh]}
            lps.append(lp)
        return lps, {k: v for k, v in p.items() if k != "layers"}

    lps, globals_ = jax.eval_shape(member, jax.random.key(0))
    pm = PrunedModel(cfg, [PrunedLayer(kv_groups=h, d_ff=cfg.d_ff, params=lp)
                           for h, lp in zip(heads, lps)], globals_)
    return PrunedServeModel(pm, SLOT_LEN)


@pytest.mark.parametrize("adapter", ["dense", "member"])
def test_serve_decode_writes_kv_in_place(one_chip, monkeypatch, adapter):
    """Both adapters' ``jit_serve_decode`` at the serving cells' widths
    (GPT-2 small, 64 slots of 1,024; depth cut to 2 dense layers, or a
    member with layers of 12, 2 and 1 heads) convert no K/V buffer or
    stacked cache out of its layout, and donate the K/V whole: a per-slot
    scatter made the compiler copy every buffer out of its layout and
    back, and the dense layer scan copied each layer's slice out and back.
    An asynchronous copy (``copy-start``) that keeps the layout only moves
    a buffer between on-chip and device memory."""
    from repro.serve import engine
    monkeypatch.setattr(engine, "_donate_kv", lambda: True)
    if adapter == "dense":
        cfg = GPT2_SMALL.replace(num_layers=2)
        model = _dense_decode(cfg)
    else:
        cfg = GPT2_SMALL.replace(num_layers=3)
        model = _member_decode(cfg, (12, 2, 1))
    cache = jax.eval_shape(lambda: model.init_slots(SLOTS))
    args = _on(one_chip, (model.weights, cache["attn"], cache["pos"],
                          jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32), {}))
    _assert_kv_in_place(model._step.lower(*args).compile(), cache["attn"])


def _assert_kv_in_place(c, attn):
    """No K/V buffer (nor one layer's slice of a stack) is copied or moved
    into another layout, and the K/V are donated whole."""
    import re
    kv = jax.tree.leaves(attn)
    # a buffer, and for a stacked cache also one layer's slice of it
    shapes = {s for x in kv for s in (
        (x.shape, x.shape[1:], (1, *x.shape[1:])) if x.ndim == 5
        else (x.shape,))}
    kv_shapes = {"bf16[%s]" % ",".join(map(str, s)) for s in shapes}
    text = c.as_text()
    copies = re.findall(r"= (bf16\[[\d,]*\])\S* copy\(", text)
    assert not kv_shapes & set(copies), sorted(copies)
    moves = re.findall(r"= \((bf16\[[\d,]*\])(\{[^}]*\}), bf16\[[\d,]*\]"
                       r"(\{[^}]*\}), u32\[\]\S*\) copy-start\(", text)
    layout = lambda s: re.sub(r"S\(\d+\)", "", s)  # noqa: E731
    converted = [m for m in moves
                 if m[0] in kv_shapes and layout(m[1]) != layout(m[2])]
    assert not converted, converted
    kv_bytes = sum(x.size * x.dtype.itemsize for x in kv)
    assert c.memory_analysis().alias_size_in_bytes == kv_bytes


def test_mellum_decode_writes_kv_in_place(one_chip, monkeypatch):
    """Mellum2's ``jit_serve_decode`` at its published widths (one period
    of 3 window layers and 1 full one, 8 held experts, the code-long
    cell's 32 slots of 4,096) writes the window rings of 1,024 and the full
    caches in place: each is one stack per position of the period, held
    heads-major (heads of 128 lanes), read and written back once a scan
    step, and none is converted out of its layout."""
    from repro.models import model_init
    from repro.serve import engine
    monkeypatch.setattr(engine, "_donate_kv", lambda: True)
    cfg = MELLUM2_12B.replace(num_layers=4, experts_held=8)
    # weights held in bfloat16, norm scales in float32
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x if path[-1].key == "scale" else
        jax.ShapeDtypeStruct(x.shape, jnp.bfloat16),
        jax.eval_shape(lambda k: model_init(cfg, k)[0], jax.random.key(0)))
    model = engine.DenseServeModel(cfg, params, 4096)
    cache = jax.eval_shape(lambda: model.init_slots(32))
    assert [x["k"].shape for x in cache["attn"]] == [
        (1, 32, 4, n, 128) for n in (1024, 1024, 1024, 4096)]
    args = _on(one_chip, (model.weights, cache["attn"], cache["pos"],
                          jax.ShapeDtypeStruct((32, 1), jnp.int32),
                          {"route": cache["route"],
                           "live": jax.ShapeDtypeStruct((32,), jnp.bool_)}))
    _assert_kv_in_place(model._step.lower(*args).compile(), cache["attn"])
