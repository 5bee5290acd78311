"""Heterogeneous pruned-model execution.

After ZipLM shrink, layers have *different* head counts / FC widths (and
some modules are dropped entirely), so the homogeneous ``lax.scan`` stack no
longer applies. This runtime runs the per-layer parameter lists as an
unrolled loop over ``transformer``'s own blocks (``_self_block`` for
forward and prefill, ``decode_block`` for decode), each under a view
config of its layer's widths; a dropped module is an absent key, whose
half the block skips. This is where the structural speedup materializes
(smaller matmuls / skipped modules). A pruned MoE and a pruned SSM keep
their own code here.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from .layers import apply_norm, compute_dtype, embed_tokens
from .transformer import (_ffn_half, _self_block, decode_block, embed_at,
                          final_logits, init_cache)


@dataclass
class PrunedLayer:
    kv_groups: int = 0        # attention KV groups remaining (0 = dropped)
    d_ff: int = 0             # FFN intermediate remaining (0 = dropped)
    ssm_heads: int = 0
    expert_ff: List[int] = field(default_factory=list)  # per remaining expert
    params: Dict[str, Any] = field(default_factory=dict)


@dataclass
class PrunedModel:
    cfg: Any                  # original ModelConfig
    layers: List[PrunedLayer]
    globals_: Dict[str, Any]  # embed / final_norm / head (+cross params)

    def num_params(self) -> int:
        return int(sum(x.size for x in jax.tree.leaves(self.weights())))

    def encoder_params(self) -> int:
        """Transformer-stack params only (paper reports 'encoder size')."""
        return int(sum(x.size for x in jax.tree.leaves(self.weights()[0])))

    def weights(self):
        """The model's arrays as one tree: (per-layer params, globals)."""
        return [l.params for l in self.layers], self.globals_

    def with_weights(self, weights) -> "PrunedModel":
        """This structure over ``weights`` (as :meth:`weights` gives them):
        a jitted function that takes them as an argument rebuilds the model
        inside, so the arrays are not baked in as constants."""
        lps, globals_ = weights
        return PrunedModel(self.cfg, [dataclasses.replace(l, params=lp)
                                      for l, lp in zip(self.layers, lps)],
                           globals_)


def _vcfg(cfg, lcfg: PrunedLayer):
    """Per-layer view config: head counts shrunk to this layer's survivors.
    ``head_dim`` is pinned: a config that derives it as d_model // heads
    (GPT2-small) would otherwise widen each surviving head."""
    return cfg.replace(num_heads=lcfg.kv_groups * cfg.q_per_kv,
                       num_kv_heads=lcfg.kv_groups,
                       head_dim=cfg.resolved_head_dim)


def _block(lcfg: PrunedLayer):
    """The layer's params as ``transformer``'s blocks take them: all but a
    pruned MoE, which :func:`_moe_forward` applies after the block."""
    return {k: v for k, v in lcfg.params.items() if k != "moe"}


def _moe_forward(cfg, lcfg: PrunedLayer, x):
    """The layer's pruned MoE half (norm, experts, residual), where it kept
    one. Per-expert widths differ. Fully-dropped experts keep
    their router column and hold a ``None`` compute slot, so the top-k
    selection (and the normalization over the selected weights) is exactly
    the masked model's — a dead expert can still win a top-k slot and
    absorb routing weight, it just contributes nothing. Dense-gather
    dispatch per live expert (unrolled; few experts after pruning)."""
    # kept apart from moe.py, which would otherwise branch on its caller
    if not lcfg.expert_ff:
        return x
    lp = lcfg.params["moe"]
    dt = x.dtype
    b, s, d = x.shape
    t = b * s
    xf = apply_norm(cfg, lcfg.params["ln2"], x).reshape(t, d)
    logits = (xf @ lp["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    k = min(cfg.num_experts_per_tok, probs.shape[-1])
    topw, topi = jax.lax.top_k(probs, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    out = jnp.zeros((t, d), dt)
    for e, ep in enumerate(lp["experts"]):
        if ep is None:  # dropped: routable, zero contribution, no FLOPs
            continue
        w_e = jnp.where(topi == e, topw, 0.0).sum(-1).astype(dt)  # (t,)
        h = jax.nn.silu(xf @ ep["wg"].astype(dt)) * (xf @ ep["wu"].astype(dt))
        out = out + w_e[:, None] * (h @ ep["wd"].astype(dt))
    return x + out.reshape(b, s, d)


def _ssm_forward(cfg, lcfg: PrunedLayer, lp, x):
    """SSD block at pruned width (dims derive from the shrunk weights)."""
    # kept apart: a view config cannot state a pruned SSM's head count
    # (``ssm_heads`` derives from ``d_inner``)
    from . import ssm as ssm_mod
    di = lcfg.ssm_heads * cfg.ssm_head_dim
    dt_ = x.dtype
    b, s, d = x.shape
    h = lcfg.ssm_heads
    hp = cfg.ssm_head_dim
    z = x @ lp["in_z"].astype(dt_)
    xs = x @ lp["in_x"].astype(dt_)
    bc = x @ lp["in_bc"].astype(dt_)
    dtv = x @ lp["in_dt"].astype(dt_)
    xs = jax.nn.silu(ssm_mod.causal_conv1d(xs, lp["conv_x"],
                                           lp["conv_x_b"]))
    bc = jax.nn.silu(ssm_mod.causal_conv1d(bc, lp["conv_bc"],
                                           lp["conv_bc_b"]))
    B, C = jnp.split(bc, 2, axis=-1)
    dtv = jax.nn.softplus(dtv.astype(jnp.float32) + lp["dt_bias"])
    A = -jnp.exp(lp["A_log"])
    y, _ = ssm_mod.ssd_chunked(xs.reshape(b, s, h, hp), dtv, A, B, C,
                               cfg.ssm_chunk)
    y = y + lp["D"].astype(dt_)[None, None, :, None] * xs.reshape(b, s, h, hp)
    y = ssm_mod._gated_headnorm(y.reshape(b, s, di) * jax.nn.silu(z),
                                lp["norm"], hp)
    return y @ lp["out_proj"].astype(dt_)


def _mixers(cfg, lcfg: PrunedLayer, x):
    """The token mixers of a layer with a pruned SSM (or of a hybrid
    layer): what is left of its SSM and its attention, on one norm and
    averaged in a hybrid."""
    lp = lcfg.params
    if "ln1" not in lp:     # both dropped
        return x
    h = apply_norm(cfg, lp["ln1"], x)
    y = _ssm_forward(cfg, lcfg, lp["ssm"], h) if "ssm" in lp else 0.0
    if "attn" in lp:
        y = y + attn_mod.self_attention(_vcfg(cfg, lcfg), lp["attn"], h)[0]
    return x + (0.5 * y if cfg.hybrid else y)


def _forward(pm: PrunedModel, tokens, build_cache: bool = False):
    """The layers in order, each ``transformer._self_block`` under its view
    config (a layer beside a pruned SSM: its :func:`_mixers` and the
    block's FFN half), then its pruned MoE. Returns (fp32 logits, each
    layer's prompt K/V, or None where it caches none)."""
    cfg = pm.cfg
    x = embed_tokens(cfg, pm.globals_["embed"], tokens)
    kvs = []
    for lcfg in pm.layers:
        vcfg, lp = _vcfg(cfg, lcfg), _block(lcfg)
        if cfg.hybrid or "ssm" in lp:
            x, kv = _ffn_half(vcfg, lp, _mixers(cfg, lcfg, x))[0], None
        else:
            x, _, kv, _, _ = _self_block(vcfg, lp, x, build_cache=build_cache,
                                         capture=False)
        kvs.append(None if kv is None else dict(zip("kv", kv)))
        x = _moe_forward(cfg, lcfg, x)
    return final_logits(cfg, pm.globals_, x), kvs


def forward_pruned(pm: PrunedModel, tokens, frontend_embeds=None):
    """Unrolled forward over heterogeneous pruned layers -> fp32 logits."""
    return _forward(pm, tokens)[0]


# ----------------------------------------------------------------------
# pruned decode runtime (serving)
# ----------------------------------------------------------------------

def _check_decodable(cfg):
    if cfg.family == "ssm" or cfg.hybrid or cfg.encoder_decoder \
            or cfg.cross_attn_every:
        raise NotImplementedError(
            "pruned decode runtime covers attention+FFN/MoE decoders only; "
            f"family={cfg.family!r} hybrid={cfg.hybrid} "
            f"enc-dec={cfg.encoder_decoder} needs the dense runtime")


def init_cache_pruned(pm: PrunedModel, batch: int, max_len: int, dtype=None,
                      *, per_slot: bool = False):
    """Per-layer pruned KV cache: bytes follow the *shrunk* structure.

    Dropped attention modules get ``None``; kept ones a (B, max_len,
    kv_groups, head_dim) buffer — this is the cache-bytes win the serve
    bench asserts.
    """
    _check_decodable(pm.cfg)
    kv_heads = [l.kv_groups if "attn" in l.params else 0 for l in pm.layers]
    return init_cache(pm.cfg, batch, max_len, dtype, kv_heads=kv_heads,
                      per_slot=per_slot)


def kv_cache_bytes_per_layer(pm: PrunedModel, batch: int, max_len: int,
                             dtype=None) -> List[int]:
    """Per-layer byte footprint of ``init_cache_pruned``'s k/v buffers.

    0 for layers whose attention module is pruned away or whose whole
    layer is dropped — those allocate no cache at all.  KV-head pruning
    (GQA levels remove whole KV heads with their query groups) makes
    these entries strictly shrink; that is the serving-side win the
    serve tests/bench assert per layer.
    """
    itemsize = jnp.dtype(dtype or compute_dtype(pm.cfg)).itemsize
    dh = pm.cfg.resolved_head_dim
    return [2 * batch * max_len * l.kv_groups * dh * itemsize
            if "attn" in l.params else 0 for l in pm.layers]


def kv_cache_bytes(pm: PrunedModel, batch: int, max_len: int,
                   dtype=None) -> int:
    """Exact byte footprint of ``init_cache_pruned``'s k/v buffers."""
    return sum(kv_cache_bytes_per_layer(pm, batch, max_len, dtype))


def prefill_pruned(pm: PrunedModel, tokens, max_len: int, *,
                   full_logits: bool = False):
    """Pruned prefill: full forward that also fills the per-layer KV cache.

    Mirrors ``model.serve_prefill`` for the heterogeneous runtime. Returns
    (last-position logits (B,1,V) — or all positions (B,S,V) with
    ``full_logits=True``, for bucket-padded serving prefill — and the
    cache) with ``cache["pos"]`` scalar; the serving engine re-homes rows
    into per-slot caches itself.
    """
    b, s = tokens.shape
    if s > max_len:
        raise RuntimeError(f"prompt_len={s} exceeds cache max_len={max_len}")
    cache = init_cache_pruned(pm, b, max_len)
    logits, kvs = _forward(pm, tokens, build_cache=True)
    # the prompt's rows at the start of each layer's cache, as
    # ``model.assemble_prefill_cache`` places a full layer's
    axis = attn_mod.kv_pos_axis(pm.cfg)
    cache["attn"] = jax.tree.map(
        lambda c, r: jax.lax.dynamic_update_slice_in_dim(c, r, 0, axis=axis),
        cache["attn"], kvs)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return (logits if full_logits else logits[:, -1:]), cache


def decode_step_pruned(pm: PrunedModel, cache, tokens):
    """One-token decode over heterogeneous pruned layers (unrolled), each
    layer ``transformer.decode_block``.

    ``cache["pos"]`` scalar (lockstep) or (B,) per-slot vector, same
    contract as ``transformer.decode_step``. Returns (logits, new_cache).
    """
    cfg = pm.cfg
    pos = cache["pos"]
    x = embed_at(cfg, pm.globals_["embed"], tokens, pos)
    kv = list(cache["attn"])
    for i, lcfg in enumerate(pm.layers):
        x, kv[i], _ = decode_block(_vcfg(cfg, lcfg), _block(lcfg), x, kv[i],
                                   pos)
        x = _moe_forward(cfg, lcfg, x)
    return final_logits(cfg, pm.globals_, x), {"pos": pos + 1, "attn": kv}
