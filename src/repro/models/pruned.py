"""Heterogeneous pruned-model execution.

After ZipLM shrink, layers have *different* head counts / FC widths (and
some modules are dropped entirely), so the homogeneous ``lax.scan`` stack no
longer applies. This module runs per-layer parameter lists with an unrolled
loop, reusing the same primitive ops — this is where the structural speedup
actually materializes (smaller matmuls / skipped modules).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from .layers import apply_norm, compute_dtype, embed_tokens, unembed


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
        leaves = jax.tree.leaves([l.params for l in self.layers]) \
            + jax.tree.leaves(self.globals_)
        return int(sum(x.size for x in leaves))

    def encoder_params(self) -> int:
        """Transformer-stack params only (paper reports 'encoder size')."""
        return int(sum(x.size for l in self.layers
                       for x in jax.tree.leaves(l.params)))


def _vcfg(cfg, lcfg: PrunedLayer):
    """Per-layer view config: head counts shrunk to this layer's survivors.
    ``head_dim`` is pinned: a config that derives it as d_model // heads
    (GPT2-small) would otherwise widen each surviving head."""
    return cfg.replace(num_heads=lcfg.kv_groups * cfg.q_per_kv,
                       num_kv_heads=lcfg.kv_groups,
                       head_dim=cfg.resolved_head_dim)


def _attn_forward(cfg, lcfg: PrunedLayer, lp, x):
    out, _ = attn_mod.self_attention(_vcfg(cfg, lcfg), lp, x)
    return out


def _ffn_forward(cfg, lp, x):
    dt = x.dtype
    if "wg" in lp:
        h = jax.nn.silu(x @ lp["wg"].astype(dt)) * (x @ lp["wu"].astype(dt))
    else:
        h = jax.nn.gelu(x @ lp["wi"].astype(dt) + lp["bi"].astype(dt))
    y = h @ lp["wd"].astype(dt)
    if "bd" in lp:
        y = y + lp["bd"].astype(dt)
    return y


def _moe_forward(cfg, lcfg: PrunedLayer, lp, x):
    """Pruned MoE: per-expert widths differ. Fully-dropped experts keep
    their router column and hold a ``None`` compute slot, so the top-k
    selection (and the normalization over the selected weights) is exactly
    the masked model's — a dead expert can still win a top-k slot and
    absorb routing weight, it just contributes nothing. Dense-gather
    dispatch per live expert (unrolled; few experts after pruning)."""
    dt = x.dtype
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
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
    return out.reshape(b, s, d)


def _ssm_forward(cfg, lcfg: PrunedLayer, lp, x):
    """SSD block at pruned width (dims derive from the shrunk weights)."""
    from . import ssm as ssm_mod
    di = lcfg.ssm_heads * cfg.ssm_head_dim
    dt_ = x.dtype
    b, s, d = x.shape
    n = cfg.ssm_state
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


def forward_pruned(pm: PrunedModel, tokens, frontend_embeds=None):
    """Unrolled forward over heterogeneous pruned layers -> fp32 logits."""
    cfg = pm.cfg
    x = embed_tokens(cfg, pm.globals_["embed"], tokens)
    for lcfg in pm.layers:
        lp = lcfg.params
        attn_out = None
        if lcfg.kv_groups > 0 and "attn" in lp:
            h = apply_norm(cfg, lp["ln1"], x)
            attn_out = _attn_forward(cfg, lcfg, lp["attn"], h)
        ssm_out = None
        if lcfg.ssm_heads > 0 and "ssm" in lp:
            h = apply_norm(cfg, lp["ln1"], x)
            ssm_out = _ssm_forward(cfg, lcfg, lp["ssm"], h)
        if attn_out is not None and ssm_out is not None:
            x = x + 0.5 * (attn_out + ssm_out)
        elif cfg.hybrid and (attn_out is not None or ssm_out is not None):
            live = attn_out if attn_out is not None else ssm_out
            x = x + 0.5 * live
        elif attn_out is not None:
            x = x + attn_out
        elif ssm_out is not None:
            x = x + ssm_out

        if lcfg.expert_ff:
            h2 = apply_norm(cfg, lp["ln2"], x)
            x = x + _moe_forward(cfg, lcfg, lp["moe"], h2)
        elif lcfg.d_ff > 0 and ("ffn" in lp):
            h2 = apply_norm(cfg, lp["ln2"], x)
            x = x + _ffn_forward(cfg, lp["ffn"], h2)
    x = apply_norm(cfg, pm.globals_["final_norm"], x)
    return unembed(cfg, pm.globals_["embed"], pm.globals_.get("head", {}), x)


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
    from .transformer import init_cache
    _check_decodable(pm.cfg)
    kv_heads = [l.kv_groups if (l.kv_groups > 0 and "attn" in l.params) else 0
                for l in pm.layers]
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
            if (l.kv_groups > 0 and "attn" in l.params) else 0
            for l in pm.layers]


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
    cfg = pm.cfg
    _check_decodable(cfg)
    b, s = tokens.shape
    if s > max_len:
        raise RuntimeError(f"prompt_len={s} exceeds cache max_len={max_len}")
    cache = init_cache_pruned(pm, b, max_len)
    x = embed_tokens(cfg, pm.globals_["embed"], tokens)
    for i, lcfg in enumerate(pm.layers):
        lp = lcfg.params
        if lcfg.kv_groups > 0 and "attn" in lp:
            vcfg = _vcfg(cfg, lcfg)
            h = apply_norm(cfg, lp["ln1"], x)
            # recompute k/v for the cache; attention reuses them internally
            _, k, v = attn_mod._project_qkv(vcfg, lp["attn"], h, h)
            if cfg.pos_emb == "rope":
                pos = jnp.arange(s)[None, :]
                k = attn_mod.apply_rope(k, pos, cfg.rope_theta)
            buf = cache["attn"][i]
            cache["attn"][i] = {
                n: jax.lax.dynamic_update_slice_in_dim(
                    buf[n], attn_mod.cache_rows(vcfg, x).astype(buf[n].dtype),
                    0, axis=attn_mod.kv_pos_axis(vcfg))
                for n, x in (("k", k), ("v", v))}
            a, _ = attn_mod.self_attention(vcfg, lp["attn"], h)
            x = x + a
        if lcfg.expert_ff:
            h2 = apply_norm(cfg, lp["ln2"], x)
            x = x + _moe_forward(cfg, lcfg, lp["moe"], h2)
        elif lcfg.d_ff > 0 and "ffn" in lp:
            h2 = apply_norm(cfg, lp["ln2"], x)
            x = x + _ffn_forward(cfg, lp["ffn"], h2)
    x = apply_norm(cfg, pm.globals_["final_norm"], x)
    logits = unembed(cfg, pm.globals_["embed"], pm.globals_.get("head", {}), x)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return (logits if full_logits else logits[:, -1:]), cache


def decode_step_pruned(pm: PrunedModel, cache, tokens):
    """One-token decode over heterogeneous pruned layers (unrolled).

    ``cache["pos"]`` scalar (lockstep) or (B,) per-slot vector, same
    contract as ``transformer.decode_step``. Returns (logits, new_cache).
    """
    cfg = pm.cfg
    pos = cache["pos"]
    if cfg.pos_emb == "learned":
        positions = pos[:, None] if jnp.ndim(pos) == 1 else pos[None]
    else:
        positions = None
    x = embed_tokens(cfg, pm.globals_["embed"], tokens, positions=positions)
    new_attn = list(cache["attn"])
    for i, lcfg in enumerate(pm.layers):
        lp = lcfg.params
        if lcfg.kv_groups > 0 and "attn" in lp:
            h = apply_norm(cfg, lp["ln1"], x)
            a, new_attn[i] = attn_mod.self_attention(
                _vcfg(cfg, lcfg), lp["attn"], h,
                cache=cache["attn"][i], cache_pos=pos)
            x = x + a
        if lcfg.expert_ff:
            h2 = apply_norm(cfg, lp["ln2"], x)
            x = x + _moe_forward(cfg, lcfg, lp["moe"], h2)
        elif lcfg.d_ff > 0 and "ffn" in lp:
            h2 = apply_norm(cfg, lp["ln2"], x)
            x = x + _ffn_forward(cfg, lp["ffn"], h2)
    x = apply_norm(cfg, pm.globals_["final_norm"], x)
    logits = unembed(cfg, pm.globals_["embed"], pm.globals_.get("head", {}), x)
    return logits, {"pos": pos + 1, "attn": new_attn}
