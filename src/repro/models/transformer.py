"""Transformer stacks: init + forward (train / prefill / decode) for every
assigned family (dense / moe / ssm / hybrid / vlm / audio enc-dec / encoder).

Layers are stacked along a leading ``layers`` dim and executed with
``jax.lax.scan`` (+ per-block ``jax.remat``) so the HLO is O(1) in depth.
VLM cross-attention layers use a two-level scan: outer over groups of
``cross_attn_every`` self-layers, each followed by one cross-attn module.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..distributed.activation import constrain_batch
from . import attention as attn_mod
from . import ffn as ffn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .layers import (apply_norm, compute_dtype, dense_init, embed_tokens,
                     embedding_init, layer_rope, lm_head_init, norm_init,
                     unembed)

# named scope of the held experts and their router in the decode and
# prefill programs (attention's are ``attention.ATTN_SCOPE``); the compiler
# keeps it in each operation's ``op_name`` metadata
MOE_SCOPE = "moe"


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------

def _block_init(cfg, key, nlayers: int, *, kind: str):
    """kind: self | ssm | hybrid | decoder (self+cross)."""
    p: Dict[str, Any] = {}
    s: Dict[str, Any] = {}
    ks = iter(jax.random.split(key, 8))
    if kind != "ssm":
        p["ln1"], s["ln1"] = norm_init(cfg, nlayers)
        p["attn"], s["attn"] = attn_mod.attention_init(next(ks), cfg, nlayers)
        p["ln2"], s["ln2"] = norm_init(cfg, nlayers)
        if cfg.num_experts:
            p["moe"], s["moe"] = moe_mod.moe_init(next(ks), cfg, nlayers)
        else:
            p["ffn"], s["ffn"] = ffn_mod.ffn_init(next(ks), cfg, nlayers)
        if kind == "hybrid":
            p["ssm"], s["ssm"] = ssm_mod.ssm_init(next(ks), cfg, nlayers)
        if kind == "decoder":
            p["lnx"], s["lnx"] = norm_init(cfg, nlayers)
            p["xattn"], s["xattn"] = attn_mod.attention_init(
                next(ks), cfg, nlayers, cross=True)
    else:
        p["ln1"], s["ln1"] = norm_init(cfg, nlayers)
        p["ssm"], s["ssm"] = ssm_mod.ssm_init(next(ks), cfg, nlayers)
    return p, s


def block_kind(cfg) -> str:
    if cfg.family == "ssm":
        return "ssm"
    if cfg.hybrid:
        return "hybrid"
    if cfg.encoder_decoder:
        return "decoder"
    return "self"


def model_init(cfg, key):
    """Returns (params, specs) for the full model."""
    ks = iter(jax.random.split(key, 10))
    p: Dict[str, Any] = {}
    s: Dict[str, Any] = {}
    p["embed"], s["embed"] = embedding_init(next(ks), cfg)
    n = len(cfg.attention_period)
    if n > 1:
        # a layer pattern: one stack per position of the period, so that
        # a scan step over periods reads (and writes the cache of) each
        # stack once
        _check_pattern(cfg)
        k_layers = next(ks)
        p["layers"], s["layers"] = (list(t) for t in zip(*[
            _block_init(cfg, jax.random.fold_in(k_layers, j),
                        cfg.num_layers // n, kind=block_kind(cfg))
            for j in range(n)]))
    else:
        p["layers"], s["layers"] = _block_init(cfg, next(ks), cfg.num_layers,
                                               kind=block_kind(cfg))
    p["final_norm"], s["final_norm"] = norm_init(cfg)
    p["head"], s["head"] = lm_head_init(next(ks), cfg)

    if cfg.encoder_decoder:
        enc_cfg = cfg.replace(causal=False, attention="full")
        p["enc_layers"], s["enc_layers"] = _block_init(
            enc_cfg, next(ks), cfg.num_encoder_layers, kind="self")
        p["enc_norm"], s["enc_norm"] = norm_init(cfg)
        p["enc_pos"] = dense_init(next(ks), (cfg.num_frontend_tokens,
                                             cfg.d_model), in_axis=-1)
        s["enc_pos"] = (None, "embed")

    if cfg.cross_attn_every:
        g = cfg.num_layers // cfg.cross_attn_every
        p["cross"], s["cross"] = {}, {}
        p["cross"]["lnx"], s["cross"]["lnx"] = norm_init(cfg, g)
        p["cross"]["xattn"], s["cross"]["xattn"] = attn_mod.attention_init(
            next(ks), cfg, g, cross=True)
        if cfg.frontend_dim and cfg.frontend_dim != cfg.d_model:
            p["frontend_proj"] = dense_init(
                next(ks), (cfg.frontend_dim, cfg.d_model))
            s["frontend_proj"] = (None, "embed")
    return p, s


# ----------------------------------------------------------------------
# block forward (full-sequence; used by train & prefill)
# ----------------------------------------------------------------------

def _self_block(cfg, lp, x, *, build_cache: bool, capture: bool,
                attn_kind=None):
    """One standard block, its attention of kind ``attn_kind`` (default
    ``cfg.attention``). A pruned layer without ``attn`` skips that half
    and caches nothing. Returns (x, aux, cache_kv, ssm_cache, captures)."""
    caps: Dict[str, Any] = {}
    cap_attn = {} if capture else None
    kind = block_kind(cfg)
    attn_kind = attn_kind or cfg.attention

    cache_kv = ssm_cache = None
    if "attn" in lp:
        h = apply_norm(cfg, lp["ln1"], x)
        if build_cache:
            # recompute k/v for the cache (prefill); attention reuses them
            q, k, v = attn_mod._project_qkv(cfg, lp["attn"], h, h)
            if cfg.pos_emb == "rope":
                pos = jnp.arange(h.shape[1])[None, :]
                k = attn_mod.apply_rope(k, pos, *layer_rope(cfg, attn_kind))
            cache_kv = (attn_mod.cache_rows(cfg, k),
                        attn_mod.cache_rows(cfg, v))
        a, _ = attn_mod.self_attention(cfg, lp["attn"], h, capture=cap_attn,
                                       kind=attn_kind)
        if kind == "hybrid":
            m = ssm_mod.ssm_apply(cfg, lp["ssm"], h,
                                  capture=caps if capture else None,
                                  return_cache=build_cache)
            if build_cache:
                m, ssm_cache = m
            a = 0.5 * (a + m)
        x = x + a
    if capture:
        caps["attn"] = cap_attn

    cap_ffn = {} if capture else None
    x, aux, _ = _ffn_half(cfg, lp, x, capture=cap_ffn)
    if capture:
        caps["ffn"] = cap_ffn
    return x, aux, cache_kv, ssm_cache, caps


def _ffn_half(cfg, lp, x, live=None, capture=None):
    """A block's second half: norm, FFN / held-expert MoE / MoE, residual;
    a pruned layer with neither ``ffn`` nor ``moe`` passes ``x`` through.
    Returns (x, aux, the held experts' assignments of the tokens where
    ``live`` holds, or None)."""
    aux = jnp.zeros((), jnp.float32)
    if "ffn" not in lp and "moe" not in lp:
        return x, aux, None
    h2 = apply_norm(cfg, lp["ln2"], x)
    held = None
    if cfg.experts_held:
        with jax.named_scope(MOE_SCOPE):
            f, held = moe_mod.moe_apply_held(cfg, lp["moe"], h2, live)
    elif cfg.num_experts:
        f, aux = moe_mod.moe_apply(cfg, lp["moe"], h2, capture=capture)
    else:
        f = ffn_mod.ffn_apply(cfg, lp["ffn"], h2, capture=capture)
    return x + f, aux, held


def decode_block(cfg, lp, x, kv, pos, attn_kind=None, live=None):
    """One self-attention layer of a one-token decode, the body of both
    runtimes' loops (``decode_step``'s scan over stacks, the pruned
    runtime's unrolled list): norm, attention of kind ``attn_kind`` on the
    layer's K/V ``kv`` at positions ``pos``, residual, then
    :func:`_ffn_half`. A pruned layer without ``attn`` (its ``kv`` None)
    skips that half. Returns (x, kv, held-expert assignments or None)."""
    if "attn" in lp:
        h = apply_norm(cfg, lp["ln1"], x)
        a, kv = attn_mod.self_attention(cfg, lp["attn"], h, cache=kv,
                                        cache_pos=pos, kind=attn_kind)
        x = x + a
    x, _, held = _ffn_half(cfg, lp, x, live)
    return x, kv, held


def _ssm_block(cfg, lp, x, *, build_cache: bool = False, capture: bool):
    caps: Dict[str, Any] = {}
    h = apply_norm(cfg, lp["ln1"], x)
    y = ssm_mod.ssm_apply(cfg, lp["ssm"], h,
                          capture=caps if capture else None,
                          return_cache=build_cache)
    ssm_cache = None
    if build_cache:
        y, ssm_cache = y
    return x + y, jnp.zeros((), jnp.float32), None, ssm_cache, caps


def _decoder_block(cfg, lp, x, enc_kv, *, build_cache: bool = False,
                   capture: bool):
    caps: Dict[str, Any] = {}
    cap_a = {} if capture else None
    h = apply_norm(cfg, lp["ln1"], x)
    cache_kv = None
    if build_cache:
        _, k, v = attn_mod._project_qkv(cfg, lp["attn"], h, h)
        if cfg.pos_emb == "rope":
            pos = jnp.arange(h.shape[1])[None, :]
            k = attn_mod.apply_rope(k, pos, cfg.rope_theta)
        cache_kv = (attn_mod.cache_rows(cfg, k), attn_mod.cache_rows(cfg, v))
    a, _ = attn_mod.self_attention(cfg, lp["attn"], h, capture=cap_a)
    x = x + a
    hx = apply_norm(cfg, lp["lnx"], x)
    cap_x = {} if capture else None
    x = x + attn_mod.cross_attention(cfg, lp["xattn"], hx, enc_kv,
                                     capture=cap_x)
    h2 = apply_norm(cfg, lp["ln2"], x)
    cap_f = {} if capture else None
    x = x + ffn_mod.ffn_apply(cfg, lp["ffn"], h2, capture=cap_f)
    if capture:
        caps.update(attn=cap_a, xattn=cap_x, ffn=cap_f)
    return x, jnp.zeros((), jnp.float32), cache_kv, None, caps


def _maybe_remat(cfg, fn):
    return jax.remat(fn) if cfg.remat == "block" else fn


_F32_LAYER_LEAVES = {"scale", "bias", "A_log", "D", "dt_bias", "norm",
                     "gate", "conv_b"}


def _cast_layer_params(layers_p, dt):
    """Cast the big matmul weights to compute dtype BEFORE the layer scan:
    the per-layer FSDP all-gather then moves bf16 instead of fp32 master
    weights (halves gather wire bytes). Norm/scalar leaves stay fp32."""
    def cast(path, x):
        leaf = str(getattr(path[-1], "key", ""))
        if leaf in _F32_LAYER_LEAVES or not jnp.issubdtype(
                x.dtype, jnp.floating):
            return x
        return x.astype(dt)

    return jax.tree_util.tree_map_with_path(cast, layers_p)


# ----------------------------------------------------------------------
# full-sequence stacks
# ----------------------------------------------------------------------

def _scan_stack(cfg, layers_p, x, body_fn, *, collect_hiddens: bool):
    """Scan body_fn over stacked layer params."""
    def body(carry, lp):
        x = constrain_batch(carry)
        x, aux, cache_kv, ssm_cache, caps = body_fn(x, lp)
        x = constrain_batch(x)
        ys = {"aux": aux}
        if cache_kv is not None:
            ys["cache_k"], ys["cache_v"] = cache_kv
        if ssm_cache is not None:
            ys["cache_ssm"] = ssm_cache
        if caps:
            ys["caps"] = caps
        if collect_hiddens:
            ys["hidden"] = x
        return x, ys

    x, ys = jax.lax.scan(body, x, layers_p)
    return x, ys


def _scan_periods(cfg, layers_p, x, *, build_cache: bool, capture: bool,
                  collect_hiddens: bool):
    """The stack of a layer pattern as a scan over periods: ``layers_p`` is
    one stacked tree per position of the period, and each step runs the
    period's blocks in order, each with its attention kind. The per-layer
    outputs come back stacked over all layers, in order, as from
    :func:`_scan_stack`; the cache K/V as one stack per position."""
    period = cfg.attention_period
    blocks = [_maybe_remat(cfg, functools.partial(
        _self_block, cfg, build_cache=build_cache, capture=capture,
        attn_kind=k)) for k in period]

    def body(x, lps):
        per_layer, kv = [], []
        for block, lp in zip(blocks, lps):
            x = constrain_batch(x)
            x, aux, cache_kv, _, caps = block(lp, x)
            x = constrain_batch(x)
            y = {"aux": aux}
            if caps:
                y["caps"] = caps
            if collect_hiddens:
                y["hidden"] = x
            per_layer.append(y)
            kv.append(cache_kv)
        ys = jax.tree.map(lambda *a: jnp.stack(a), *per_layer)
        if build_cache:
            ys["cache_k"] = [c[0] for c in kv]
            ys["cache_v"] = [c[1] for c in kv]
        return x, ys

    x, ys = jax.lax.scan(body, x, list(layers_p))
    # (periods, position, ...) -> (layers, ...)
    caches = {k: ys.pop(k) for k in ("cache_k", "cache_v") if k in ys}
    ys = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), ys)
    ys.update(caches)
    return x, ys


def encoder_forward(cfg, params, frontend_embeds, *, capture: bool = False):
    """Whisper-style encoder over precomputed frame embeddings."""
    enc_cfg = cfg.replace(causal=False, attention="full")
    x = frontend_embeds.astype(compute_dtype(cfg))
    x = x + params["enc_pos"][None, :x.shape[1]].astype(x.dtype)

    def body2(x, lp):
        y, aux, _, _, caps = _self_block(enc_cfg, lp, x, build_cache=False,
                                         capture=capture)
        return y, aux, None, None, caps

    x, ys = _scan_stack(cfg, params["enc_layers"], x,
                        _maybe_remat(cfg, body2), collect_hiddens=False)
    return apply_norm(cfg, params["enc_norm"], x), ys


def forward(cfg, params, tokens, *, frontend_embeds=None, mode: str = "train",
            capture: bool = False, collect_hiddens: bool = False):
    """Full-sequence forward.

    mode: "train" (logits over all positions) or "prefill" (also returns the
    KV cache). Returns dict(logits, hiddens?, caches?, captures?, aux).
    """
    dt = compute_dtype(cfg)
    build_cache = mode == "prefill"
    x = constrain_batch(embed_tokens(cfg, params["embed"], tokens))
    out: Dict[str, Any] = {}
    params = dict(params)
    params["layers"] = _cast_layer_params(params["layers"], dt)

    enc_kv = None
    if cfg.encoder_decoder:
        enc_out, _ = encoder_forward(cfg, params, frontend_embeds,
                                     capture=capture)
        out["encoder_out"] = enc_out
        # per-layer cross K/V: vmap over stacked decoder layer params
        enc_kv = jax.vmap(lambda lp: attn_mod.cross_kv(cfg, lp, enc_out))(
            params["layers"]["xattn"])
        out["cross_kv"] = enc_kv

    cross_kv_g = None
    if cfg.cross_attn_every:
        fe = frontend_embeds.astype(dt)
        if "frontend_proj" in params:
            fe = jnp.einsum("btf,fd->btd", fe, params["frontend_proj"].astype(dt))
        cross_kv_g = jax.vmap(lambda lp: attn_mod.cross_kv(cfg, lp, fe))(
            params["cross"]["xattn"])
        out["frontend_kv"] = cross_kv_g

    kind = block_kind(cfg)
    if kind == "ssm":
        def body(x, lp):
            return _ssm_block(cfg, lp, x, build_cache=build_cache,
                              capture=capture)
    elif kind == "decoder":
        def body(x, lp):
            lp, kv = lp["lp"], lp["kv"]
            return _decoder_block(cfg, lp, x, kv, build_cache=build_cache,
                                  capture=capture)
    else:
        def body(x, lp):
            return _self_block(cfg, lp, x, build_cache=build_cache,
                               capture=capture)

    body = _maybe_remat(cfg, body)

    if cfg.cross_attn_every:
        # two-level scan: groups of `every` self layers + 1 cross module
        every = cfg.cross_attn_every
        g = cfg.num_layers // every
        grouped = jax.tree.map(
            lambda a: a.reshape(g, every, *a.shape[1:]), params["layers"])

        def group_body(x, gp):
            lp, cp, kv = gp["layers"], gp["cross"], gp["kv"]

            def inner(x, lp1):
                x = constrain_batch(x)
                x, aux, ckv, scache, caps = body(x, lp1)
                x = constrain_batch(x)
                ys = {"aux": aux}
                if ckv is not None:
                    ys["cache_k"], ys["cache_v"] = ckv
                if scache is not None:
                    ys["cache_ssm"] = scache
                if caps:
                    ys["caps"] = caps
                if collect_hiddens:
                    ys["hidden"] = x
                return x, ys

            x, ys = jax.lax.scan(inner, x, lp)
            hx = apply_norm(cfg, cp["lnx"], x)
            cap_x = {} if capture else None
            x = x + attn_mod.cross_attention(cfg, cp["xattn"], hx, kv,
                                             capture=cap_x)
            if capture:
                ys["cross_caps"] = cap_x
            return x, ys

        cross_grouped = params["cross"]
        x, ys = jax.lax.scan(
            group_body, x,
            {"layers": grouped, "cross": cross_grouped, "kv": cross_kv_g})
        # flatten (g, every, ...) -> (L, ...)
        ys = jax.tree.map(
            lambda a: (a.reshape(cfg.num_layers, *a.shape[2:])
                       if a.ndim >= 2 and a.shape[:2] == (g, every) else a), ys)
    elif kind == "decoder":
        x, ys = _scan_stack(cfg, {"lp": params["layers"], "kv": enc_kv}, x,
                            body, collect_hiddens=collect_hiddens)
    elif len(cfg.attention_period) > 1:
        _check_pattern(cfg)
        x, ys = _scan_periods(cfg, params["layers"], x,
                              build_cache=build_cache, capture=capture,
                              collect_hiddens=collect_hiddens)
    else:
        x, ys = _scan_stack(cfg, params["layers"], x, body,
                            collect_hiddens=collect_hiddens)

    out["logits"] = final_logits(cfg, params, constrain_batch(x))
    out["aux"] = jnp.mean(ys["aux"]) if "aux" in ys else jnp.zeros(())
    if collect_hiddens:
        out["hiddens"] = ys.get("hidden")
    if capture and "caps" in ys:
        out["captures"] = ys["caps"]
    if build_cache and "cache_k" in ys:
        # every position's K/V, (L,B,S,HKV,D) or (L,B,HKV,S,D) (the cache's
        # order), or under a layer pattern a list of one such stack per
        # position of the period
        k, v = ys["cache_k"], ys["cache_v"]
        out["cache"] = ([{"k": a, "v": b} for a, b in zip(k, v)]
                        if isinstance(k, list) else {"k": k, "v": v})
    if build_cache and "cache_ssm" in ys:
        out["cache_ssm"] = ys["cache_ssm"]
    return out


def _check_pattern(cfg):
    period = cfg.attention_period
    if block_kind(cfg) != "self" or cfg.cross_attn_every:
        raise NotImplementedError(
            "a layer pattern is run for self-attention decoders only")
    if cfg.num_layers % len(period) or not set(period) <= set(
            attn_mod.ATTN_SCOPE):
        raise ValueError(f"{cfg.num_layers} layers do not repeat the "
                         f"pattern {period}")


def ring_cache(kv, length, size: int, axis: int):
    """A window ring of ``size`` entries from prefill K/V ``kv`` (stacked
    over layers, positions on ``axis``) of a prompt of ``length`` real
    positions (may be traced; the rest is padding): entry j holds the last
    real position p with p % size == j. Entries whose position would be
    negative hold anything; decode masks them."""
    j = jnp.arange(size)
    last = jnp.asarray(length, jnp.int32) - 1
    src = jnp.clip(last - (last - j) % size, 0, kv["k"].shape[axis] - 1)
    return jax.tree.map(lambda a: jnp.take(a, src, axis=axis), kv)


# ----------------------------------------------------------------------
# decode
# ----------------------------------------------------------------------

def init_cache(cfg, batch: int, seq_len: int, dtype=None, *,
               kv_heads=None, per_slot: bool = False):
    """Allocate decode caches for the whole stack.

    ``kv_heads``: optional per-layer KV-head counts (a sequence of length
    ``num_layers``, e.g. ``[l.kv_groups for l in PrunedModel.layers]``) —
    the cache is then a *list* of per-layer ``{k, v}`` buffers sized by the
    pruned structure (``None`` for fully-dropped attention modules), so a
    ZipLM-shrunk model pays KV-cache bytes only for the heads it kept.
    Both forms feed the same layer body, :func:`decode_block`: the
    ``decode_step`` scan hands it one layer's slice of the stacked form,
    the pruned runtime's unrolled loop
    (``models.pruned.decode_step_pruned``) one buffer of the list.

    ``per_slot=True`` allocates a per-slot position vector ``pos: (B,)``
    (continuous-batching serving) instead of the scalar lockstep position.
    """
    dtype = dtype or compute_dtype(cfg)
    pos0 = jnp.zeros((batch,) if per_slot else (), jnp.int32)
    cache: Dict[str, Any] = {"pos": pos0}
    kind = block_kind(cfg)
    if kind != "ssm" and cfg.attention != "none":
        if kv_heads is not None:
            if len(kv_heads) != cfg.num_layers:
                raise ValueError(
                    f"kv_heads has {len(kv_heads)} entries for "
                    f"{cfg.num_layers} layers")
            cache["attn"] = [
                None if not h else
                {n: jnp.zeros(attn_mod.kv_shape(cfg, batch, seq_len, int(h)),
                              dtype) for n in ("k", "v")}
                for h in kv_heads]
        elif len(cfg.attention_period) > 1:
            # one stack per position of the layer pattern, each of its
            # layers' attention kind (a window ring or max_len positions)
            period = cfg.attention_period
            cache["attn"] = [attn_mod.init_kv_cache(
                cfg, batch, seq_len, cfg.num_layers // len(period), dtype,
                kind=k) for k in period]
        else:
            cache["attn"] = attn_mod.init_kv_cache(cfg, batch, seq_len,
                                                   cfg.num_layers, dtype)
    if kind in ("ssm", "hybrid"):
        cache["ssm"] = ssm_mod.init_ssm_cache(cfg, batch, cfg.num_layers, dtype)
    if cfg.encoder_decoder:
        t = cfg.num_frontend_tokens
        dh = cfg.resolved_head_dim
        shape = (cfg.num_layers, batch, t, cfg.num_kv_heads, dh)
        cache["cross"] = {"k": jnp.zeros(shape, dtype),
                          "v": jnp.zeros(shape, dtype)}
    if cfg.cross_attn_every:
        g = cfg.num_layers // cfg.cross_attn_every
        t = cfg.num_frontend_tokens
        dh = cfg.resolved_head_dim
        shape = (g, batch, t, cfg.num_kv_heads, dh)
        cache["cross"] = {"k": jnp.zeros(shape, dtype),
                          "v": jnp.zeros(shape, dtype)}
    return cache


def embed_at(cfg, embed_p, tokens, pos):
    """One token per row, embedded at position ``pos``: a scalar (lockstep
    batch) or (B,) per-slot positions."""
    positions = None
    if cfg.pos_emb == "learned":
        positions = pos[:, None] if jnp.ndim(pos) == 1 else pos[None]
    return embed_tokens(cfg, embed_p, tokens, positions=positions)


def final_logits(cfg, params, x):
    """Final norm and unembedding of ``params`` (a model's tree, or the
    globals of a pruned one): fp32 logits."""
    x = apply_norm(cfg, params["final_norm"], x)
    return unembed(cfg, params["embed"], params.get("head", {}), x)


def decode_step(cfg, params, cache, tokens):
    """One-token decode. tokens: (B, 1). Returns (logits (B,1,V), new_cache).

    ``cache["pos"]`` is a scalar (lockstep batch) or a (B,) vector of
    per-slot positions (continuous batching): each slot then embeds, RoPE-
    rotates, writes and masks at its own absolute position.

    Under a layer pattern the layers run as a scan over its periods, each
    layer with its attention kind (``cfg.attention_period``): the params
    and the K/V are one stack per position of the period, so that each
    scan step reads each stack's slice once and writes it back in place.
    With held experts (``cfg.experts_held``) and a routing counter
    ``cache["route"]`` (int32 (3,): held token-expert assignments, held
    experts with a token summed over layers and steps, most tokens on one
    held expert in a layer and step), the step adds its routing to the
    counter, over the slots where ``cache["live"]`` (B,) holds (all
    without it).
    """
    pos = cache["pos"]
    x = constrain_batch(embed_at(cfg, params["embed"], tokens, pos))
    kind = block_kind(cfg)
    period = cfg.attention_period
    live = cache.get("live")

    # The stacked self-attention K/V ride in the scan's carry, and layer i
    # reads and writes back its own slice: passed as xs and restacked as
    # ys, every layer's slice is copied out and back. Other caches stay
    # per-layer xs/ys.
    def layer(x, kv, i, lp, attn_kind, new_c):
        x = constrain_batch(x)
        if kind == "ssm":
            h = apply_norm(cfg, lp["ln1"], x)
            y, new_c["ssm"] = ssm_mod.ssm_decode_step(cfg, lp["ssm"], h,
                                                      lp["cache_ssm"])
            return x + y, kv
        layer_kv = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), kv)
        if kind == "self":
            x, layer_kv, held = decode_block(cfg, lp, x, layer_kv, pos,
                                             attn_kind, live)
        else:   # hybrid or decoder: the halves around their own modules
            h = apply_norm(cfg, lp["ln1"], x)
            a, layer_kv = attn_mod.self_attention(
                cfg, lp["attn"], h, cache=layer_kv, cache_pos=pos,
                kind=attn_kind)
            if kind == "hybrid":
                m, new_c["ssm"] = ssm_mod.ssm_decode_step(
                    cfg, lp["ssm"], h, lp["cache_ssm"])
                a = 0.5 * (a + m)
            x = x + a
            if kind == "decoder":
                hx = apply_norm(cfg, lp["lnx"], x)
                x = x + attn_mod.cross_attention(cfg, lp["xattn"], hx,
                                                 lp["cache_cross"])
            x, _, held = _ffn_half(cfg, lp, x, live)
        if held is not None:
            new_c.setdefault("route", []).append(held)
        kv = jax.tree.map(
            lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, i, 0),
            kv, layer_kv)
        return x, kv

    def body(carry, lp):
        x, kv, i = carry
        new_c = {}
        if len(period) == 1:
            x, kv = layer(x, kv, i, lp, period[0], new_c)
        else:   # position j of the period: its params, its K/V stack
            kv = list(kv)
            for j, attn_kind in enumerate(period):
                x, kv[j] = layer(x, kv[j], i, lp[j], attn_kind, new_c)
        if "route" in new_c:
            new_c["route"] = jnp.stack(new_c["route"])
        return (x, kv, i + 1), new_c

    if len(period) > 1:
        _check_pattern(cfg)
        scan_in = list(params["layers"])
    else:
        scan_in = dict(params["layers"])
    if "ssm" in cache:
        scan_in["cache_ssm"] = cache["ssm"]
    if kind == "decoder":
        scan_in["cache_cross"] = cache["cross"]
    carry = (x, cache.get("attn"), jnp.int32(0))

    if cfg.cross_attn_every:
        every = cfg.cross_attn_every
        g = cfg.num_layers // every
        grouped = jax.tree.map(lambda a: a.reshape(g, every, *a.shape[1:]),
                               scan_in)

        def group_body(carry, gp):
            (x, kv, i), new_c = jax.lax.scan(body, carry, gp["layers"])
            hx = apply_norm(cfg, gp["cross"]["lnx"], x)
            x = x + attn_mod.cross_attention(cfg, gp["cross"]["xattn"], hx,
                                             gp["kv"])
            return (x, kv, i), new_c

        (x, new_attn, _), new_caches = jax.lax.scan(
            group_body, carry,
            {"layers": grouped, "cross": params["cross"],
             "kv": cache["cross"]})
        new_caches = jax.tree.map(
            lambda a: a.reshape(cfg.num_layers, *a.shape[2:]), new_caches)
    else:
        (x, new_attn, _), new_caches = jax.lax.scan(body, carry, scan_in)

    logits = final_logits(cfg, params, x)
    new_cache = dict(cache)
    new_cache.pop("live", None)
    new_cache["pos"] = pos + 1
    if "attn" in cache:
        new_cache["attn"] = new_attn
    if "ssm" in new_caches:
        new_cache["ssm"] = new_caches["ssm"]
    if "route" in cache and "route" in new_caches:
        held = new_caches["route"].reshape(-1, cfg.experts_held)  # (L, held)
        r = cache["route"]
        new_cache["route"] = jnp.stack([
            r[0] + held.sum(), r[1] + (held > 0).sum(),
            jnp.maximum(r[2], held.max())]).astype(jnp.int32)
    return logits, new_cache
