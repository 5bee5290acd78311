"""Model facade: init, loss, capture, prefill/decode, and dry-run input specs.

This is the public API used by the trainer, the ZipLM pruner, the serving
path and the multi-pod dry-run.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import transformer
from .layers import compute_dtype
from .transformer import decode_step, forward, init_cache, model_init


def cross_entropy(logits, labels, mask=None):
    """Token-level CE. logits fp32 (B,S,V); labels (B,S); mask (B,S) or None."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def loss_fn(cfg, params, batch, *, collect_hiddens=False):
    """Next-token (decoder) or masked (encoder) LM loss."""
    out = forward(cfg, params, batch["tokens"],
                  frontend_embeds=batch.get("frontend"),
                  collect_hiddens=collect_hiddens)
    logits = out["logits"]
    if cfg.causal:
        logits = logits[:, :-1]
        labels = batch["tokens"][:, 1:]
        mask = batch.get("mask")
        mask = mask[:, 1:] if mask is not None else None
    else:
        labels = batch["labels"]
        mask = batch.get("mask")
    loss = cross_entropy(logits, labels, mask)
    out["loss"] = loss + 0.01 * out["aux"]
    return out


def make_batch(cfg, key, batch: int, seq: int) -> Dict[str, jnp.ndarray]:
    """Synthetic batch matching input_specs (for smoke tests / examples)."""
    ks = jax.random.split(key, 3)
    b = {"tokens": jax.random.randint(ks[0], (batch, seq), 0, cfg.vocab_size)}
    if not cfg.causal:
        b["labels"] = jax.random.randint(ks[1], (batch, seq), 0, cfg.vocab_size)
    if cfg.frontend != "none":
        b["frontend"] = jax.random.normal(
            ks[2], (batch, cfg.num_frontend_tokens, cfg.frontend_dim),
            jnp.float32).astype(compute_dtype(cfg))
    return b


def input_specs(cfg, shape_cfg, *, for_grad: bool = False) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a dry-run cell.

    train/prefill: token batch (+ frontend embeddings stub for audio/vlm).
    decode: one-token batch + fully-populated KV/SSM cache structs.
    """
    b, s = shape_cfg.global_batch, shape_cfg.seq_len
    dt = compute_dtype(cfg)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    if shape_cfg.mode in ("train", "prefill"):
        specs = {"tokens": sds((b, s))}
        if not cfg.causal:
            specs["labels"] = sds((b, s))
        if cfg.frontend != "none":
            specs["frontend"] = sds((b, cfg.num_frontend_tokens,
                                     cfg.frontend_dim), dt)
        return specs

    # decode: single token + cache
    cache = jax.eval_shape(lambda: init_cache(cfg, b, s))
    return {"tokens": sds((b, 1)), "cache": cache}


def serve_prefill(cfg, params, batch, max_len: Optional[int] = None):
    """Prefill: full forward that also materializes the decode cache.

    ``max_len`` sizes the KV cache; callers that know their generation
    length must pass ``prompt_len + steps`` (``generate`` does) — the
    fallback of 2x the prompt length is only headroom for interactive use.
    Decoding past the cache capacity is NOT silently tolerated by the
    full-attention decode path (the write index clamps to the last slot,
    corrupting every later token), so ``generate``/the serving engine
    raise before stepping past it.
    """
    b, s = batch["tokens"].shape
    max_len = max_len or 2 * s
    out = forward(cfg, params, batch["tokens"],
                  frontend_embeds=batch.get("frontend"), mode="prefill")
    cache = assemble_prefill_cache(cfg, out, b, s, max_len)
    return out["logits"][:, -1:], cache


def assemble_prefill_cache(cfg, out, batch: int, s, max_len: int):
    """Build the decode cache from a prefill ``forward`` output dict.

    Shared by ``serve_prefill`` and the continuous-batching engine (which
    prefills at a padded bucket length and re-homes rows into slots).
    ``s`` is the prompt's true length (it may be traced): the window
    layers' rings take the last real positions of the prompt, each at
    ``p % window``, never the bucket's padding.
    """
    cache = init_cache(cfg, batch, max_len)
    if "cache" in out:
        # one K/V stack per position of the layer pattern (one without)
        period = cfg.attention_period
        multi = len(period) > 1
        pre = out["cache"] if multi else [out["cache"]]
        dst = cache["attn"] if multi else [cache["attn"]]
        axis = 1 + attn_mod.kv_pos_axis(cfg)    # stacked over layers
        for j, kind in enumerate(period):
            if kind == "sliding_window":
                dst[j] = transformer.ring_cache(pre[j], s,
                                                dst[j]["k"].shape[axis], axis)
            else:   # prefill rows at the start of the cache
                dst[j] = jax.tree.map(
                    lambda c, p: jax.lax.dynamic_update_slice_in_dim(
                        c, p, 0, axis=axis), dst[j], pre[j])
        cache["attn"] = dst if multi else dst[0]
    if "cache_ssm" in out:
        cache["ssm"] = out["cache_ssm"]
    if "frontend_kv" in out:
        cache["cross"] = out["frontend_kv"]
    if "cross_kv" in out:
        cache["cross"] = out["cross_kv"]
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return cache


def serve_step(cfg, params, cache, tokens):
    """One new token against an existing cache (the decode_* dry-run target)."""
    return decode_step(cfg, params, cache, tokens)


def sample_token(logits, key=None, *, temperature: float = 1.0,
                 top_k: int = 0):
    """Next token from (B,1,V) logits: greedy if key is None, else sampled.

    ``temperature`` scales the logits before sampling; ``top_k > 0``
    restricts sampling to the k highest-probability tokens.
    """
    if key is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


def generate(cfg, params, prompt, steps: int, *, frontend=None, key=None,
             temperature: float = 1.0, top_k: int = 0,
             max_len: Optional[int] = None):
    """Generation loop (host-side; used in examples and as a serving oracle).

    Greedy when ``key=None``; temperature/top-k sampling when a PRNG key is
    passed. The KV cache is sized ``prompt_len + steps`` by default so the
    requested generation always fits; an explicit smaller ``max_len`` raises
    instead of silently clamping the cache write index.
    """
    s = prompt.shape[1]
    if max_len is None:
        max_len = s + steps
    if s + steps > max_len:
        raise RuntimeError(
            f"generation overflows the KV cache: prompt_len={s} + "
            f"steps={steps} > max_len={max_len}; decoding past capacity "
            "would overwrite the last cache slot and corrupt output")
    logits, cache = serve_prefill(
        cfg, params, {"tokens": prompt, "frontend": frontend}
        if frontend is not None else {"tokens": prompt}, max_len=max_len)
    tok = sample_token(logits, None if key is None else jax.random.fold_in(key, 0),
                       temperature=temperature, top_k=top_k)
    outs = [tok]
    step = jax.jit(lambda p, c, t: serve_step(cfg, p, c, t))
    for i in range(steps - 1):
        logits, cache = step(params, cache, tok)
        tok = sample_token(
            logits, None if key is None else jax.random.fold_in(key, i + 1),
            temperature=temperature, top_k=top_k)
        outs.append(tok)
    return jnp.concatenate(outs, axis=1)
