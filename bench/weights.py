"""Seeded weights for a GPT-2 configuration, made on the device.

The benchmark makes the weights, not the program: one jitted call from the
seed returns a plain tree (``embed``, ``pos``, ``layers`` as a list of
per-layer dicts, ``lnf``) that the plain reference reads directly. The
adapters below hand the same arrays to the program in its own layouts.

Scales follow the program's own initialisation (truncated normal, std
1/sqrt(fan_in)); biases and norm parameters are drawn too, so that every
term of the forward is exercised. A layer whose ``heads`` is 0 has no
attention block, and one whose ``n_inner`` is 0 has no MLP.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from traffic import seed_words


def layer_widths(cfg: Dict) -> List[Dict[str, int]]:
    """Per-layer kept widths: the config's ``layers`` list, else dense."""
    if "layers" in cfg:
        return [{"heads": int(l["heads"]), "n_inner": int(l["n_inner"])}
                for l in cfg["layers"]]
    return [{"heads": cfg["n_head"], "n_inner": cfg["n_inner"]}
            for _ in range(cfg["n_layer"])]


def jax_key(seed: int, salt: int = 0):
    import jax
    word = int(seed_words(seed, 2, salt).generate_state(1)[0])
    return jax.random.key(word)


def make_weights(cfg: Dict, seed: int):
    """The plain weight tree of ``cfg`` from ``seed``, in one jitted call,
    in float32: the program's master-weight type."""
    import jax
    import jax.numpy as jnp

    d, dh, v, p = cfg["n_embd"], cfg["head_dim"], cfg["vocab_size"], \
        cfg["n_positions"]
    widths = layer_widths(cfg)

    def tn(key, shape, fan_in):
        return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                           jnp.float32) / math.sqrt(fan_in)

    def small(key, shape):
        return 0.02 * jax.random.normal(key, shape, jnp.float32)

    def build(key):
        k_embed, k_pos, k_lnf, k_layers = jax.random.split(key, 4)
        tree = {"embed": tn(k_embed, (v, d), d), "pos": tn(k_pos, (p, d), d)}
        kg, kb = jax.random.split(k_lnf)
        tree["lnf"] = {"g": 1.0 + small(kg, (d,)), "b": small(kb, (d,))}
        layers = []
        for i, w in enumerate(widths):
            ks = iter(jax.random.split(jax.random.fold_in(k_layers, i), 12))
            lp = {}
            if w["heads"]:
                hd = w["heads"] * dh
                lp["ln1"] = {"g": 1.0 + small(next(ks), (d,)),
                             "b": small(next(ks), (d,))}
                lp["wq"] = tn(next(ks), (d, hd), d)
                lp["wk"] = tn(next(ks), (d, hd), d)
                lp["wv"] = tn(next(ks), (d, hd), d)
                lp["wo"] = tn(next(ks), (hd, d), hd)
            if w["n_inner"]:
                f = w["n_inner"]
                lp["ln2"] = {"g": 1.0 + small(next(ks), (d,)),
                             "b": small(next(ks), (d,))}
                lp["wi"] = tn(next(ks), (d, f), d)
                lp["bi"] = small(next(ks), (f,))
                lp["wd"] = tn(next(ks), (f, d), f)
                lp["bd"] = small(next(ks), (d,))
            layers.append(lp)
        tree["layers"] = layers
        return tree

    return jax.jit(build)(jax_key(seed))


def _norm(n):
    return {"scale": n["g"], "bias": n["b"]}


def _attn(lp):
    return {k: lp[k] for k in ("wq", "wk", "wv", "wo")}


def _ffn(lp):
    return {k: lp[k] for k in ("wi", "bi", "wd", "bd")}


def dense_params(w):
    """The program's stacked dense tree (``repro.models.transformer``)."""
    import jax
    import jax.numpy as jnp

    def stack(layers):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *[
            {"ln1": _norm(l["ln1"]), "attn": _attn(l), "ln2": _norm(l["ln2"]),
             "ffn": _ffn(l)} for l in layers])

    return {"embed": {"table": w["embed"], "pos": w["pos"]},
            "layers": jax.jit(stack)(w["layers"]),
            "final_norm": _norm(w["lnf"]), "head": {}}


def pruned_model(pcfg, w, widths):
    """The program's per-layer ``PrunedModel`` (program config ``pcfg``)
    over the same arrays."""
    from repro.models.pruned import PrunedLayer, PrunedModel

    layers = []
    for lw, lp in zip(widths, w["layers"]):
        params = {}
        if lw["heads"]:
            params["ln1"] = _norm(lp["ln1"])
            params["attn"] = _attn(lp)
        if lw["n_inner"]:
            params["ln2"] = _norm(lp["ln2"])
            params["ffn"] = _ffn(lp)
        layers.append(PrunedLayer(kv_groups=lw["heads"], d_ff=lw["n_inner"],
                                  params=params))
    return PrunedModel(cfg=pcfg, layers=layers,
                       globals_={"embed": {"table": w["embed"],
                                           "pos": w["pos"]},
                                 "final_norm": _norm(w["lnf"])})
