"""The program's side of a Mellum2 configuration (``model_type`` ``mellum``):
its ``ModelConfig`` and the engine adapter that serves the benchmark's
weights.

The configuration holds ``num_experts`` experts of each layer here (one
rank of expert parallelism, experts 0 .. num_experts - 1) of the
``num_experts_published`` that the router scores. It is served by the
scanned runtime (``repro.serve.DenseServeModel``), one scan step per
period of ``layer_types``.

Weights are made on the device from the seed, one jitted call per array,
in the program's stacked layout and in bfloat16 (norm scales in float32,
as the program keeps them). Scales follow the program's own initialisation
(truncated normal, std 1/sqrt(fan_in)); norm scales are drawn about 1.
The reference reads the same arrays through :func:`plain_tree`.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Tuple

from weights import jax_key

KINDS = {"sliding_attention": "sliding_window", "full_attention": "full"}


def period(layer_types):
    """The shortest prefix of ``layer_types`` that repeats to the whole."""
    n = len(layer_types)
    for p in range(1, n + 1):
        if n % p == 0 and list(layer_types[:p]) * (n // p) == list(
                layer_types):
            return tuple(layer_types[:p])
    return tuple(layer_types)


def program_config(cfg: Dict):
    from repro.configs.base import ModelConfig, Yarn
    rope = cfg["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    if full["rope_theta"] != window["rope_theta"] \
            or window["rope_type"] != "default" \
            or full["rope_type"] != "yarn":
        raise ValueError("the program runs one theta, YaRN on full layers")
    if cfg["hidden_act"] != "silu" or not cfg["norm_topk_prob"] \
            or set(cfg["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("the program runs SwiGLU experts on every layer "
                         "with renormalised top-k weights")
    return ModelConfig(
        name=cfg["name"], family="moe", num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["attention_bias"], attention="sliding_window",
        window_size=cfg["sliding_window"],
        attention_pattern=tuple(KINDS[k] for k in period(cfg["layer_types"])),
        rope_theta=float(full["rope_theta"]),
        full_rope_scaling=Yarn(
            factor=float(full["factor"]),
            original_max_position=int(full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        num_experts=cfg["num_experts_published"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts"], ffn_activation="swiglu",
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        max_position=cfg["max_position_embeddings"],
        dtype=cfg["compute_dtype"], source=cfg["source"])


def shapes(cfg: Dict) -> Dict:
    """Shape and fan-in of every weight, in the program's layout: the
    layers as one stack per position of the ``layer_types`` period."""
    L, d, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    n = len(period(cfg["layer_types"]))
    g = L // n
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    e, held, f = (cfg["num_experts_published"], cfg["num_experts"],
                  cfg["moe_intermediate_size"])
    layer = {
        "ln1": {"scale": ((g, d), None)},
        "attn": {"wq": ((g, d, hq), d), "wk": ((g, d, hkv), d),
                 "wv": ((g, d, hkv), d), "wo": ((g, hq, d), hq)},
        "ln2": {"scale": ((g, d), None)},
        "moe": {"router": ((g, d, e), d), "wg": ((g, held, d, f), d),
                "wu": ((g, held, d, f), d), "wd": ((g, held, f, d), f)},
    }
    return {"embed": {"table": ((V, d), d)}, "head": {"w": ((V, d), d)},
            "final_norm": {"scale": ((d,), None)},
            "layers": [layer] * n}


def make_params(cfg: Dict, seed: int):
    """The program's parameter tree from ``seed``, made on the device one
    array at a time (so that no array's float32 draw is live beside the
    others')."""
    import jax
    import jax.numpy as jnp

    def draw(key, shape, fan_in):
        if fan_in is None:      # a norm scale, float32 about 1
            return 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
        return (jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                            jnp.float32)
                / math.sqrt(fan_in)).astype(jnp.bfloat16)

    spec = shapes(cfg)
    leaves, tree = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple))
    key = jax_key(seed, 16)
    out = []
    for i, (shape, fan_in) in enumerate(leaves):
        fn = jax.jit(lambda k, shape=shape, fan_in=fan_in: draw(k, shape,
                                                                fan_in))
        out.append(fn(jax.random.fold_in(key, i)))
    return jax.tree.unflatten(tree, out)


def plain_tree(params) -> Dict:
    """The reference's view of the same arrays, under its own names: per
    position of the period, its layers' weights stacked."""
    layers = []
    for lay in params["layers"]:
        lp = dict(lay["attn"])
        lp.update(lay["moe"])
        lp["ln1"], lp["ln2"] = lay["ln1"]["scale"], lay["ln2"]["scale"]
        layers.append(lp)
    return {"embed": params["embed"]["table"], "head": params["head"]["w"],
            "norm": params["final_norm"]["scale"], "layers": layers}


def serve_model(cfg: Dict, seed: int, max_len: int
                ) -> Tuple[object, Callable[[], Dict]]:
    """(engine adapter, plain_weights) for ``cfg``, weights from ``seed``.
    ``plain_weights()`` returns the reference's tree over the same
    arrays."""
    from repro.serve import DenseServeModel

    # the program's configuration first: a program that cannot state this
    # model fails before any weight is made
    pcfg = program_config(cfg)
    params = make_params(cfg, seed)
    return (DenseServeModel(pcfg, params, max_len),
            lambda: plain_tree(params))
