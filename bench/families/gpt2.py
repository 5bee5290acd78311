"""The program's side of a GPT-2 configuration: its ``ModelConfig`` and the
engine adapter that serves the benchmark's weights.

A configuration without a ``layers`` list is the dense model, served by the
scanned runtime (``repro.serve.DenseServeModel``); one with per-layer widths
is a ZipLM member, served by the per-layer runtime
(``repro.serve.PrunedServeModel``).
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import weights as W


def program_config(cfg: Dict):
    from repro.configs import GPT2_SMALL
    return GPT2_SMALL.replace(
        name=cfg["name"], num_layers=cfg["n_layer"], d_model=cfg["n_embd"],
        num_heads=cfg["n_head"], num_kv_heads=cfg["n_head"],
        d_ff=cfg["n_inner"], vocab_size=cfg["vocab_size"],
        max_position=cfg["n_positions"], norm_eps=cfg["layer_norm_epsilon"],
        dtype=cfg["compute_dtype"])


def dense_params(w) -> Dict:
    """The program's stacked dense tree over the plain weights ``w``; the
    plain per-layer list is dropped, so that one copy stays on the device."""
    params = W.dense_params(w)
    w["layers"] = None
    return params


def plain_from_dense(params, n_layer: int) -> Dict:
    """The plain tree again, sliced from the stacked one."""
    lay = params["layers"]
    layers = []
    for i in range(n_layer):
        lp = {k: lay["attn"][k][i] for k in ("wq", "wk", "wv", "wo")}
        lp.update({k: lay["ffn"][k][i] for k in ("wi", "bi", "wd", "bd")})
        lp["ln1"] = {"g": lay["ln1"]["scale"][i], "b": lay["ln1"]["bias"][i]}
        lp["ln2"] = {"g": lay["ln2"]["scale"][i], "b": lay["ln2"]["bias"][i]}
        layers.append(lp)
    fn = params["final_norm"]
    return {"embed": params["embed"]["table"], "pos": params["embed"]["pos"],
            "lnf": {"g": fn["scale"], "b": fn["bias"]}, "layers": layers}


def serve_model(cfg: Dict, seed: int, max_len: int
                ) -> Tuple[object, Callable[[], Dict]]:
    """(engine adapter, plain_weights) for ``cfg``, weights from ``seed``.
    ``plain_weights()`` returns the plain tree for the reference; call it
    after the adapter is released."""
    import jax

    from repro.serve import DenseServeModel, PrunedServeModel

    pcfg = program_config(cfg)
    w = W.make_weights(cfg, seed)
    if "layers" not in cfg:
        params = dense_params(w)
        unstack = jax.jit(lambda p: plain_from_dense(p, cfg["n_layer"]))
        return DenseServeModel(pcfg, params, max_len), lambda: unstack(params)
    pm = W.pruned_model(pcfg, w, W.layer_widths(cfg))
    return PrunedServeModel(pm, max_len), lambda: w
