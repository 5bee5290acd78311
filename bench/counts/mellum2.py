"""Operations and bytes that any implementation of a Mellum2 decoder must
spend on one rank of expert parallelism, from shapes: the counts of the
configurations whose ``reference`` is ``mellum2``.

The counts are the least work of the job, never what the program happens
to run (padding, the whole cache behind the mask, recomputation and copies
are not counted):

- weights are read once a step at bfloat16 bytes, all held experts
  included, and the norm scales at float32; under uniform routing a held
  expert gets no token of a 32-slot step with probability (7/8)^32, about
  1.4%, so reading all of them is the least for nearly every step;
- a full-attention layer reads the K/V of the ``p + 1`` positions a slot at
  position ``p`` attends, a window layer those of ``min(p + 1, W)``; each
  layer writes one K/V row per active slot;
- expert operations are charged at their expectation under uniform
  routing, ``top_k * held / num_experts`` SwiGLU evaluations a token (the
  program's ``serve.route`` span gives the number that ran);
- the LM head runs at the last prompt position only.

The serving job calls ``decode_steps(cfg, positions)`` and
``prefill_flops(cfg, s)``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

COMPUTE_BYTES = 2   # bfloat16, the configuration's weights and compute
NORM_BYTES = 4      # float32 norm scales


def _dims(cfg: Dict):
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d, hq, hkv


def layer_counts(cfg: Dict) -> Tuple[int, int]:
    """(window layers, full layers)."""
    types = list(cfg["layer_types"])
    return types.count("sliding_attention"), types.count("full_attention")


def attn_weight_elems(cfg: Dict) -> int:
    d, hq, hkv = _dims(cfg)
    return 2 * d * hq + 2 * d * hkv


def router_elems(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts_published"]


def expert_elems(cfg: Dict) -> int:
    """One SwiGLU expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expected_experts(cfg: Dict) -> float:
    """Held-expert evaluations a token under uniform routing."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["num_experts_published"])


def weight_bytes(cfg: Dict) -> int:
    """Every weight a decode step reads: per layer its projections, router,
    held experts and two norms, then the final norm and the LM head."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    per_layer = (attn_weight_elems(cfg) + router_elems(cfg)
                 + cfg["num_experts"] * expert_elems(cfg)) * COMPUTE_BYTES
    return (L * (per_layer + 2 * d * NORM_BYTES) + d * NORM_BYTES
            + cfg["vocab_size"] * d * COMPUTE_BYTES)


def matmul_flops_per_token(cfg: Dict) -> float:
    """Projections, router and the expected held experts of one token
    through the stack."""
    per_layer = 2 * (attn_weight_elems(cfg) + router_elems(cfg)
                     + expected_experts(cfg) * expert_elems(cfg))
    return cfg["num_hidden_layers"] * per_layer


def head_flops(cfg: Dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def kv_bytes_per_position(cfg: Dict) -> int:
    """K and V of one position of one layer."""
    return 2 * _dims(cfg)[2] * COMPUTE_BYTES


def _keys(cfg: Dict, positions):
    """Per step (rows of ``positions``): active slots, and the keys their
    queries attend in one full layer and in one window layer."""
    positions = np.asarray(positions, np.int64)
    active = positions >= 0
    held = np.where(active, positions + 1, 0)
    return (active.sum(1), held.sum(1),
            np.minimum(held, cfg["sliding_window"]).sum(1))


def decode_steps(cfg: Dict, positions):
    """(operations, bytes) of batched decode steps, one entry per row of
    ``positions`` (steps, slots): the position each active slot feeds at
    that step, -1 where a slot is empty."""
    n, full_keys, window_keys = _keys(cfg, positions)
    nw, nf = layer_counts(cfg)
    hq, kv = _dims(cfg)[1], kv_bytes_per_position(cfg)
    keys = nf * full_keys + nw * window_keys
    flops = (n * (matmul_flops_per_token(cfg) + head_flops(cfg))
             + 4 * hq * keys)
    nbytes = (weight_bytes(cfg) + n * cfg["hidden_size"] * COMPUTE_BYTES
              + kv * (keys + n * (nw + nf)))
    return flops, nbytes


def prefill_flops(cfg: Dict, s: int) -> float:
    """A prompt of ``s`` tokens, causal (window layers attend at most W
    keys), with the LM head at its last position only."""
    nw, nf = layer_counts(cfg)
    hq = _dims(cfg)[1]
    w = cfg["sliding_window"]
    full_keys = s * (s + 1) // 2
    i = np.arange(1, s + 1)
    window_keys = int(np.minimum(i, w).sum())
    return (s * matmul_flops_per_token(cfg)
            + 4 * hq * (nf * full_keys + nw * window_keys) + head_flops(cfg))
