"""Operations and bytes that any implementation of GPT-2 must spend, from
shapes: the counts of the configurations whose ``reference`` is ``gpt2``.

The counts are the least work of the job, never what the program happens
to run: padding, recomputation and copies are not counted, so a program that
removes waste moves towards 100% of its roofline and never past it.

The serving job calls two functions of a counts module,
``decode_steps(cfg, positions)`` and ``prefill_flops(cfg, s)``, with the
configuration's plain dict (``bench/configs/<name>.json``; here with its
per-layer widths, ``weights.layer_widths``). Every architecture has its own
``bench/counts/<reference>.py`` with the two.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from weights import layer_widths

COMPUTE_BYTES = 2   # bfloat16, the configurations' compute type


def _layer_sizes(cfg: Dict) -> List[Tuple[int, int]]:
    """(heads * head_dim, n_inner) per layer."""
    return [(w["heads"] * cfg["head_dim"], w["n_inner"])
            for w in layer_widths(cfg)]


def matmul_flops_per_token(cfg: Dict) -> int:
    """Projection and MLP operations of one token through the stack."""
    d = cfg["n_embd"]
    return sum(8 * d * hd + 4 * d * f for hd, f in _layer_sizes(cfg))


def attn_flops(cfg: Dict, keys: int) -> int:
    """Scores and weighted sum of one query over ``keys`` positions."""
    return sum(4 * hd * keys for hd, _ in _layer_sizes(cfg))


def head_flops(cfg: Dict) -> int:
    """LM head of one position."""
    return 2 * cfg["n_embd"] * cfg["vocab_size"]


def prefill_flops(cfg: Dict, s: int) -> int:
    """A prompt of ``s`` tokens, causal, with the LM head at its last
    position only (the one whose logits are used)."""
    return (s * matmul_flops_per_token(cfg)
            + attn_flops(cfg, 1) * s * (s + 1) // 2 + head_flops(cfg))


def weight_elems(cfg: Dict) -> int:
    """Every weight of the model: the token table (read whole by the LM
    head), and per layer its kept projections, MLP, biases and norms."""
    d = cfg["n_embd"]
    n = cfg["vocab_size"] * d + 2 * d
    for hd, f in _layer_sizes(cfg):
        if hd:
            n += 4 * d * hd + 2 * d
        if f:
            n += 2 * d * f + f + d + 2 * d
    return n


def kv_bytes_per_position(cfg: Dict) -> int:
    """K and V of one position over every layer that keeps attention."""
    return sum(2 * hd * COMPUTE_BYTES for hd, _ in _layer_sizes(cfg))


def decode_steps(cfg: Dict, positions):
    """(operations, bytes) of batched decode steps, one entry per row of
    ``positions`` (steps, slots): the position each active slot feeds at
    that step, -1 where a slot is empty. A token at position p attends
    p + 1 keys in every layer, so a step needs only its active slots ``n``
    and the sum ``pos_sum`` of their positions: the weights read once at
    compute-type bytes plus each active slot's position row, the K/V of
    the positions each slot holds, and one new K/V position written per
    slot."""
    positions = np.asarray(positions, np.int64)
    active = positions >= 0
    n = active.sum(1)
    pos_sum = np.where(active, positions, 0).sum(1)
    kv = kv_bytes_per_position(cfg)
    flops = (n * (matmul_flops_per_token(cfg) + head_flops(cfg))
             + attn_flops(cfg, 1) * (pos_sum + n))
    nbytes = (weight_elems(cfg) * COMPUTE_BYTES
              + n * cfg["n_embd"] * COMPUTE_BYTES + kv * (pos_sum + n))
    return flops, nbytes

