"""Analytic TPU-v5e roofline cost model.

Replaces the paper's on-GPU latency measurements when targeting TPU from a
CPU-only container (DESIGN.md §3). Per-module time =
``max(FLOPs / (peak * MXU_eff), bytes / HBM_bw) + op_overhead`` with MXU
efficiency modelling (8,128)x(128,128) systolic tiling — small/off-tile
matrices waste the MXU exactly like they under-utilize A100 tensor cores
(paper Table 3), which is what makes inference-awareness matter.
"""
from __future__ import annotations

import math
from dataclasses import dataclass


@dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float       # bf16 FLOP/s per chip
    hbm_bw: float           # bytes/s per chip
    ici_bw: float           # bytes/s per link
    hbm_bytes: float
    op_overhead: float      # seconds per fused op (dispatch/latency floor)


# published per-chip peaks (Google Cloud documentation, "TPU v5e")
TPU_V5E = HardwareSpec("tpu_v5e", peak_flops=197e12, hbm_bw=819e9,
                       ici_bw=50e9, hbm_bytes=16e9, op_overhead=2e-6)

# peaks keyed by ``jax.Device.device_kind`` as JAX reports it
HARDWARE = {"TPU v5 lite": TPU_V5E}


def hardware_for(device_kind: str) -> HardwareSpec:
    """Peak table entry of a device kind; a kind not in ``HARDWARE`` is an
    error, never a default."""
    try:
        return HARDWARE[device_kind]
    except KeyError:
        raise KeyError(f"no peak entry for device kind {device_kind!r}; "
                       f"known kinds: {sorted(HARDWARE)}") from None


@dataclass(frozen=True)
class InferenceEnv:
    """The paper's 'inference specification': batch, sequence, regime, device."""
    batch: int
    seq: int
    mode: str = "prefill"          # prefill | decode | train
    hw: HardwareSpec = TPU_V5E
    tp: int = 1                    # tensor-parallel degree (chips)

    @property
    def tokens(self) -> int:
        return self.batch * (1 if self.mode == "decode" else self.seq)

    def replace(self, **kw) -> "InferenceEnv":
        import dataclasses
        return dataclasses.replace(self, **kw)


def _rup(x: int, m: int) -> int:
    return max(m, ((x + m - 1) // m) * m)


def matmul_time(env: InferenceEnv, m: int, k: int, n: int,
                bytes_per_el: int = 2) -> float:
    """Time of an (m,k)x(k,n) matmul on one chip of the env."""
    if m == 0 or k == 0 or n == 0:
        return 0.0
    hw = env.hw
    flops_eff = 2.0 * _rup(m, 8) * _rup(k, 128) * _rup(n, 128)
    t_c = flops_eff / hw.peak_flops
    bytes_ = (m * k + k * n + m * n) * bytes_per_el
    t_m = bytes_ / hw.hbm_bw
    return max(t_c, t_m) + hw.op_overhead


def allreduce_time(env: InferenceEnv, bytes_: float) -> float:
    if env.tp <= 1:
        return 0.0
    return 2.0 * bytes_ * (env.tp - 1) / env.tp / env.hw.ici_bw \
        + env.hw.op_overhead


def attn_time(cfg, env: InferenceEnv, kv_groups: int) -> float:
    """Attention block with `kv_groups` of num_kv_heads groups remaining."""
    if kv_groups == 0:
        return 0.0
    dh = cfg.resolved_head_dim
    hq = kv_groups * cfg.q_per_kv
    hkv = kv_groups
    d = cfg.d_model
    t_tok = env.tokens
    tp = env.tp
    # projections (TP-sharded over heads)
    t = matmul_time(env, t_tok, d, math.ceil(hq * dh / tp))
    t += 2 * matmul_time(env, t_tok, d, math.ceil(hkv * dh / tp))
    t += matmul_time(env, t_tok, math.ceil(hq * dh / tp), d)
    # attention einsums
    hq_loc = max(1, hq // tp)
    if env.mode == "decode":
        # memory-bound KV read + small matmuls
        kv_bytes = 2 * env.seq * (hkv / min(tp, max(hkv, 1))) * dh \
            * env.batch * 2
        t += max(4.0 * env.batch * hq_loc * env.seq * dh / env.hw.peak_flops,
                 kv_bytes / env.hw.hbm_bw) + 2 * env.hw.op_overhead
    else:
        s = env.seq
        ctx = min(s, cfg.window_size) if cfg.attention == "sliding_window" \
            else s
        flops = 4.0 * env.batch * hq_loc * s * ctx * dh
        t += flops / env.hw.peak_flops + 2 * env.hw.op_overhead
    t += allreduce_time(env, t_tok * d * 2)
    return t


def ffn_time(cfg, env: InferenceEnv, f_live: int,
             tokens: float = None) -> float:
    if f_live == 0:
        return 0.0
    d = cfg.d_model
    t_tok = tokens if tokens is not None else env.tokens
    n_mat = 3 if cfg.ffn_activation == "swiglu" else 2
    f_loc = math.ceil(f_live / env.tp)
    t = (n_mat - 1) * matmul_time(env, int(t_tok), d, f_loc)
    t += matmul_time(env, int(t_tok), f_loc, d)
    t += allreduce_time(env, t_tok * d * 2)
    return t


def moe_expert_time(cfg, env: InferenceEnv, f_live: int) -> float:
    """One expert's FFN at the expected per-expert token count (EP=tp)."""
    c = env.tokens * cfg.num_experts_per_tok / cfg.num_experts * 1.25
    return ffn_time(cfg.replace(num_experts=0), env.replace(tp=1),
                    f_live, tokens=max(1.0, c))


def ssm_time(cfg, env: InferenceEnv, heads: int) -> float:
    if heads == 0:
        return 0.0
    d = cfg.d_model
    hp = cfg.ssm_head_dim
    di = heads * hp
    n = cfg.ssm_state
    t_tok = env.tokens
    t = matmul_time(env, t_tok, d, math.ceil((2 * di + 2 * n + heads) / env.tp))
    t += matmul_time(env, t_tok, math.ceil(di / env.tp), d)
    if env.mode == "decode":
        state_bytes = env.batch * heads * hp * n * 4 * 2
        t += state_bytes / env.hw.hbm_bw + env.hw.op_overhead
    else:
        q = cfg.ssm_chunk
        flops = 2.0 * t_tok * q * (heads / env.tp) * (hp + n) \
            + 4.0 * t_tok * (heads / env.tp) * hp * n
        t += flops / env.hw.peak_flops + 4 * env.hw.op_overhead
    t += allreduce_time(env, t_tok * d * 2)
    return t


def kv_cache_bytes(cfg, kv_heads_plan, batch: int, max_len: int,
                   bytes_per_el: int = 2) -> int:
    """Total KV-cache bytes for a per-layer KV-head plan (K + V buffers).

    ``kv_heads_plan`` is ``shrink.kv_cache_plan``'s output: one KV-head
    count per layer, 0 for layers whose attention is pruned away (or
    dropped whole) — those allocate nothing.  This is the serving
    engine's currency: GQA-aware KV-head pruning is what makes it
    shrink.
    """
    dh = cfg.resolved_head_dim
    return int(sum(2 * batch * max_len * h * dh * bytes_per_el
                   for h in kv_heads_plan))


def base_time(cfg, env: InferenceEnv) -> float:
    """Unprunable remainder: embeddings, norms, logits head."""
    d, v = cfg.d_model, cfg.vocab_size
    t_tok = env.tokens
    t = matmul_time(env, t_tok, d, math.ceil(v / env.tp))  # logits
    t += allreduce_time(env, t_tok * 4)                    # softmax combine
    norm_bytes = 2 * cfg.num_layers * t_tok * d * 2 * 2
    t += norm_bytes / env.hw.hbm_bw \
        + 2 * cfg.num_layers * env.hw.op_overhead
    return t
