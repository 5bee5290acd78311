"""mellum2-12b-a2.5b [moe] — 3 window : 1 full attention, 64 experts top-8
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json].

Window layers use plain RoPE at theta 500,000, full layers the same theta
with YaRN (factor 16 over 8,192 positions). The MTP head is left out: the
config declares no MTP module and greedy decoding never uses it.
"""
from .base import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b", family="moe", num_layers=28, d_model=2304,
    num_heads=32, num_kv_heads=4, head_dim=128, d_ff=896, vocab_size=98304,
    attention="sliding_window", window_size=1024,
    attention_pattern=("sliding_window",) * 3 + ("full",),
    rope_theta=500000.0,
    full_rope_scaling=Yarn(factor=16.0, original_max_position=8192,
                           beta_fast=32.0, beta_slow=1.0,
                           attention_factor=1.2772588722239782),
    num_experts=64, num_experts_per_tok=8, ffn_activation="swiglu",
    norm="rmsnorm", norm_eps=1e-6, tie_embeddings=False,
    max_position=131072,
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
)
