"""Serve a dense vs ZipLM-pruned model with batched requests: prefill +
greedy decode, measuring wall-clock per generated token on this device
(the paper's 'pruning for latency' story, §4.2).

  PYTHONPATH=src python examples/serve_pruned.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs import GPT2_SMALL
from repro.configs.base import TrainConfig
from repro.core.oneshot import oneshot_prune
from repro.data import calibration_batches, synthetic_stream
from repro.models import generate, model_init, serve_prefill, serve_step
from repro.runtime.costmodel import InferenceEnv
from repro.runtime.device import use_compile_cache
from repro.train.train_step import make_train_state, make_train_step


def main():
    use_compile_cache()
    cfg = GPT2_SMALL.replace(name="gpt2-tiny", num_layers=4, d_model=96,
                             d_ff=384, num_heads=6, num_kv_heads=6,
                             head_dim=16, vocab_size=384, dtype="float32")
    params, _ = model_init(cfg, jax.random.key(0))
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=10, total_steps=120)
    step = jax.jit(make_train_step(cfg, tcfg))
    state = make_train_state(cfg, params, tcfg)
    data = synthetic_stream(cfg, 16, 64, seed=7)
    for _ in range(120):
        state, _ = step(state, next(data))
    params = state.params

    # prune for the *latency* environment (batch=1 decode)
    env = InferenceEnv(batch=1, seq=64, mode="decode")
    calib = calibration_batches(cfg, 16, 64, batch=8)
    res = oneshot_prune(cfg, params, calib, env, targets=[2.0],
                        search_steps=30)
    pruned = res.variants[2.0]

    prompts = next(synthetic_stream(cfg, 4, 24))["tokens"]

    def bench(p, label, steps=16):
        # Time prefill and decode SEPARATELY and warm: one warm generate
        # compiles both paths, then each phase is measured on its own —
        # never (prefill + decode wall) / decode steps.
        max_len = prompts.shape[1] + steps
        out = generate(cfg, p, prompts, steps=steps)  # reference sample
        prefill_fn = jax.jit(
            lambda toks: serve_prefill(cfg, p, {"tokens": toks}, max_len))
        step_fn = jax.jit(lambda c, t: serve_step(cfg, p, c, t))

        jax.block_until_ready(prefill_fn(prompts)[0])  # compile prefill
        t0 = time.perf_counter()
        logits, cache = prefill_fn(prompts)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        jax.block_until_ready(tok)
        prefill_ms = (time.perf_counter() - t0) * 1e3

        step_fn(cache, tok)  # compile decode before timing it
        t0 = time.perf_counter()
        for _ in range(steps - 1):
            logits, cache = step_fn(cache, tok)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        jax.block_until_ready(tok)
        decode_ms = (time.perf_counter() - t0) * 1e3 / (steps - 1)
        print(f"{label:8s} prefill {prefill_ms:7.2f} ms  "
              f"decode {decode_ms:7.2f} ms/token  sample: "
              f"{out[0, :8].tolist()}")
        return decode_ms

    print("batched serving (4 requests, prefill 24 + 16 new tokens):")
    t_dense = bench(params, "dense")
    t_pruned = bench(pruned.params, "pruned")
    print(f"masked-model decode speedup {t_dense / t_pruned:.2f}x "
          f"(guaranteed-by-table {pruned.speedup:.2f}x; "
          f"shrunk execution adds the rest — see bench table8)")


if __name__ == "__main__":
    main()
