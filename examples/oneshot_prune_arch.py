"""One-shot ZipLM pruning of any assigned architecture (reduced config):
demonstrates the generalized structure registry (GQA groups, SSD heads,
MoE experts) and the per-family latency tables.

  PYTHONPATH=src python examples/oneshot_prune_arch.py --arch mamba2-2.7b
  PYTHONPATH=src python examples/oneshot_prune_arch.py --arch dbrx-132b
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import ASSIGNED, smoke_config
from repro.core.oneshot import oneshot_prune
from repro.core.shrink import shrink
from repro.core.structures import registry
from repro.data import calibration_batches
from repro.models import model_init
from repro.runtime.costmodel import InferenceEnv
from repro.runtime.device import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b", choices=ASSIGNED)
    ap.add_argument("--target", type=float, default=2.0)
    args = ap.parse_args()

    cfg = smoke_config(args.arch).replace(dtype="float32")
    params, _ = model_init(cfg, jax.random.key(0))
    mods = registry(cfg)
    kinds = {}
    for m in mods:
        kinds[m.kind] = kinds.get(m.kind, 0) + 1
    print(f"arch={args.arch} (reduced)  prunable modules: {kinds}")

    env = InferenceEnv(batch=8, seq=128, mode="prefill")
    calib = calibration_batches(cfg, 16, 64, batch=8)
    res = oneshot_prune(cfg, params, calib, env, targets=[args.target],
                        search_steps=25)
    v = res.variants[args.target]
    print(f"target {args.target}x -> achieved {v.speedup:.2f}x  "
          f"loss {res.dense_loss:.4f} -> {v.calib_loss:.4f}")
    pm = shrink(cfg, v.params, res.db, v.assignment)
    for i, l in enumerate(pm.layers):
        desc = []
        if l.kv_groups:
            desc.append(f"kv_groups={l.kv_groups}")
        if l.ssm_heads:
            desc.append(f"ssd_heads={l.ssm_heads}")
        if l.d_ff:
            desc.append(f"d_ff={l.d_ff}")
        if l.expert_ff:
            desc.append(f"experts={l.expert_ff}")
        print(f"  layer {i}: " + (", ".join(desc) or "fully dropped"))


if __name__ == "__main__":
    main()
