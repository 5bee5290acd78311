"""The family-build job: ZipLM compression of a GPT-2 configuration.

  python3 bench/family_build.py --config gpt2-small --seed 0 --out FILE

Runs ``repro.core.oneshot.oneshot_prune`` with its own search defaults
(200 SPDY steps, population 16) for targets 1.5x and 2.0x, then
``repro.core.shrink.shrink`` of each member, on:

* the benchmark's seeded weights (``bench/weights.py``);
* 512 calibration sequences of 128 Markov-Zipf tokens (``bench/traffic.py``)
  in batches of 8, the low end of the paper's 512-2048;
* the (batch 16, seq 128) prefill environment, priced by the ``measure``
  latency backend with ``grid_subsample`` 8 and no latency cache;
* SPDY evaluation on the first calibration batch.

It writes each member's per-layer widths (heads kept, MLP width kept) with
its table speedup: ``bench/configs/gpt2-small-zip2x.json`` holds the 2.0x
member of the run at seed 0. Fails unless JAX finds a TPU, and when any
robustness breaker opened or a demotion was counted.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(BENCH, "families"),
                os.path.join(ROOT, "src")]

TARGETS = (1.5, 2.0)
CALIB_SEQS, CALIB_LEN, CALIB_BATCH = 512, 128, 8


def calibration(cfg, seed: int):
    import jax.numpy as jnp
    import numpy as np

    import traffic
    rng = np.random.default_rng(traffic.seed_words(seed, 3))
    rows = traffic.markov_zipf(cfg["vocab_size"],
                               np.full(CALIB_SEQS, CALIB_LEN), rng)
    toks = np.stack(rows)
    return [{"tokens": jnp.asarray(toks[i:i + CALIB_BATCH])}
            for i in range(0, CALIB_SEQS, CALIB_BATCH)]


def build(cfg, seed: int, search_steps: int = 200):
    """(OneShotResult, {target: PrunedModel})."""
    from repro.core.oneshot import oneshot_prune
    from repro.core.shrink import shrink
    from repro.runtime.costmodel import InferenceEnv

    import gpt2
    import weights
    pcfg = gpt2.program_config(cfg)
    params = gpt2.dense_params(weights.make_weights(cfg, seed))
    calib = calibration(cfg, seed)
    env = InferenceEnv(batch=16, seq=128, mode="prefill")
    res = oneshot_prune(pcfg, params, calib, env, TARGETS,
                        latency_backend="measure",
                        latency_kw={"grid_subsample": 8},
                        search_steps=search_steps, search_pop=16,
                        eval_batches=calib[:1], seed=seed)
    members = {t: shrink(pcfg, v.params, res.db, v.assignment)
               for t, v in res.variants.items()}
    return res, members


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="gpt2-small")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import harness
    dev = harness.require_device(1, harness.load_peaks(BENCH))
    harness.use_compile_cache(ROOT)
    cfg = harness.load_json(BENCH, "configs", args.config)

    from repro.robustness.report import report_scope
    t0 = time.perf_counter()
    with report_scope() as rep:
        res, members = build(cfg, args.seed)
    wall = time.perf_counter() - t0
    d = rep.as_dict()
    out = {"config": args.config, "seed": args.seed, "device": dev,
           "wall_s": wall, "dense_runtime_s": res.dense_runtime,
           "robustness": d, "members": {}}
    for t, pm in members.items():
        v = res.variants[t]
        out["members"][str(t)] = {
            "table_speedup": v.speedup, "calib_loss": v.calib_loss,
            "stack_params": pm.encoder_params(),
            "layers": [{"heads": l.kv_groups, "n_inner": l.d_ff}
                       for l in pm.layers]}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    if d["breakers_open"] or d["counts"]["demotions"]:
        print("family_build: a fallback hid the device", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
