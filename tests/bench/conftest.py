"""The benchmark's own modules (``bench/``) on the path of its tests, and a
tiny GPT-2 cell that runs on the CPU."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(ROOT, "bench")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"name": "gpt2-tiny", "family": "gpt2", "reference": "gpt2",
        "n_layer": 2, "n_embd": 64, "n_head": 4, "n_inner": 128,
        "n_positions": 64, "vocab_size": 256, "layer_norm_epsilon": 1e-5,
        "head_dim": 16, "compute_dtype": "bfloat16",
        "check": {"max_logit_gap": 0.1}}
TINY_MIX = {"job": "serve_job", "kind": "offline_backlog", "slots": 4,
            "max_len": 64, "requests_per_stream": 12,
            "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 4, "max": 24},
            "output": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                       "min": 2, "max": 40},
            "sampling": "greedy", "check_tokens": 300}


def tiny_ctx(cfg=None, mix=None, seed=2**33 + 5, seconds=0.3,
             control=False, bench=BENCH):
    """A serving cell's context at tiny size, without the device check,
    with the configuration's modules found by name under ``bench``."""
    import harness
    import run as R
    ctx = R.Ctx()
    ctx.cfg, ctx.mix = dict(cfg or TINY), dict(mix or TINY_MIX)
    ctx.chips = 1
    ctx.family = harness.load_module(bench, "families", ctx.cfg["family"])
    ctx.reference = harness.load_module(bench, "references",
                                        ctx.cfg["reference"])
    ctx.counts = harness.load_module(bench, "counts", ctx.cfg["reference"])
    ctx.job = harness.load_module(BENCH, ".", ctx.mix["job"])
    ctx.seed, ctx.seconds = seed, seconds
    ctx.trace, ctx.control = False, control
    ctx.t_start = time.time()
    return ctx


@pytest.fixture
def make_tiny_ctx():
    return tiny_ctx
