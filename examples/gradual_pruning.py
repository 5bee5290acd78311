"""End-to-end driver: train a ~100M-parameter model for a few hundred steps,
then run the gradual ZipLM pipeline (prune -> distill-finetune -> export)
producing a family of compressed models.

This is the paper's §4.1 workflow at CPU-feasible scale; scale knobs are
CLI flags. With --full it uses a ~100M model and 200 train steps (slow on
one CPU core); default is a fast reduced run.

  PYTHONPATH=src python examples/gradual_pruning.py [--full]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import GPT2_SMALL
from repro.configs.base import TrainConfig
from repro.core.pipeline import gradual_prune
from repro.data import calibration_batches, synthetic_stream
from repro.models import model_init
from repro.runtime.costmodel import InferenceEnv
from repro.runtime.device import use_compile_cache
from repro.train.trainer import Trainer
from repro.train.train_step import make_train_state


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="~100M params, 200 pretrain steps")
    ap.add_argument("--ckpt", default="/tmp/ziplm_example")
    args = ap.parse_args()

    if args.full:
        cfg = GPT2_SMALL.replace(name="gpt2-100m", num_layers=8,
                                 d_model=512, d_ff=2048, num_heads=8,
                                 num_kv_heads=8, vocab_size=50257)
        pretrain_steps, ft_steps, batch, seq = 200, 60, 8, 256
    else:
        cfg = GPT2_SMALL.replace(name="gpt2-tiny", num_layers=4, d_model=96,
                                 d_ff=384, num_heads=6, num_kv_heads=6,
                                 head_dim=16, vocab_size=384,
                                 dtype="float32")
        pretrain_steps, ft_steps, batch, seq = 120, 20, 16, 64
    print(f"model: {cfg.name} params={cfg.num_params()/1e6:.1f}M")

    # pretrain with the fault-tolerant trainer (checkpoints + watchdog)
    params, _ = model_init(cfg, jax.random.key(0))
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=10,
                       total_steps=pretrain_steps)
    trainer = Trainer(cfg, tcfg, ckpt_dir=os.path.join(args.ckpt, "dense"),
                      ckpt_every=50)
    state = trainer.init_or_restore(params)
    data = synthetic_stream(cfg, batch, seq, seed=7,
                            start_step=int(state.step))
    state = trainer.fit(state, data, steps=pretrain_steps)
    print(f"pretrained to step {int(state.step)}, "
          f"loss {trainer.metrics_log[-1]['loss']:.4f}")

    env = InferenceEnv(batch=16, seq=128, mode="prefill")
    calib = calibration_batches(cfg, 32, seq, batch=8)
    ft_cfg = TrainConfig(learning_rate=5e-4, warmup_steps=2,
                         total_steps=ft_steps, distill_logit=1.0,
                         distill_token=0.5)
    # a step-indexed data factory (not a bare iterator) makes the family
    # run resumable bit-exactly: re-running this script after a kill picks
    # up at the interrupted (target, stage) instead of starting over
    data = lambda step: synthetic_stream(cfg, batch, seq, seed=99,
                                         start_step=step)
    variants = gradual_prune(cfg, state.params, env, [1.5, 2.0, 3.0],
                             data, calib, tcfg=ft_cfg,
                             finetune_steps=ft_steps,
                             search_steps=25, search_pop=16, seed=0,
                             ckpt_dir=args.ckpt, verbose=True)
    print("\nfamily:")
    for v in variants:
        print(f"  {v.target}x -> {v.achieved:.2f}x  "
              f"loss {v.loss_before_ft:.4f}->{v.loss_after_ft:.4f}  "
              f"stack {v.pruned.encoder_params()/1e6:.2f}M params")


if __name__ == "__main__":
    main()
