"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
      --steps 100 --batch 8 --seq 256 --smoke

On a real pod: drop --smoke, point --ckpt-dir at durable storage, and run
one process per host (jax.distributed.initialize is called when
JAX_COORDINATOR is set).
"""
import argparse
import os
import sys

import jax


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory; defaults to a fresh "
                         "tempfile.mkdtemp so concurrent runs can't "
                         "collide")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8_ef"])
    args = ap.parse_args(argv)

    from ..runtime.device import use_compile_cache
    use_compile_cache()
    if os.environ.get("JAX_COORDINATOR"):
        jax.distributed.initialize()  # multi-host pod entry

    from ..configs import get_config, smoke_config
    from ..configs.base import TrainConfig
    from ..data import synthetic_stream
    from ..distributed.sharding import make_mesh, mesh_config_for
    from ..models import model_init
    from ..train.trainer import Trainer

    if args.ckpt_dir is None:
        import tempfile
        args.ckpt_dir = tempfile.mkdtemp(prefix="repro_train_")

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    print(f"[train] {cfg.name}: {cfg.num_params()/1e6:.1f}M params, "
          f"{jax.device_count()} devices")
    params, specs = model_init(cfg, jax.random.key(0))
    tcfg = TrainConfig(learning_rate=args.lr, warmup_steps=10,
                       total_steps=args.steps,
                       microbatches=args.microbatches,
                       grad_compression=args.grad_compression)
    # multi-device: data-parallel mesh -> the trainer's jit_train_step
    # path (FSDP shardings; int8_ef compresses the DP all-reduce). On one
    # device int8_ef has nothing to compress and the Trainer raises.
    mesh = None
    if jax.device_count() > 1:
        mesh = make_mesh((jax.device_count(),), ("data",))
    trainer = Trainer(cfg, tcfg, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every, mesh=mesh,
                      mc=mesh_config_for(mesh) if mesh else None,
                      specs=specs if mesh else None,
                      install_signal_handler=True)
    state = trainer.init_or_restore(params)
    data = synthetic_stream(cfg, args.batch, args.seq,
                            start_step=int(state.step))
    state = trainer.fit(state, data, steps=args.steps)
    print(f"[train] done at step {int(state.step)}; "
          f"final loss {trainer.metrics_log[-1]['loss']:.4f}; "
          f"stragglers flagged: {trainer.watchdog.flagged}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
