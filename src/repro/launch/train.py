"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs a real (reduced or full) training job on whatever devices exist,
with the tuned kernel deployment installed, checkpoint/auto-resume, and the
fault-tolerance runtime active.  On this CPU container the reduced configs
train for real; the full configs are exercised via the dry-run.
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp

from repro.configs import registry
from repro.core.runtime import default_runtime
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import build_model
from repro.optim import adamw
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=sorted(registry.ARCHS))
    ap.add_argument("--reduced", action="store_true", help="tiny same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--deployment", default=None, help="tuned kernel deployment JSON")
    ap.add_argument("--fleet", action="store_true",
                    help="multi-host: init jax.distributed from the scheduler env")
    args = ap.parse_args(argv)
    use_compile_cache()

    topo = None
    if args.fleet:
        from repro.launch.fleet import initialize

        topo = initialize()
        print(f"fleet: process {topo.process_id}/{topo.num_processes} "
              f"(coordinator {topo.coordinator})")

    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    if args.deployment:
        from repro.core.dispatch import Deployment

        # Training dispatch runs on the process default runtime (the trainer
        # owns every thread here, so an isolated handle buys nothing).
        default_runtime().install(Deployment.load(args.deployment))
        print(f"installed kernel deployment from {args.deployment}")

    model = build_model(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    data = DataConfig(global_batch=args.batch, seq_len=args.seq)
    if topo is not None:
        from repro.launch.fleet import fleet_data_config

        data = fleet_data_config(data, topo)
    opt = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps)
    tcfg = TrainerConfig(
        total_steps=args.steps,
        ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir,
        num_microbatches=args.microbatches,
    )
    trainer = Trainer(model, cfg, data, opt, tcfg)
    step, _params, _opt, metrics = trainer.train()
    print(f"finished at step {step}: loss={float(metrics.get('loss', float('nan'))):.4f}")


if __name__ == "__main__":
    main()
