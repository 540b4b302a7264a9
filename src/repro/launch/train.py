"""Training launcher: the per-host entrypoint a Mirage-provisioned sub-job
runs on real hardware.

On a TPU pod each host calls ``jax.distributed.initialize()`` (from the
batch scheduler's env) and runs this module; in this container it runs
single-process on the local device. The loop is the chained-sub-job
protocol: resume from the newest checkpoint, train until the wall-clock
guard (or step budget) fires, checkpoint, exit 0 — the successor sub-job
(already queued by the provisioner) picks it up.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --steps 100 --wall-limit 3600 --ckpt-dir checkpoints/svc [--smoke]
"""
from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--max-steps", type=int, default=10**9)
    ap.add_argument("--wall-limit", type=float, default=None)
    ap.add_argument("--ckpt-dir", default="checkpoints/train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize() (multi-host pods)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.distributed:
        import jax
        jax.distributed.initialize()

    from repro.data import DataConfig, data_iterator
    from repro.models import registry, transformer
    from repro.train import ChainConfig, ChainedTrainer, OptimizerConfig

    cfg = registry.get_config(args.arch, smoke=args.smoke)
    ocfg = OptimizerConfig(lr=args.lr, warmup_steps=20,
                           total_steps=args.max_steps)
    chain = ChainConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                        wall_limit_s=args.wall_limit, max_steps=args.max_steps)
    dc = DataConfig(batch=args.batch, seq_len=args.seq)
    trainer = ChainedTrainer(cfg, ocfg, chain, data_iterator(cfg, dc),
                             num_microbatches=args.microbatches)
    if trainer.maybe_resume():
        print(f"[train] resumed at step {trainer.step}")
        trainer.data_iter = data_iterator(cfg, dc, start_step=trainer.step)
    n = transformer.param_count(trainer.params)
    print(f"[train] arch={args.arch} params={n:,} target_steps={args.steps}")
    info = trainer.run_subjob(args.steps)
    print(f"[train] exit: {info['reason']} at step {info['steps_done']} "
          f"(stragglers flagged: {info['stragglers']})")


if __name__ == "__main__":
    main()
