"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch mamba2-780m \
      --steps 50 [--reduced] [--batch 8 --seq 128] [--ckpt ck.npz]

Trains the architecture at its published widths and depth on the default
device (one accelerator chip); ``--reduced`` swaps in the 2-layer,
d_model-256 smoke variant of the same family, which is what fits a CPU
run. Models too large for one chip are exercised through
``repro.launch.dryrun`` (lower + compile on a placeholder mesh).
"""
from __future__ import annotations

import argparse

from repro.configs import get_config, reduced as reduce_cfg
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.adamw import AdamWConfig
from repro.runtime.train_loop import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="2-layer smoke variant (default: published size)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    print(f"training {cfg.name} ({cfg.arch_type}), {cfg.num_layers}L "
          f"d={cfg.d_model}, batch={args.batch} seq={args.seq}")
    trainer = Trainer(cfg, args.batch, args.seq,
                      AdamWConfig(lr=args.lr, total_steps=args.steps),
                      ckpt_path=args.ckpt)
    trainer.restore()
    report = trainer.train(args.steps, log_every=10,
                           ckpt_every=args.ckpt_every)
    print(f"done: loss {report.losses[0]:.4f} -> {report.final_loss:.4f}, "
          f"{report.mean_step_time*1e3:.0f} ms/step")


if __name__ == "__main__":
    main()
