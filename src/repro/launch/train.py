"""Training launcher: ``python -m repro.launch.train --arch granite-3-2b``.

Runs a real (CPU-feasible) training job on the smoke config by default, or
the full config when ``--full`` is given (requires the matching hardware).
Wires the complete production path: deterministic sharded data, sharded
train step, checkpoints, straggler monitor, resume.
"""

from __future__ import annotations

import argparse
import logging


from repro.configs import get_config
from repro.core.envknobs import configure_compile_cache
from repro.core.evaluators import on_tpu
from repro.core.profiles import attached_profile
from repro.data import DataConfig
from repro.models.model import RunConfig
from repro.optim import adamw
from repro.train import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (needs real TPUs)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--remat", default="none",
                    choices=["none", "full", "dots"])
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    configure_compile_cache()
    if on_tpu():                  # an unknown chip raises before training
        logging.info("device profile: %s", attached_profile().name)
    cfg = get_config(args.arch, smoke=not args.full)
    data_cfg = DataConfig(seq_len=args.seq_len,
                          global_batch=args.global_batch,
                          vocab_size=cfg.vocab_size)
    trainer = Trainer(
        cfg, data_cfg,
        TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir, log_every=args.log_every),
        run=RunConfig(remat=args.remat, microbatch=args.microbatch),
        opt_cfg=adamw.OptimConfig(lr=args.lr, total_steps=args.steps))
    if not args.resume:
        trainer.init_state()
    out = trainer.train()
    first = out["history"][0]["loss"] if out["history"] else float("nan")
    last = out["history"][-1]["loss"] if out["history"] else float("nan")
    print(f"trained {args.arch} ({cfg.name}) to step {out['final_step']}: "
          f"loss {first:.4f} -> {last:.4f}")


if __name__ == "__main__":
    main()
