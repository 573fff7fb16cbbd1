"""Production training launcher.

On this container it runs the reduced configs on the single CPU device;
on a real fleet the SAME entry point runs under ``jax.distributed`` (one
process per host) with the production mesh — the step function and
shardings are identical to what launch/dryrun.py proves compiles for
(16,16) and (2,16,16).

    python -m repro.launch.train --arch smollm-360m --steps 100 --smoke
    python -m repro.launch.train --arch smollm-360m --mesh single  # fleet
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke
from repro.data import TokenPipeline
from repro.models import build_model
from repro.train import OptConfig, Trainer, TrainerConfig, TrainConfig


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced per-arch config (CPU-sized)")
    args = ap.parse_args()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family in ("encdec", "vlm"):
        raise SystemExit(f"{args.arch}: use a family-specific driver for the "
                         "stubbed-frontend archs (examples/)")
    model = build_model(cfg)
    print(f"[train] {cfg.name}: {model.n_params()/1e6:.1f}M params on "
          f"{jax.device_count()} device(s)")
    pipe = TokenPipeline(vocab=cfg.vocab, batch=args.batch, seq=args.seq)
    tcfg = TrainerConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir, log_every=10,
        train=TrainConfig(
            opt=OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps),
            microbatches=args.microbatches))
    Trainer(model, pipe, tcfg).run(resume=args.resume)


if __name__ == "__main__":
    main()
