"""Training CLI.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --steps 100 --batch 8 --seq 128 --policy mixed --qat \
      [--reduced] [--grad-compression posit8] [--opt-dtype posit8]

Single-host driver; the production meshes are exercised by
``repro.launch.dryrun`` (this container has one real device)."""

from __future__ import annotations

import argparse

import jax

from ..configs import get_config
from ..configs.base import RunConfig
from ..data import TokenStream
from ..train.loop import train_loop
from .cache import setup_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--policy", default="fp32")
    ap.add_argument("--qat", action="store_true")
    ap.add_argument("--grad-compression", default="none")
    ap.add_argument("--opt-dtype", default="float32")
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config (CPU-friendly)")
    args = ap.parse_args()
    setup_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    run = RunConfig(
        arch=args.arch, steps=args.steps, lr=args.lr,
        microbatch=args.microbatch, qat=args.qat,
        precision_policy=args.policy, grad_compression=args.grad_compression,
        opt_state_dtype=args.opt_dtype, checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every)
    data = TokenStream(vocab=cfg.vocab, seq_len=args.seq,
                       global_batch=args.batch, frontend=cfg.frontend,
                       d_model=cfg.d_model, n_patches=cfg.n_patches)
    state, hist = train_loop(cfg, run, data)
    print(f"final loss: {hist['loss'][-1]:.4f} at step {int(state.step)}")


if __name__ == "__main__":
    main()
