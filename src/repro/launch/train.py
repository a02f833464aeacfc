"""Training CLI (end-to-end driver, deliverable b).

  PYTHONPATH=src python -m repro.launch.train \
      --arch qwen2-7b --smoke --steps 200 --ckpt-dir /tmp/ckpt

--smoke trains the reduced config on CPU (the ~100M-class run); the full
configs are for real TPU slices (the multi-pod dry-run proves lowering).
"""
from __future__ import annotations

import argparse
import json

import jax

from ..configs import ARCHS, RunConfig, reduced
from ..data import DataConfig
from ..train import train
from ..train.fault_tolerance import FailureInjector
from .compile_cache import configure_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=0,
                    help="inject a failure at this step (FT demo)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    configure_compile_cache()

    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = reduced(cfg)
    rc = RunConfig(optimizer=args.optimizer, learning_rate=args.lr,
                   microbatches=args.microbatches, remat=False,
                   attn_impl="naive", warmup_steps=max(1, args.steps // 10))
    dc = DataConfig(seed=args.seed, vocab=cfg.vocab, seq_len=args.seq,
                    global_batch=args.batch)
    injector = (FailureInjector(fail_at_steps=(args.fail_at,))
                if args.fail_at else None)
    res = train(cfg, rc, dc, n_steps=args.steps, seed=args.seed,
                ckpt_dir=args.ckpt_dir or None,
                ckpt_every=args.ckpt_every, injector=injector)
    print(json.dumps({
        "arch": cfg.name, "steps": args.steps,
        "resumed_from": res.resumed_from,
        "loss_first": res.losses[0], "loss_last": res.losses[-1],
        "stragglers": res.straggler_steps,
        "devices": len(jax.devices()),
    }, indent=1))


if __name__ == "__main__":
    main()
