"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        [--reduced] [--steps 100] [--batch 4] [--seq 256] [--ckpt DIR]

Full configs run through the production mesh shardings (requires real
devices or the dry-run's forced host-device count); --reduced runs the
smoke-scale variant on whatever devices exist.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from ..configs import ARCHS, get_config
from ..training.train_loop import train
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"# training {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"params≈{cfg.n_params() / 1e6:.1f}M on {jax.device_count()} device(s)")
    out = train(cfg, steps=args.steps, batch=args.batch, seq_len=args.seq,
                seed=args.seed, lr=args.lr, log_every=args.log_every,
                checkpoint_dir=args.ckpt)
    for h in out["history"]:
        print(json.dumps(h))
    print(f"# done: final_loss={out['final_loss']:.4f} "
          f"wall={out['wall_s']:.1f}s")


if __name__ == "__main__":
    main()
