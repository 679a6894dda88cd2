"""Training launcher.

Smoke scale (this container, executes for real):
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --smoke --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config, serving_config
from repro.data.dataset import lm_batches
from repro.launch.steps import make_train_step
from repro.models.init import count_params, init_params
from repro.training.checkpoint import save_pytree


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b-thinking")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--serving-vocab", action="store_true",
                    help="wire the smoke config to the task tokenizer")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--save", default=None, help="checkpoint path (.npz)")
    args = ap.parse_args()

    cfg = serving_config(args.arch) if args.serving_vocab \
        else get_config(args.arch, smoke=args.smoke)
    params = init_params(cfg, jax.random.PRNGKey(0))
    print(f"[train] arch={cfg.name} params={count_params(params):,}")

    step_fn, opt = make_train_step(cfg, lr=args.lr,
                                   microbatches=args.microbatches)
    opt_state = opt.init(params)
    step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    batches = lm_batches(args.seq, args.batch)
    t0 = time.time()
    for step in range(args.steps):
        arr = next(batches)
        batch = {"tokens": jnp.asarray(arr[:, :-1]),
                 "labels": jnp.asarray(arr[:, 1:])}
        params, opt_state, loss = step_fn(params, opt_state, batch)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"  step {step:5d} loss {float(loss):.4f} "
                  f"({time.time() - t0:.1f}s)")
    if args.save:
        save_pytree(args.save, params)
        print(f"[train] saved to {args.save}")


if __name__ == "__main__":
    main()
