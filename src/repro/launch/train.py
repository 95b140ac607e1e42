"""End-to-end training driver (example application + fault-tolerance demo).

Runs a real training loop on the current host (CPU smoke scale or the full
mesh): deterministic data pipeline with background prefetch, microbatched
AdamW train step, periodic crash-consistent checkpoints, and automatic
resume from the newest checkpoint — kill it at any step and rerun the same
command to continue (the deterministic pipeline regenerates exactly the
batches that would have followed; see checkpoint/ckpt.py).

  PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b --smoke \
      --steps 100 --ckpt-dir /tmp/ckpt --ckpt-every 20
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.configs.base import ShapeSpec
from repro.data import Prefetcher, make_batch_iterator
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry as R
from repro.train import AdamWConfig, make_train_step
from repro.train.step import TrainState


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    args = p.parse_args(argv)
    enable_compile_cache()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    cfg = cfg.scaled(num_microbatches=args.microbatches)
    api = R.build(cfg)
    shape = ShapeSpec("cli", args.seq_len, args.batch, "train")

    opt = AdamWConfig(
        lr=args.lr, warmup_steps=args.warmup, total_steps=args.steps,
        schedule=cfg.lr_schedule,
    )
    step_fn = jax.jit(make_train_step(api, opt))

    state = TrainState.create(api, jax.random.PRNGKey(args.seed))
    start = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every or 0)
        restored = mgr.restore_latest(jax.eval_shape(lambda: state))
        if restored is not None:
            start, state = restored
            print(f"resumed from checkpoint at step {start}")

    it = Prefetcher(
        make_batch_iterator(cfg, shape, seed=args.seed, start_step=start), depth=2
    )
    t0 = time.perf_counter()
    tokens_done = 0
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        state, metrics = step_fn(state, batch)
        tokens_done += args.batch * args.seq_len
        if mgr and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            mgr.maybe_save(step + 1, state)
        if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
            dt = time.perf_counter() - t0
            print(
                f"step {step + 1:5d}  loss {float(metrics['loss']):.4f}  "
                f"lr {float(metrics['lr']):.2e}  gnorm {float(metrics['grad_norm']):.3f}  "
                f"tok/s {tokens_done / dt:,.0f}"
            )
    print("done")
    return state


if __name__ == "__main__":
    main()
