"""Training launcher: real steps on the local device(s).

On this CPU container it runs reduced configs end-to-end (the full configs
are exercised by launch/dryrun.py); on a real pod the same driver binds the
production mesh.  Composes: config registry -> data pipeline -> train step
-> checkpointing -> elastic/straggler hooks.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b \
      --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro import configs
from repro.obs.clock import MonotonicClock
from repro.checkpoint import CheckpointManager
from repro.config import OptimConfig, RunConfig, ShapeConfig
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.train import step as train_step_mod


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get_config(args.arch))
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    run = RunConfig(model=cfg, shape=shape,
                    optim=OptimConfig(lr=args.lr, warmup_steps=10,
                                      total_steps=max(args.steps, 2)),
                    microbatch=args.microbatch)

    state = train_step_mod.make_train_state(run, jax.random.key(run.seed))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        state.params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"devices={jax.device_count()}")

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if ckpt and args.resume and ckpt.latest_step() is not None:
        state, extra = ckpt.restore(state)
        start = int(extra.get("step", 0))
        print(f"resumed from step {start}")

    step_fn = jax.jit(train_step_mod.build_train_step(run),
                      donate_argnums=(0,))
    data = SyntheticLM(cfg, args.batch, args.seq, seed=run.seed)
    it = Prefetcher(data.iterate(start), depth=2)

    wall = MonotonicClock()
    t0 = wall.now_us()
    for i in range(start, args.steps):
        batch = {k: jax.numpy.asarray(v) for k, v in next(it).items()}
        state, metrics = step_fn(state, batch)
        if (i + 1) % args.log_every == 0 or i + 1 == args.steps:
            dt = (wall.now_us() - t0) / 1e6 / max(i + 1 - start, 1)
            print(f"step {i+1:5d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} {dt*1e3:.0f} ms/step",
                  flush=True)
        if ckpt and (i + 1) % args.ckpt_every == 0:
            ckpt.save(i + 1, state, extra={"step": i + 1})
    it.close()
    if ckpt:
        ckpt.save(args.steps, state, extra={"step": args.steps})
        print(f"checkpointed at {args.ckpt_dir}")


if __name__ == "__main__":
    main()
