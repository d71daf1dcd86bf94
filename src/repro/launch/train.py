"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --reduced --steps 50 --batch 8 --seq 64

Runs on whatever devices exist (1 CPU locally; a pod via the production
mesh) through the same pjit path: mesh -> sharding rules -> model ->
fault-tolerant loop with checkpoint/restart.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs.base import get_config
from repro.data import DataConfig, SyntheticPipeline
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.launch.steps import make_train_step, train_state_shardings
from repro.models import Model
from repro.optim import AdamWConfig, adamw_init
from repro.runtime.fault import FaultPolicy, FaultTolerantLoop
from repro.sharding import ShardingRules


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced).replace(
        param_dtype="float32", compute_dtype="float32", remat="none")
    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())
    rules = ShardingRules(mesh)
    model = Model(cfg, rules)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps)
    pipe = SyntheticPipeline(cfg, DataConfig(args.batch, args.seq,
                                             seed=args.seed))

    params = model.init(jax.random.PRNGKey(args.seed))
    state = adamw_init(params, opt_cfg)
    step_fn = jax.jit(
        make_train_step(model, opt_cfg, args.microbatches),
        in_shardings=(train_state_shardings(model, rules, opt_cfg),
                      None),
        donate_argnums=(0,))

    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    loop = FaultTolerantLoop(
        step_fn, ckpt,
        FaultPolicy(checkpoint_every=args.ckpt_every))
    state, start = loop.resume_or_init(state)
    if start:
        print(f"[train] resumed from checkpoint at step {start}")

    losses = []

    def on_metrics(step, metrics):
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % 10 == 0 or step == start:
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e}")

    t0 = time.time()
    state, end = loop.run(state, pipe.host_slice, start, args.steps,
                          on_metrics)
    dt = time.time() - t0
    tput = args.steps * args.batch * args.seq / max(dt, 1e-9)
    print(f"[train] {args.steps} steps in {dt:.1f}s "
          f"({tput:,.0f} tok/s), loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
