"""Training launcher: mesh-sharded train loop with fault tolerance.

Features exercised end-to-end (CPU-scale with smoke configs; the same code
path drives the production mesh):
  * pjit train step with param/ZeRO-1/batch shardings,
  * async atomic checkpointing + exact resume (pure-function data pipeline),
  * node-failure recovery: any step exception reloads the latest checkpoint
    and continues (``--simulate-failure-at`` injects one for testing),
  * straggler watchdog: per-step wall-clock vs running median; slow steps
    are logged with the step payload so an external scheduler can
    re-dispatch (single-process stand-in for the real-fleet mitigation).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen25-05b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import statistics
import time

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.checkpoint import AsyncCheckpointer, latest_step, restore
from repro.data import make_dataset
from repro.distributed import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.training import AdamWConfig, TrainConfig, make_train_step
from repro.training.train_step import init_train_state


class SimulatedNodeFailure(RuntimeError):
    """The failure ``--simulate-failure-at`` injects into one step."""


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen25-05b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-axis", type=int, default=1)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--simulate-failure-at", type=int, default=-1)
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    model = build_model(cfg)
    mesh = make_host_mesh(args.data_axis, args.model_axis)
    tcfg = TrainConfig(optimizer=AdamWConfig(
        lr=args.lr, warmup_steps=args.warmup, decay_steps=args.steps,
        weight_decay=0.0))
    ds = make_dataset(cfg, args.batch, args.seq, args.seed)

    with shd.use_mesh(mesh):
        step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))
        state = init_train_state(model, jax.random.PRNGKey(args.seed))
        start = 0
        ckpt = None
        if args.ckpt_dir:
            ckpt = AsyncCheckpointer(args.ckpt_dir)
            if latest_step(args.ckpt_dir) is not None:
                tpl = jax.eval_shape(lambda: init_train_state(
                    model, jax.random.PRNGKey(args.seed)))
                state, start = restore(args.ckpt_dir, tpl)
                print(f"[train] resumed from step {start}")

        losses, times = [], []
        i = start
        failed_once = False
        while i < args.steps:
            batch = {k: jnp.asarray(v) for k, v in ds.batch_at(i).items()}
            t0 = time.time()
            try:
                if i == args.simulate_failure_at and not failed_once:
                    failed_once = True
                    raise SimulatedNodeFailure("simulated node failure")
                state, metrics = step_fn(state, batch)
                jax.block_until_ready(metrics["loss"])
            except SimulatedNodeFailure as e:  # reload + retry; any other
                # error (a compile error, a bad shape) is not a node
                # failure and would recur on every retry, so it propagates
                print(f"[train] step {i} failed ({e}); recovering from "
                      "latest checkpoint")
                if ckpt is None or latest_step(args.ckpt_dir) is None:
                    state = init_train_state(model,
                                             jax.random.PRNGKey(args.seed))
                    i = 0
                else:
                    ckpt.wait()
                    tpl = jax.eval_shape(lambda: init_train_state(
                        model, jax.random.PRNGKey(args.seed)))
                    state, i = restore(args.ckpt_dir, tpl)
                continue
            dt = time.time() - t0
            times.append(dt)
            if len(times) >= 5:
                med = statistics.median(times[-50:])
                if dt > args.straggler_factor * med:
                    print(f"[train] STRAGGLER step {i}: {dt:.3f}s vs median "
                          f"{med:.3f}s — flagged for re-dispatch")
            losses.append(float(metrics["loss"]))
            if i % args.log_every == 0:
                print(f"[train] step {i} loss={losses[-1]:.4f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"dt={dt*1e3:.0f}ms")
            i += 1
            if ckpt and (i % args.ckpt_every == 0 or i == args.steps):
                ckpt.save(i, state)
        if ckpt:
            ckpt.close()
    print(f"[train] done: loss {losses[0]:.4f} → {losses[-1]:.4f}")
    return {"first_loss": losses[0] if losses else None,
            "last_loss": losses[-1] if losses else None,
            "steps": len(losses)}


if __name__ == "__main__":
    main()
