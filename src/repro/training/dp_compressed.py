"""Data-parallel train step with explicit int8+EF gradient reduction.

The main train path (`training/train_step.py`) lets XLA insert the DP
all-reduce (bf16 via `grad_comm_dtype`). This variant makes the reduction
EXPLICIT so it can be compressed below bf16 — the pattern intended for the
cross-pod `pod` axis where DCI bandwidth, not ICI, bounds the collective
term (DESIGN.md §5):

  * the whole step runs under `shard_map` over the DP axes,
  * each shard computes grads on its micro-batch,
  * grads cross the wire as int8 codes + one f32 scale per tensor
    (`distributed.compression.int8_psum_mean`), error feedback carries the
    residual to the next step,
  * AdamW applies the reduced gradient identically on every shard
    (replicated params, deterministic),
  * the EF residual is genuinely per-worker state: it carries an explicit
    leading DP dim sharded over the mesh (never falsely "replicated").

Tested against the uncompressed reduction on an 8-device mesh
(tests/test_compression.py): descent parity within tolerance, EF bounded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.compression import int8_psum_mean
from repro.training.optim import AdamWConfig, adamw_init, adamw_update


def dp_degree(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def init_dp_state(model, key, mesh) -> tuple[dict, dict]:
    """→ (replicated train state, per-shard EF residuals [n_dp, ...])."""
    params = model.init(key)
    n = dp_degree(mesh)
    ef = jax.tree.map(lambda p: jnp.zeros((n,) + p.shape, jnp.float32),
                      params)
    state = {"params": params, "opt": adamw_init(params),
             "step": jnp.zeros((), jnp.int32)}
    return state, ef


def make_dp_train_step(model, mesh, opt_cfg: AdamWConfig,
                       compress: bool = True):
    """Returns jit'd ``step(state, ef, batch) -> (state, ef, metrics)``."""
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def shard_body(state, ef, batch):
        def loss_fn(p):
            return model.loss(p, batch)

        (loss, _), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        loss = jax.lax.pmean(loss, dp_axes)

        if compress:
            flat_g, tdef = jax.tree.flatten(grads)
            flat_e = jax.tree.leaves(ef)
            red, new_e = [], []
            for g, e in zip(flat_g, flat_e):
                r, ne = int8_psum_mean(g, e[0], dp_axes)
                red.append(r)
                new_e.append(ne[None])
            grads = jax.tree.unflatten(tdef, red)
            ef = jax.tree.unflatten(tdef, new_e)
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, dp_axes), grads)

        params, opt, opt_metrics = adamw_update(
            state["params"], grads, state["opt"], state["step"], opt_cfg)
        new_state = {"params": params, "opt": opt,
                     "step": state["step"] + 1}
        return new_state, ef, {"loss": loss, **opt_metrics}

    step = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), P(dp_axes), P(dp_axes)),
        out_specs=(P(), P(dp_axes), P()),
        check_vma=False,
    )
    return jax.jit(step)
