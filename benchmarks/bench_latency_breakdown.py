"""Paper Table I: per-op latency breakdown of one decode step.

The paper profiles Qwen2.5-0.5B decode on the KV260's ARM PS and finds
91.6% of time in MAC operations (matmuls) — the observation that justifies
offloading matmuls to the accelerator. We reproduce the experiment on this
host CPU with the real qwen25-05b dims (single layer, averaged): each
component jit'd and timed separately, then scaled by num_layers.

Output: name,us_per_call,percent — compare the MAC share against 91.6%.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

import repro.configs as C
from repro.models import layers


def _time(fn, *args, iters=20) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run(csv_rows: list) -> dict:
    cfg = C.get_config("qwen25-05b")
    d, q_dim, kv_dim, f = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.d_ff
    b, s_ctx = 1, 1024  # single-request decode against a 1k context
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (b, d), jnp.float32)
    wq = jax.random.normal(key, (d, q_dim), jnp.float32) * 0.02
    wk = jax.random.normal(key, (d, kv_dim), jnp.float32) * 0.02
    wo = jax.random.normal(key, (q_dim, d), jnp.float32) * 0.02
    wg = jax.random.normal(key, (d, f), jnp.float32) * 0.02
    wd = jax.random.normal(key, (f, d), jnp.float32) * 0.02
    bias_q = jnp.zeros((q_dim,))
    kcache = jax.random.normal(key, (b, s_ctx, cfg.num_kv_heads,
                                     cfg.head_dim), jnp.float32)
    gamma = jnp.ones((d,))
    h_attn = jax.random.normal(key, (b, q_dim), jnp.float32)
    h_ff = jax.random.normal(key, (b, f), jnp.float32)
    qh = jax.random.normal(key, (b, cfg.num_heads, cfg.head_dim))
    cos, sin = layers.rope_cos_sin(jnp.zeros((b,), jnp.int32), cfg.head_dim,
                                   cfg.rope_theta)

    comps = {
        # linear ops (MACs)
        "qkv_projection_mac": jax.jit(
            lambda x: (x @ wq, x @ wk, x @ wk)),
        "qkv_bias_add": jax.jit(lambda x: (x @ wq) + bias_q),
        "attention_scores_values": jax.jit(
            lambda q, k: jnp.einsum(
                "bkgs,bskd->bkgd",
                jax.nn.softmax(jnp.einsum("bkgd,bskd->bkgs",
                                          q.reshape(b, 2, 7, 64), k), -1),
                k)),
        "output_proj_residual": jax.jit(lambda h, x: x + h @ wo),
        "ffn_gate_up_mac": jax.jit(
            lambda x: jax.nn.silu(x @ wg) * (x @ wg)),
        "ffn_down_residual": jax.jit(lambda h, x: x + h @ wd),
        # non-linear ops (paper: stay on the CPU/VPU)
        "rope": jax.jit(lambda q: layers.apply_rope(q, cos, sin, 64)),
        "rmsnorm": jax.jit(
            lambda x: layers.rmsnorm({"gamma": gamma}, x)),
        "silu_elemwise_mul": jax.jit(lambda g, u: jax.nn.silu(g) * u),
    }
    args = {
        "qkv_projection_mac": (x,), "qkv_bias_add": (x,),
        "attention_scores_values": (qh, kcache),
        "output_proj_residual": (h_attn, x),
        "ffn_gate_up_mac": (x,), "ffn_down_residual": (h_ff, x),
        "rope": (qh,), "rmsnorm": (x,), "silu_elemwise_mul": (h_ff, h_ff),
    }
    mac_ops = {"qkv_projection_mac", "attention_scores_values",
               "output_proj_residual", "ffn_gate_up_mac",
               "ffn_down_residual"}

    times = {k: _time(fn, *args[k]) for k, fn in comps.items()}
    total = sum(times.values())
    mac_pct = 100 * sum(times[k] for k in mac_ops) / total
    for k, v in times.items():
        tag = "MAC" if k in mac_ops else "nonlinear"
        csv_rows.append((f"latency_breakdown/{k}", f"{v:.1f}",
                         f"{100*v/total:.1f}%({tag})"))
    csv_rows.append(("latency_breakdown/mac_share", f"{total:.1f}",
                     f"{mac_pct:.1f}% (paper Table I: 91.6%)"))
    return {"mac_pct": mac_pct, "total_us_per_layer": total}


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    print(run(rows))
    for r in rows:
        print(",".join(r))
