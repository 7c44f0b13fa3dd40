"""Pallas TPU flash attention (§Perf C3): tiled online-softmax attention.

Why it exists here: the roofline of long-context prefill (§Roofline,
smollm/hubert/glm4 prefill_32k) is dominated by f32 score tensors hitting
HBM — ~2 TB/layer at S=32k. Flash tiling (Dao et al.; TPU adaptation per
the splash-kernel lineage) keeps each [block_q × block_k] score tile in
VMEM and carries the online-softmax state (running max m, normalizer l,
accumulator) across the K grid axis, so score traffic never leaves VMEM.

Supports: causal masking, sliding windows (gemma3/hymba local layers), GQA
(q-head → kv-head mapping in the BlockSpec index maps). Validated in
interpret mode against `ref.flash_attention_ref` over
shape/window/GQA sweeps (tests/test_flash_attention.py).

Layout: q [B, H, S, hd], k/v [B, Hkv, S, hd] — grid (B·H, S/bq, S/bk),
K innermost (accumulation), online state in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_k: int, n_k: int, scale: float,
                  causal: bool, window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # [bq, hd]
    k = k_ref[0].astype(jnp.float32)            # [bk, hd]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # [bq, 128] replicated
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1)[:, None]          # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)              # [bq, 128]
    p = jnp.exp(s - m_new[:, :1])                # [bq, bk]
    l_new = l_prev * alpha + jnp.broadcast_to(
        jnp.sum(p, axis=1)[:, None], l_prev.shape)
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == n_k - 1)
    def _flush():
        l = l_ref[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)          # fully-masked rows → 0
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "causal", "window", "block_q",
                              "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float | None = None, causal: bool = True,
                    window: int = 0, block_q: int = 128,
                    block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q [B, H, S, hd], k/v [B, Hkv, S, hd] → [B, H, S, hd].

    GQA: H % Hkv == 0; q head h reads kv head h // (H // Hkv).
    S must divide by block_q/block_k (the wrapper in ops pads).
    """
    b, h, s, hd = q.shape
    hkv = k.shape[1]
    g = h // hkv
    scale = scale if scale is not None else hd ** -0.5
    n_k = s // block_k
    grid = (b * h, s // block_q, n_k)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, n_k=n_k,
        scale=scale, causal=causal, window=window)

    qf = q.reshape(b * h, s, hd)
    kf = k.reshape(b * hkv, s, hd)
    vf = v.reshape(b * hkv, s, hd)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda bh, qi, ki, _g=g: (bh // _g, ki, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda bh, qi, ki, _g=g: (bh // _g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 128), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32),
                        pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)
    return out.reshape(b, h, s, hd)
