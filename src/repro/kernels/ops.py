"""Jit-ready wrappers around the Pallas kernels (block-shape selection,
padding, platform dispatch).

The wrapper implements the two block regimes of DESIGN.md §2: a GEMV-like
schedule for decode (tiny M) and a GEMM schedule for prefill/training-shape
matmuls. VMEM budgeting note: one grid step holds the double-buffered
blocks ``2 * (bm*bk*2 (x, bf16) + bk/8*bn*4 (qw) + bk/GS*bn*5 (f32
scales + int8 zeros) + bm*bn*4 (out))`` plus ``bm*bn*4`` (acc) plus the
unpack temporaries ``bk*bn*10`` (int32 codes, f32 and bf16 weights).
The largest Qwen2.5-0.5B block — the down projection, where K/GS = 76
forces bk = K = 4864, at bm = 256, bn = 128 — is ≈ 6 MB of blocks plus
≈ 6 MB of temporaries, inside the 16 MiB of scoped VMEM a v5e kernel
gets by default (tests/test_tpu_compile.py compiles it for that chip).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.packing import PackedLinear
from repro.kernels.awq_matmul import awq_gateup_pallas, awq_matmul_pallas


def _divisor_block(dim: int, quantum: int, target: int) -> int:
    """Largest multiple of ``quantum`` that divides ``dim`` and is <= target."""
    best = quantum
    b = quantum
    while b <= min(dim, target):
        if dim % b == 0:
            best = b
        b += quantum
    return best


def choose_blocks(m: int, k: int, n: int, group_size: int,
                  ) -> tuple[int, int, int]:
    """(block_m, block_n, block_k) for the fused kernel.

    * block_k must be a multiple of the dequant group (metadata travels with
      its weights — the AWQ_MACRO invariant) and divide K. The scale and
      zero blocks are ``[block_k / GS, bn]``, and Mosaic takes a block's
      second-minor dim only as a multiple of 8 or the array's whole dim,
      so block_k / GS is the largest multiple of 8 dividing K / GS (up to
      1024 rows of K), else the whole K.
    * block_n multiples of 128 keep the MXU lane dimension full.
    * block_m picks the schedule: ≤ 8 rows ride one 8-sublane block (the
      decode GEMV regime — every weight block is streamed exactly once);
      larger M gets a GEMM block up to 256. The serving scheduler emits
      M = width · num_slots for every width in
      ``scheduler.width_family(chunk, spec_k)`` ({1, 2, 4, …, chunk} plus
      the k+1 spec-verify widths), so M is frequently NOT a multiple of 8
      — those pad up to the next 8-sublane boundary and take it as one
      block when ≤ 256 (single grid row) instead of degrading to bm=8.
    """
    groups = k // group_size
    per_block = [g for g in range(8, 1024 // group_size + 1, 8)
                 if groups % g == 0]
    block_k = per_block[-1] * group_size if per_block else k
    block_n = _divisor_block(n, 128, 512) if n % 128 == 0 else \
        _divisor_block(n, 8, 512)
    if m <= 8:
        block_m = 8                              # GEMV schedule
    elif m % 8 == 0:
        block_m = _divisor_block(m, 8, 256)      # GEMM, exact tiling
    else:
        padded = -(-m // 8) * 8                  # GEMM over padded rows
        block_m = padded if padded <= 256 else _divisor_block(padded, 8, 256)
    return block_m, block_n, block_k


def _pad_rows(x: jax.Array, block_m: int) -> tuple[jax.Array, int]:
    m = x.shape[0]
    pad = (-m) % block_m
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, m


def awq_matmul(x: jax.Array, p: PackedLinear, *,
               compute_dtype=jnp.bfloat16,
               interpret: bool | None = None) -> jax.Array:
    """Fused quantized matmul ``x [M, K] -> [M, N] float32``.

    ``interpret=None`` auto-selects: compiled on TPU, interpret elsewhere
    (the kernel body then runs as a reference-shaped CPU program — used by
    the allclose test sweeps).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    k = x.shape[-1]
    bm, bn, bk = choose_blocks(x.shape[0], k, p.n, p.group_size)
    xp, m = _pad_rows(x, bm)
    y = awq_matmul_pallas(
        xp, p.qweight, p.scales, p.zeros, group_size=p.group_size,
        block_m=bm, block_n=bn, block_k=bk, compute_dtype=compute_dtype,
        interpret=interpret)
    return y[:m]


def awq_gateup(x: jax.Array, gate: PackedLinear, up: PackedLinear, *,
               compute_dtype=jnp.bfloat16,
               interpret: bool | None = None) -> jax.Array:
    """Fused ``silu(x@Wg) * (x@Wu)`` — single pass over activations."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if gate.group_size != up.group_size or gate.n != up.n:
        raise ValueError("gate/up shape mismatch")
    k = x.shape[-1]
    bm, bn, bk = choose_blocks(x.shape[0], k, gate.n, gate.group_size)
    xp, m = _pad_rows(x, bm)
    y = awq_gateup_pallas(
        xp, gate.qweight, gate.scales, gate.zeros, up.qweight, up.scales,
        up.zeros, group_size=gate.group_size, block_m=bm, block_n=bn,
        block_k=bk, compute_dtype=compute_dtype, interpret=interpret)
    return y[:m]
