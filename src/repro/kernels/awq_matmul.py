"""Pallas TPU kernel: fused unpack + dequantize + matmul (the MACRO_MAC unit).

This is the TPU adaptation of the paper's accelerator (§III-B, Fig. 4):

  paper (KV260 fabric)                     this kernel (TPU)
  ------------------------------------     ------------------------------------
  4× AXI 128-bit channels streaming        `pallas_call` grid pipeline: HBM→VMEM
  AWQ_MACROs from DDR                      DMA of the *packed int32* blocks,
                                           double-buffered across grid steps
  unpack unit (shift + bitmask)            `>> (4*j) & 0xF` on VREGs
  dequant (q - zero) * scale per group     group-broadcast fused in VMEM
  8×8 PE array + adder tree (FP32 MAC)     128×128 MXU `jnp.dot` (f32 accum)
  partial-sum accumulation per out chan    VMEM f32 scratch accumulated over
                                           the K grid axis

The key property preserved from the paper: weights cross the bandwidth-
critical boundary (HBM→VMEM here, DDR→PL there) in packed INT4 form, with
scales/zeros riding in the same block (block_k is a multiple of the quant
group, so dequant metadata always travels with its weights), and are only
expanded to float inside the compute unit's pipeline.

Block-shape regimes (DESIGN.md §2): decode is a GEMV (`block_m = 8`), prefill
a GEMM (`block_m = 128..256`) — one kernel, two schedules, selected by the
wrapper in `ops.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import PACK


def _unpack_dequant(qw_block, s_block, z_block, block_k: int, block_n: int,
                    group_size: int, compute_dtype):
    """[bk//8, bn] int32 → [bk, bn] float, dequantized (in-VMEM pipeline)."""
    # Shift+mask unpack, mirroring the paper's unpack unit (Fig. 4b), in
    # int32: Mosaic has no uint32→float cast, and a logical shift + 0xF
    # mask leaves 0..15, which int32→float converts exactly. The stack
    # axis is the nibble index j ⇒ original row = word_row * 8 + j.
    nibs = [jax.lax.shift_right_logical(qw_block, jnp.int32(4 * j)) & 0xF
            for j in range(PACK)]
    q = jnp.stack(nibs, axis=1).reshape(block_k, block_n)  # int32
    groups = block_k // group_size
    qf = q.reshape(groups, group_size, block_n).astype(jnp.float32)
    z = z_block.astype(jnp.float32)[:, None, :]   # [g, 1, bn]
    s = s_block.astype(jnp.float32)[:, None, :]   # [g, 1, bn]
    w = (qf - z) * s                              # PE op, Fig. 4d
    return w.reshape(block_k, block_n).astype(compute_dtype)


def _awq_matmul_kernel(x_ref, qw_ref, s_ref, z_ref, o_ref, acc_ref, *,
                       block_k: int, block_n: int, n_k: int, group_size: int,
                       compute_dtype):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _unpack_dequant(qw_ref[...], s_ref[...], z_ref[...], block_k,
                        block_n, group_size, compute_dtype)
    x = x_ref[...].astype(compute_dtype)
    # MXU MAC with f32 accumulation (adder-tree analogue).
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "block_m", "block_n", "block_k",
                     "compute_dtype", "interpret"))
def awq_matmul_pallas(x: jax.Array, qweight: jax.Array, scales: jax.Array,
                      zeros: jax.Array, *, group_size: int, block_m: int,
                      block_n: int, block_k: int,
                      compute_dtype=jnp.bfloat16,
                      interpret: bool = False) -> jax.Array:
    """``x [M, K] @ dequant(qweight [K//8, N]) → [M, N] float32``.

    Shape contract (enforced by the `ops.py` wrapper): M % block_m == 0,
    N % block_n == 0, K % block_k == 0, block_k % group_size == 0.
    """
    m, k = x.shape
    n = qweight.shape[1]
    n_k = k // block_k
    grid = (m // block_m, n // block_n, n_k)

    kernel = functools.partial(
        _awq_matmul_kernel, block_k=block_k, block_n=block_n, n_k=n_k,
        group_size=group_size, compute_dtype=compute_dtype)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k // PACK, block_n),
                         lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_k // group_size, block_n),
                         lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_k // group_size, block_n),
                         lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name="awq_matmul",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, qweight, scales, zeros)


def _awq_gateup_kernel(x_ref, qg_ref, sg_ref, zg_ref, qu_ref, su_ref, zu_ref,
                       o_ref, accg_ref, accu_ref, *, block_k: int,
                       block_n: int, n_k: int, group_size: int,
                       compute_dtype):
    """Fused FFN front: silu(x@Wg) * (x@Wu) — one pass over x per K block.

    The paper's Table I shows gate+up projections are 51% of inference time;
    fusing them halves the activation traffic (x is streamed once) and skips
    the intermediate HBM round-trip for silu/mul — this is the beyond-paper
    kernel used in the §Perf hillclimb.
    """
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    x = x_ref[...].astype(compute_dtype)
    wg = _unpack_dequant(qg_ref[...], sg_ref[...], zg_ref[...], block_k,
                         block_n, group_size, compute_dtype)
    wu = _unpack_dequant(qu_ref[...], su_ref[...], zu_ref[...], block_k,
                         block_n, group_size, compute_dtype)
    accg_ref[...] += jnp.dot(x, wg, preferred_element_type=jnp.float32)
    accu_ref[...] += jnp.dot(x, wu, preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _flush():
        g = accg_ref[...]
        u = accu_ref[...]
        o_ref[...] = (g * jax.lax.logistic(g) * u).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "block_m", "block_n", "block_k",
                     "compute_dtype", "interpret"))
def awq_gateup_pallas(x, qw_gate, s_gate, z_gate, qw_up, s_up, z_up, *,
                      group_size: int, block_m: int, block_n: int,
                      block_k: int, compute_dtype=jnp.bfloat16,
                      interpret: bool = False) -> jax.Array:
    m, k = x.shape
    n = qw_gate.shape[1]
    n_k = k // block_k
    grid = (m // block_m, n // block_n, n_k)
    kernel = functools.partial(
        _awq_gateup_kernel, block_k=block_k, block_n=block_n, n_k=n_k,
        group_size=group_size, compute_dtype=compute_dtype)
    wspec = pl.BlockSpec((block_k // PACK, block_n), lambda i, j, kk: (kk, j))
    gspec = pl.BlockSpec((block_k // group_size, block_n),
                         lambda i, j, kk: (kk, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
                  wspec, gspec, gspec, wspec, gspec, gspec],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32),
                        pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name="awq_gateup",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, qw_gate, s_gate, z_gate, qw_up, s_up, z_up)
