"""Weight packing — the paper's ``AWQ_MACRO`` layout, adapted for TPU.

Two layouts live here on purpose (DESIGN.md §2):

1. **TPU compute layout** (`pack_int4`/`unpack_int4`): qweights are packed 8
   consecutive K-rows per int32 word → tensor ``[K//8, N] int32``; scales and
   zeros stay as lane-aligned ``[K//GS, N]`` tensors. One VMEM block of the
   Pallas kernel carries whole dequant groups (block_k % GS == 0), which is
   the TPU analogue of the paper's bandwidth-aligned 128-bit AXI strips: the
   dequant metadata always travels with the weights it dequantizes, enabling
   on-the-fly dequantization inside the MAC pipeline.

2. **Byte-exact ``AWQ_MACRO`` serialization** (`awq_macro_bytes` et al.): the
   paper's Fig. 3 block — GS×8 INT4 qweights + 8 FP16 scales + a 128-bit
   zeros strip (8×INT4 used, 96 bits zero padding). This is the layout the
   55.1 % compression claim is measured against, so the compression benchmark
   serializes through it byte-for-byte.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantize import QuantConfig

PACK = 8  # int4 values per int32 word


# ---------------------------------------------------------------------------
# TPU compute layout
# ---------------------------------------------------------------------------

def pack_int4(q: jax.Array) -> jax.Array:
    """Pack uint4-coded ``[K, N] int32`` → ``[K//8, N] int32``.

    Nibble ``j`` of word ``w`` holds row ``w*8 + j`` (little-endian nibbles),
    mirroring the paper's unpack unit which shifts+masks 8 INT4 chunks out of
    each 32-bit word (Fig. 4b).
    """
    k, n = q.shape
    if k % PACK != 0:
        raise ValueError(f"K={k} not divisible by {PACK}")
    qq = q.astype(jnp.uint32).reshape(k // PACK, PACK, n)
    shifts = (4 * jnp.arange(PACK, dtype=jnp.uint32))[None, :, None]
    word = jnp.sum(qq << shifts, axis=1, dtype=jnp.uint32)
    return word.astype(jnp.int32)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4` → ``[K, N] int32`` in [0, 15]."""
    kp, n = packed.shape
    w = packed.astype(jnp.uint32)[:, None, :]  # [K//8, 1, N]
    shifts = (4 * jnp.arange(PACK, dtype=jnp.uint32))[None, :, None]
    nib = (w >> shifts) & jnp.uint32(0xF)
    return nib.reshape(kp * PACK, n).astype(jnp.int32)


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class PackedLinear:
    """A quantized linear layer's on-device tensors (TPU layout).

    Attributes:
      qweight:     [K//8, N] int32 — packed uint4 codes.
      scales:      [K//GS, N] float (bf16/f32) — per-(group, out-chan) scale.
      zeros:       [K//GS, N] int8 — asymmetric zero-points (uint4 codes).
      input_scale: [K] float32 — AWQ inverse activation scale (x * input_scale
                   before the matmul); ones when folded into the producer.
      bias:        [N] or None.
      group_size:  static.
      tp:          static — how a serving mesh splits the layer over its
                   ``model`` axis: ``"col"`` (N), ``"row"`` (K) or None.
                   Set by `distributed.sharding.tag_tensor_parallel`;
                   the fused kernel follows it under `shard_map`.
    """

    qweight: jax.Array
    scales: jax.Array
    zeros: jax.Array
    input_scale: jax.Array
    bias: jax.Array | None
    group_size: int
    tp: str | None = None

    @property
    def k(self) -> int:
        return self.qweight.shape[-2] * PACK  # last-2 dims: leading = layers

    @property
    def n(self) -> int:
        return self.qweight.shape[-1]

    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        return ([(ga("qweight"), self.qweight), (ga("scales"), self.scales),
                 (ga("zeros"), self.zeros),
                 (ga("input_scale"), self.input_scale),
                 (ga("bias"), self.bias)], (self.group_size, self.tp))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, group_size=aux[0], tp=aux[1])


def pack_linear(q: jax.Array, scales: jax.Array, zeros: jax.Array,
                input_scale: jax.Array | None, bias: jax.Array | None,
                cfg: QuantConfig) -> PackedLinear:
    k = q.shape[0]
    if input_scale is None:
        input_scale = jnp.ones((k,), jnp.float32)
    return PackedLinear(
        qweight=pack_int4(q),
        scales=scales.astype(jnp.float32),
        zeros=zeros.astype(jnp.int8),
        input_scale=input_scale.astype(jnp.float32),
        bias=bias,
        group_size=cfg.group_size,
    )


def dequantize_packed(p: PackedLinear,
                      dtype=jnp.float32) -> jax.Array:
    """Materialize the float weight ``[K, N]`` (reference path only)."""
    q = unpack_int4(p.qweight)
    g = p.k // p.group_size
    qg = q.reshape(g, p.group_size, p.n).astype(jnp.float32)
    w = (qg - p.zeros[:, None, :].astype(jnp.float32)) * \
        p.scales[:, None, :].astype(jnp.float32)
    return w.reshape(p.k, p.n).astype(dtype)


# ---------------------------------------------------------------------------
# Byte-exact AWQ_MACRO serialization (paper Fig. 3) — compression benchmark
# ---------------------------------------------------------------------------

def awq_macro_nbytes(group_size: int) -> int:
    """Bytes of one AWQ_MACRO covering GS×8 weights.

    qweights: GS*8 nibbles = GS*4 bytes; scales: 8×FP16 = 16 B; zeros strip:
    128 bits = 16 B (8×INT4 used + 96 bits padding, per §III-A).
    """
    return group_size * 4 + 16 + 16


def macro_count(k: int, n: int, group_size: int) -> int:
    """#macros for a [K, N] linear: one per (K-group, 8 output channels)."""
    if k % group_size or n % 8:
        raise ValueError(f"[{k},{n}] not tileable by GS={group_size}x8")
    return (k // group_size) * (n // 8)


def packed_linear_nbytes(k: int, n: int, group_size: int) -> int:
    """Exact serialized size of one quantized linear in AWQ_MACRO format."""
    return macro_count(k, n, group_size) * awq_macro_nbytes(group_size)


def awq_macro_bytes(q: np.ndarray, scales: np.ndarray, zeros: np.ndarray,
                    group_size: int) -> bytes:
    """Serialize a whole [K, N] quantized linear into AWQ_MACRO strips.

    Layout per macro (paper Fig. 3, one macro = GS rows × 8 output channels):
      [GS*8 nibbles qweights][8 × fp16 scales][8 nibbles zeros + 96-bit pad]
    Nibble order within the qweight strip is row-major over (GS, 8) with
    little-endian nibble packing inside each byte.
    """
    k, n = q.shape
    g = k // group_size
    out = bytearray()
    q = q.astype(np.uint8)
    zeros = zeros.astype(np.uint8)
    scales16 = scales.astype(np.float16)
    for gi in range(g):
        rows = slice(gi * group_size, (gi + 1) * group_size)
        for nj in range(0, n, 8):
            tile = q[rows, nj:nj + 8].reshape(-1)          # GS*8 nibbles
            lo, hi = tile[0::2], tile[1::2]
            out += (lo | (hi << 4)).astype(np.uint8).tobytes()
            out += scales16[gi, nj:nj + 8].tobytes()        # 16 B
            ztile = zeros[gi, nj:nj + 8]
            zlo, zhi = ztile[0::2], ztile[1::2]
            out += (zlo | (zhi << 4)).astype(np.uint8).tobytes()  # 4 B used
            out += b"\x00" * 12                             # 96-bit padding
    return bytes(out)


def parse_awq_macro_bytes(buf: bytes, k: int, n: int, group_size: int):
    """Inverse of :func:`awq_macro_bytes` (round-trip tested)."""
    g = k // group_size
    q = np.zeros((k, n), np.uint8)
    scales = np.zeros((g, n), np.float16)
    zeros = np.zeros((g, n), np.uint8)
    mb = awq_macro_nbytes(group_size)
    idx = 0
    for gi in range(g):
        rows = slice(gi * group_size, (gi + 1) * group_size)
        for nj in range(0, n, 8):
            macro = buf[idx * mb:(idx + 1) * mb]
            idx += 1
            qb = np.frombuffer(macro[: group_size * 4], np.uint8)
            nib = np.empty(group_size * 8, np.uint8)
            nib[0::2] = qb & 0xF
            nib[1::2] = qb >> 4
            q[rows, nj:nj + 8] = nib.reshape(group_size, 8)
            scales[gi, nj:nj + 8] = np.frombuffer(
                macro[group_size * 4: group_size * 4 + 16], np.float16)
            zb = np.frombuffer(
                macro[group_size * 4 + 16: group_size * 4 + 20], np.uint8)
            znib = np.empty(8, np.uint8)
            znib[0::2] = zb & 0xF
            znib[1::2] = zb >> 4
            zeros[gi, nj:nj + 8] = znib
    return q, scales, zeros
