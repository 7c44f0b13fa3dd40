"""Pure-jnp oracles for the Pallas kernels.

Every kernel in this package is validated elementwise against these
references (interpret mode on CPU, sweeping shapes/dtypes). The math here is
the paper's PE dataflow (Fig. 4d): ``w = (q - zero) * scale``, then MAC with
the input activation, accumulated in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.packing import PackedLinear, unpack_int4


def dequant_ref(qweight: jax.Array, scales: jax.Array, zeros: jax.Array,
                group_size: int, dtype=jnp.float32) -> jax.Array:
    """Unpack + dequantize packed weights → float ``[K, N]``."""
    q = unpack_int4(qweight)  # [K, N] int32
    k, n = q.shape
    g = k // group_size
    qg = q.reshape(g, group_size, n).astype(jnp.float32)
    w = (qg - zeros[:, None, :].astype(jnp.float32)) \
        * scales[:, None, :].astype(jnp.float32)
    return w.reshape(k, n).astype(dtype)


def awq_matmul_ref(x: jax.Array, qweight: jax.Array, scales: jax.Array,
                   zeros: jax.Array, group_size: int,
                   compute_dtype=jnp.float32) -> jax.Array:
    """``x [M, K] @ dequant(qweight) [K, N] -> [M, N] float32``."""
    w = dequant_ref(qweight, scales, zeros, group_size, compute_dtype)
    return jnp.dot(x.astype(compute_dtype), w,
                   preferred_element_type=jnp.float32)


def awq_matmul_ref_packed(x: jax.Array, p: PackedLinear,
                          compute_dtype=jnp.float32) -> jax.Array:
    return awq_matmul_ref(x, p.qweight, p.scales, p.zeros, p.group_size,
                          compute_dtype)


def awq_gateup_ref(x: jax.Array, qw_gate, s_gate, z_gate, qw_up, s_up, z_up,
                   group_size: int, compute_dtype=jnp.float32) -> jax.Array:
    """Fused SwiGLU FFN front: ``silu(x @ Wg) * (x @ Wu)`` (paper Table I's
    dominant 51% row, gate+up projections)."""
    g = awq_matmul_ref(x, qw_gate, s_gate, z_gate, group_size, compute_dtype)
    u = awq_matmul_ref(x, qw_up, s_up, z_up, group_size, compute_dtype)
    return jax.nn.silu(g) * u


def gather_pages(leaf, page_table):
    """Head-major pool leaf ``[N, Hkv, P, ...]`` → the slots' logical view
    ``[B, pages_per_slot · P, Hkv, ...]`` through ``page_table [B, n]``."""
    g = jnp.swapaxes(leaf[page_table], 2, 3)        # [B, n, P, Hkv, ...]
    return g.reshape(g.shape[0], g.shape[1] * g.shape[2], *g.shape[3:])


def paged_attention_ref(q, k_pool, ks, v_pool, vs, page_table, pos, *,
                        scale=None):
    """Oracle for the fused dequant + paged-attention decode kernel.

    q [B, Hkv, G, hd]; k/v pools [N, Hkv, P, hd] int8; ks/vs [N, Hkv, P]
    f32 scale strips; page_table [B, pages_per_slot] int32; pos [B] int32
    (inclusive last valid position). Gathers the slot's pages into logical
    order, dequantizes, then runs plain masked softmax attention —
    exactly the jnp fallback path in `models.attention`.
    """
    b, hkv, g, hd = q.shape
    s_slot = page_table.shape[1] * k_pool.shape[2]
    scale = scale if scale is not None else hd ** -0.5
    k = gather_pages(k_pool.astype(jnp.float32)
                     * ks[..., None].astype(jnp.float32), page_table)
    v = gather_pages(v_pool.astype(jnp.float32)
                     * vs[..., None].astype(jnp.float32), page_table)
    sc = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32), k) * scale
    valid = jnp.arange(s_slot)[None, :] <= pos[:, None]    # [B, S]
    sc = jnp.where(valid[:, None, None, :], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bkgs,bskd->bkgd", p, v)


def chunk_visibility_ref(pos, *, s_slot, rpos=None, amask=None,
                         window: int = 0):
    """Shared mask semantics for the generalized chunk attention read.

    Returns the boolean visibility ``[B, C, S_slot]`` of every slot
    position to every in-span query under the three-part rule:

      * **committed span** (``k < pos[b, 0]``): visible when inside the
        sliding window, ``k > rpos[b, i] - window`` (always, if
        ``window == 0``) — the causal watermark test;
      * **in-span** (``pos[b, 0] <= k < pos[b, 0] + C``): visible iff
        ``amask[b, i, k - pos[b, 0]]`` — the explicit ancestor-mask
        block (callers fold any in-span window bound into ``amask``);
      * everything else (future slots, stale table tails): masked.

    ``pos[b, i]`` is token *i*'s KV **slot** position — in-span tokens
    always occupy contiguous slots from the committed watermark
    ``pos[b, 0]`` (``-1`` marks padding). ``rpos`` is the **logical**
    (RoPE/depth) position, defaulting to ``pos``; the two differ only
    for tree-speculation rows, where siblings share a depth but not a
    slot. ``amask=None`` reproduces plain causality: in-span token j
    visible to query i iff ``j <= i`` and token j is not padding.
    """
    b, c = pos.shape
    if rpos is None:
        rpos = pos
    if amask is None:
        tri = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :])
        amask = tri[None] & (pos >= 0)[:, None, :]         # [B, C, C]
        if window:
            amask = amask & (jnp.arange(c)[None, None, :]
                             > jnp.arange(c)[None, :, None] - window)
    base = pos[:, 0][:, None, None]                        # [B, 1, 1]
    k_slot = jnp.arange(s_slot)[None, None, :]             # [1, 1, S]
    committed = k_slot < base
    if window:
        committed = committed & (k_slot > rpos[:, :, None] - window)
    off = k_slot - base                                    # [B, 1, S]
    in_span = (off >= 0) & (off < c)
    offc = jnp.clip(off, 0, c - 1)
    vis_in = jnp.take_along_axis(
        amask.astype(bool), jnp.broadcast_to(offc, (b, c, s_slot)), axis=2)
    return (pos >= 0)[:, :, None] & (committed | (in_span & vis_in))


def paged_attention_chunk_ref(q, k_pool, ks, v_pool, vs, page_table, pos, *,
                              scale=None, rpos=None, amask=None,
                              window: int = 0):
    """Oracle for the multi-query (chunked-prefill) paged-attention kernel.

    q [B, C, Hkv, G, hd] — C queries per batch row (a prefill chunk, a
    speculation tree, or a single decode token at C=1); k/v pools
    [N, Hkv, P, hd] int8 with ks/vs [N, Hkv, P] f32 scale strips;
    page_table [B, pages_per_slot] int32 (one table row per batch row —
    all C queries of a row belong to the same request slot); pos [B, C]
    int32 absolute query **slot** positions, ``-1`` marking padding
    queries (masked everywhere, output zero).

    Visibility follows `chunk_visibility_ref`: committed pages pass the
    causal watermark (+ optional sliding-window) test, in-span keys pass
    through the explicit ``[C, C]`` ancestor-mask block (plain causality
    when ``amask=None``). Rows whose mask is empty — padding queries or
    all-masked ancestor rows — produce exactly 0, matching the kernel's
    ``l == 0`` flush.
    """
    b, c, hkv, g, hd = q.shape
    s_slot = page_table.shape[1] * k_pool.shape[2]
    scale = scale if scale is not None else hd ** -0.5
    k = gather_pages(k_pool.astype(jnp.float32)
                     * ks[..., None].astype(jnp.float32), page_table)
    v = gather_pages(v_pool.astype(jnp.float32)
                     * vs[..., None].astype(jnp.float32), page_table)
    sc = jnp.einsum("bckgd,bskd->bckgs", q.astype(jnp.float32), k) * scale
    vis = chunk_visibility_ref(pos, s_slot=s_slot, rpos=rpos, amask=amask,
                               window=window)              # [B, C, S]
    vism = vis[:, :, None, None, :]
    sc = jnp.where(vism, sc, -1e30)
    # masked-row-exact-zero softmax: rows with an empty mask keep l = 0
    # and flush to 0 instead of averaging garbage
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.where(vism, jnp.exp(sc - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    return jnp.einsum("bckgs,bskd->bckgd", p, v)


def flash_attention_ref(q, k, v, *, scale=None, causal=True,
                        window: int = 0):
    """Oracle for the flash kernel: plain masked softmax attention.

    q [B, H, S, hd], k/v [B, Hkv, S, hd] → [B, H, S, hd] (GQA broadcast).
    """
    b, h, s, hd = q.shape
    hkv = k.shape[1]
    g = h // hkv
    scale = scale if scale is not None else hd ** -0.5
    qg = q.reshape(b, hkv, g, s, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    sc = jnp.einsum("bkgqd,bksd->bkgqs", qg, kf) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    sc = jnp.where(mask[None, None, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", p, vf)
    return o.reshape(b, h, s, hd).astype(q.dtype)
