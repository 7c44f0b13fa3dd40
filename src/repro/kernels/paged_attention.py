"""Pallas TPU fused dequant + paged-attention kernel (decode + chunked
prefill).

Why it exists: with int8 KV pages (§Perf A4 at serving scale) the serving
hot loop is bandwidth-bound on the page pool. The pure-jnp path in
`models.attention` gathers the slot's pages into a logical
``[B, S_slot, Hkv, hd]`` view, dequantizes it, then attends — XLA
materializes the gathered + dequantized (bf16) copy in HBM, paying ~2.5×
the pool's int8 byte traffic. This kernel reads the int8 codes and their
float32 scale strips page-by-page straight out of the pool (the page
table rides in scalar-prefetch memory and drives the BlockSpec index
maps — vLLM-TPU style), dequantizes in VMEM, and carries online softmax
state across the page grid axis, so nothing but the final output ever
leaves VMEM in float.

Two entry points over one kernel body:

  * `paged_attention_chunk` — **multi-query blocks** (chunked prefill):
    ``C`` queries per batch row share one page-table row and are masked
    causally against *per-token* absolute positions, so one page read is
    amortized over the whole chunk — the compute-density win that makes
    hybrid prefill+decode steps pay for themselves.
  * `paged_attention` — the single-token decode form (``C = 1``), kept as
    the stable API for the decode hot path and the kernel test suite.

Layout: q ``[B, C, Hkv, G, hd]`` (head = kv_head·G + group), head-major
pools ``[N, Hkv, P, hd]`` int8 with scales ``[N, Hkv, P]`` f32, page_table
``[B, pages_per_slot]`` int32, pos ``[B, C]`` int32 (inclusive last valid
absolute position per query; ``-1`` = padding query, fully masked). Grid
``(B, Hkv, pages_per_slot)``, pages innermost (accumulation axis); the
C·G query rows of a (batch, kv-head) cell ride the MXU together.

Off-TPU the wrappers drop to `kernels.ref.paged_attention_chunk_ref`
(numerically equal up to online-softmax reassociation); interpret mode
runs the kernel body as a CPU program for the allclose sweeps in
tests/test_paged_attention.py and tests/test_chunked_prefill.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def supported() -> bool:
    """Whether the compiled kernel path should be used for decode."""
    return jax.default_backend() == "tpu"


def _paged_attn_kernel(tables_ref, pos_ref, rpos_ref,  # scalar prefetch
                       q_ref, k_ref, ks_ref, v_ref, vs_ref, am_ref,
                       o_ref, m_ref, l_ref, acc_ref, *,
                       page_size: int, n_blocks: int, n_chunk: int,
                       n_groups: int, scale: float, window: int):
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                    # [gp, hd]
    k = k_ref[0, 0].astype(jnp.float32)                    # [P, hd] codes
    v = v_ref[0, 0].astype(jnp.float32)
    # fused dequant, VMEM-only: the per-(head, position) scale is constant
    # along hd, so it scales score columns (K) and probability columns (V)
    # as a [1, P] row instead of rescaling the [P, hd] code tile. The
    # scale block holds every head's strip ([Hkv, P], a legal tile);
    # this cell's row is picked with a mask-and-sum over the head axis.
    ks_all = ks_ref[0].astype(jnp.float32)                 # [Hkv, P]
    vs_all = vs_ref[0].astype(jnp.float32)
    if ks_all.shape[0] == 1:
        ks_row, vs_row = ks_all, vs_all
    else:
        mine = jax.lax.broadcasted_iota(jnp.int32, ks_all.shape, 0) == h
        ks_row = jnp.sum(jnp.where(mine, ks_all, 0.0), axis=0, keepdims=True)
        vs_row = jnp.sum(jnp.where(mine, vs_all, 0.0), axis=0, keepdims=True)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) \
        * (ks_row * scale)
    # per-row query position: row r of the q block is chunk token r // G.
    # pos lives in SMEM (scalar prefetch); a vector gather out of SMEM is
    # not expressible, so select it with a static unroll over the (small,
    # compile-time) chunk length — padded rows keep -1 and mask everything.
    rows = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0) \
        // n_groups                                        # [gp, 1] chunk idx
    q_pos = jnp.full((s.shape[0], 1), -1, jnp.int32)
    for cc in range(n_chunk):
        q_pos = jnp.where(rows == cc, pos_ref[b * n_chunk + cc], q_pos)
    k_pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)                             # [gp, P]
    # three-part visibility (see kernels.ref.chunk_visibility_ref):
    #   committed pages — causal watermark test against the row base
    #   (everything below pos[b, 0] is committed KV), optionally bounded
    #   below by the sliding window on the row's *logical* position;
    #   in-span keys — the explicit [C, C] ancestor-mask block, selected
    #   per slot offset with a static unroll (no VMEM gathers on TPU);
    #   padding rows (q_pos = -1) — masked everywhere.
    base = pos_ref[b * n_chunk]
    committed = k_pos < base
    if window:
        r_pos = jnp.full((s.shape[0], 1), -1, jnp.int32)
        for cc in range(n_chunk):
            r_pos = jnp.where(rows == cc, rpos_ref[b * n_chunk + cc], r_pos)
        committed = committed & (k_pos > r_pos - window)
    am = am_ref[0]                                         # [gp, C] f32
    in_span = jnp.zeros(s.shape, jnp.bool_)
    for t in range(n_chunk):
        in_span = in_span | ((k_pos == base + t) & (am[:, t:t + 1] > 0.5))
    s = jnp.where((q_pos >= 0) & (committed | in_span), s, NEG_INF)

    m_prev = m_ref[...]                                    # [gp, 128] replicated
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1)[:, None]                    # [gp, 1]
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)
    # masked entries must contribute exactly 0: for a fully masked row
    # (padding query, q_pos = -1) m_new is still NEG_INF, so the plain
    # exp(s - m) would be exp(0) = 1 per key and the row would silently
    # average v. Valid rows are unchanged (exp(NEG_INF - m) underflows
    # to 0 anyway); fully masked rows keep l = 0 and flush to 0.
    p = jnp.where(s > NEG_INF * 0.5,
                  jnp.exp(s - m_new[:, :1]), 0.0)          # [gp, P]
    l_new = l_prev * alpha + jnp.broadcast_to(
        jnp.sum(p, axis=1)[:, None], l_prev.shape)
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
        p * vs_row, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == n_blocks - 1)
    def _flush():
        l = l_ref[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)                    # fully masked row
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def default_amask(pos: jax.Array, window: int = 0) -> jax.Array:
    """Plain-causal ancestor mask for a linear chunk: in-span token j is
    visible to query i iff ``j <= i`` and token j is not padding, with
    the in-span half of any sliding-window bound folded in (committed
    pages get their window test inside the kernel)."""
    c = pos.shape[1]
    tri = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    am = tri[None] & (pos >= 0)[:, None, :]                # [B, C, C]
    if window:
        am = am & (jnp.arange(c)[None, None, :]
                   > jnp.arange(c)[None, :, None] - window)
    return am


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "window"))
def paged_attention_chunk(q: jax.Array, k_pool: jax.Array, ks: jax.Array,
                          v_pool: jax.Array, vs: jax.Array,
                          page_table: jax.Array, pos: jax.Array, *,
                          scale: float | None = None,
                          rpos: jax.Array | None = None,
                          amask: jax.Array | None = None,
                          window: int = 0,
                          interpret: bool = False) -> jax.Array:
    """Fused dequant + multi-query masked attention over int8 KV pages.

    q ``[B, C, Hkv, G, hd]`` — C queries per row (prefill chunk, token
    tree, or decode at C = 1); k/v pools ``[N, Hkv, P, hd]`` int8; ks/vs
    ``[N, Hkv, P]`` f32; page_table ``[B, pages_per_slot]`` int32 (one
    row per batch row — all C queries of a row read the same slot's
    pages); pos ``[B, C]`` int32 per-query inclusive **slot** positions
    (``-1`` ⇒ padding query, output 0): in-span tokens always occupy
    contiguous slots from the committed watermark ``pos[b, 0]``.
    Returns ``[B, C, Hkv, G, hd]`` float32.

    Mask semantics (`kernels.ref.chunk_visibility_ref` is the oracle):
    committed pages — everything below ``pos[b, 0]`` — pass the causal
    watermark test, bounded below by ``k > rpos[b, i] - window`` when a
    sliding ``window`` is set (``rpos`` is the row's logical/RoPE
    position, defaulting to ``pos``; the two differ only for tree rows).
    In-span keys route through the explicit ``[B, C, C]`` ancestor-mask
    block ``amask`` (plain causality when ``None``), which lets one
    kernel serve linear chunks, speculation trees, and windowed reads.
    Stale table tails and the scratch page sit above the watermark and
    outside the span, so they never leak into the softmax.
    """
    b, c, hkv, g, hd = q.shape
    page_size = k_pool.shape[2]
    n_blocks = page_table.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    if rpos is None:
        rpos = pos
    if amask is None:
        amask = default_amask(pos, window)
    # expand query rows over GQA groups (row r = query r // G) and pad to
    # the same gp row quantum as q — padded rows are all-masked anyway
    am = jnp.repeat(amask.astype(jnp.float32), g, axis=1)  # [B, C·G, C]
    # fold the chunk into the row axis: row r = query (r // G) group (r % G);
    # pad rows to the fp32 sublane quantum so tiny chunks (C·G < 8) still
    # map onto full tiles — padded rows carry pos -1 and are sliced off
    rows = c * g
    gp = max(8, rows)
    qr = jnp.moveaxis(q, 1, 2).reshape(b, hkv, rows, hd)
    if gp != rows:
        qr = jnp.concatenate(
            [qr, jnp.zeros((b, hkv, gp - rows, hd), qr.dtype)], axis=2)
        am = jnp.concatenate(
            [am, jnp.zeros((b, gp - rows, c), am.dtype)], axis=1)

    grid = (b, hkv, n_blocks)
    # one head's [P, hd] code tile of the page per grid cell (the last two
    # block dims are the array's own, as Mosaic requires); the scale block
    # takes the page's whole [Hkv, P] strip for the same reason
    kv_spec = pl.BlockSpec(
        (1, 1, page_size, hd),
        lambda bi, hi, ji, tables, pos_, rpos_, _nb=n_blocks:
        (tables[bi * _nb + ji], hi, 0, 0))
    scale_spec = pl.BlockSpec(
        (1, hkv, page_size),
        lambda bi, hi, ji, tables, pos_, rpos_, _nb=n_blocks:
        (tables[bi * _nb + ji], 0, 0))
    kernel = functools.partial(_paged_attn_kernel, page_size=page_size,
                               n_blocks=n_blocks, n_chunk=c, n_groups=g,
                               scale=scale, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, gp, hd),
                         lambda bi, hi, ji, tables, pos_, rpos_:
                         (bi, hi, 0, 0)),
            kv_spec, scale_spec, kv_spec, scale_spec,
            pl.BlockSpec((1, gp, c),
                         lambda bi, hi, ji, tables, pos_, rpos_:
                         (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, hd),
                               lambda bi, hi, ji, tables, pos_, rpos_:
                               (bi, hi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((gp, 128), jnp.float32),
                        pltpu.VMEM((gp, 128), jnp.float32),
                        pltpu.VMEM((gp, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, hd), jnp.float32),
        interpret=interpret,
        name="paged_attention_chunk",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(page_table.reshape(-1).astype(jnp.int32),
      pos.reshape(-1).astype(jnp.int32),
      rpos.reshape(-1).astype(jnp.int32),
      qr, k_pool, ks, v_pool, vs, am)
    out = out[:, :, :rows].reshape(b, hkv, c, g, hd)
    return jnp.moveaxis(out, 2, 1)


def paged_attention_chunk_sharded(q: jax.Array, k_pool: jax.Array,
                                  ks: jax.Array, v_pool: jax.Array,
                                  vs: jax.Array, page_table: jax.Array,
                                  pos: jax.Array, *, mesh,
                                  scale: float | None = None,
                                  rpos: jax.Array | None = None,
                                  amask: jax.Array | None = None,
                                  window: int = 0,
                                  interpret: bool = False) -> jax.Array:
    """Tensor-parallel form: the chunk kernel under `shard_map` over the
    KV-head axis of the ``model`` mesh axis.

    KV heads are independent throughout — the online softmax, the
    watermark/ancestor mask, and the dequant all run per (batch, kv-head)
    grid cell — so each mesh shard simply runs the unmodified kernel body
    over its local ``Hkv / |model|`` heads of the pool
    (`distributed.paged_cache_pspec` stripes the pools the same way) with
    ZERO cross-device communication inside the kernel; the output
    concatenates back along heads. Page tables, positions, and the
    ancestor-mask block are replicated (page IDs and mask bits are
    device-agnostic).
    """
    from jax.sharding import PartitionSpec as P

    if mesh.shape.get("model", 1) == 1:
        return paged_attention_chunk(q, k_pool, ks, v_pool, vs, page_table,
                                     pos, scale=scale, rpos=rpos,
                                     amask=amask, window=window,
                                     interpret=interpret)
    if rpos is None:
        rpos = pos
    if amask is None:
        amask = default_amask(pos, window)
    head = P(None, "model", None)                       # [N, Hkv, P]
    return jax.shard_map(
        lambda q_, k_, ks_, v_, vs_, t_, p_, rp_, am_: paged_attention_chunk(
            q_, k_, ks_, v_, vs_, t_, p_, scale=scale, rpos=rp_, amask=am_,
            window=window, interpret=interpret),
        mesh=mesh,
        in_specs=(P(None, None, "model", None, None), P(*head, None), head,
                  P(*head, None), head, P(None, None), P(None, None),
                  P(None, None), P(None, None, None)),
        out_specs=P(None, None, "model", None, None),
        check_vma=False,
    )(q, k_pool, ks, v_pool, vs, page_table, pos, rpos, amask)


def paged_attention(q: jax.Array, k_pool: jax.Array, ks: jax.Array,
                    v_pool: jax.Array, vs: jax.Array,
                    page_table: jax.Array, pos: jax.Array, *,
                    scale: float | None = None,
                    window: int = 0,
                    interpret: bool = False) -> jax.Array:
    """Single-token decode form: q ``[B, Hkv, G, hd]``, pos ``[B]``.

    Thin wrapper over `paged_attention_chunk` with a chunk of one — the
    decode hot path and the chunked-prefill path share one kernel body.
    At C = 1 slot and logical positions coincide, so ``pos`` serves as
    both the watermark and the window anchor. Returns ``[B, Hkv, G, hd]``
    float32.
    """
    out = paged_attention_chunk(q[:, None], k_pool, ks, v_pool, vs,
                                page_table, pos[:, None], scale=scale,
                                window=window, interpret=interpret)
    return out[:, 0]
