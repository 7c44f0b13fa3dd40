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
``(B, ceil(pages_per_slot / ppb))``, page blocks innermost (accumulation
axis): one step reads ``ppb`` whole pages — every KV head's codes and
scale strips — builds the visibility mask of the block once, and runs the
C·G query rows of each KV head over the block's ``ppb·P`` keys on the
MXU. ``ppb`` (`pages_per_block`) follows from the shapes alone. A step
costs a fixed overhead besides its bytes, so fewer, larger steps are what
bring the kernel toward its bandwidth bound.

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


# Pages per grid step: at most this many, and fewer where one page's
# buffers and f32 working set would overrun the VMEM budget below. Each
# page of a block is four operands of the call (see
# `paged_attention_chunk`) whose index maps every step variant lowers
# at set-up. 8 pages halve those operands against 16; on a TPU v5e the
# kernel then runs as fast for one-token rows and up to a fifth slower
# for 16-token chunks.
_MAX_PAGES_PER_BLOCK = 8
_VMEM_BUDGET_BYTES = 4 * 2**20


def pages_per_block(n_pages: int, page_size: int, hkv: int, hd: int,
                    rows: int) -> int:
    """Pages one grid step reads, from shapes alone: each page costs its
    double-buffered int8 K/V codes and f32 scale strips for every KV head,
    every head's f32 dequantized K/V rows, and one head's score columns."""
    per_page = (2 * 2 * hkv * page_size * (hd + 4)
                + 4 * page_size * (2 * hkv * hd + 2 * rows))
    return max(1, min(n_pages, _MAX_PAGES_PER_BLOCK,
                      _VMEM_BUDGET_BYTES // per_page))


def _paged_attn_kernel(tables_ref, pos_ref, rpos_ref,  # scalar prefetch
                       q_ref, *refs, page_size: int, ppb: int,
                       n_chunk: int, n_groups: int, scale: float,
                       window: int):
    # operands: page 0's k, ks, v, vs, then amask, then the k, ks, v, vs
    # of pages 1..ppb-1 (see `paged_attention_chunk`); then the output and
    # the online-softmax scratch, one slab per KV head
    am_ref = refs[4]
    k_refs, ks_refs, v_refs, vs_refs = zip(
        refs[:4], *(refs[1 + 4 * i:5 + 4 * i] for i in range(1, ppb)))
    o_ref, m_ref, l_ref, acc_ref = refs[1 + 4 * ppb:]
    hkv, gp = q_ref.shape[1], q_ref.shape[2]
    keys = ppb * page_size
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the visibility mask of the whole [C·G, ppb·P] block, built once and
    # shared by every KV head. Row r of the q block is chunk token r // G;
    # pos lives in SMEM (scalar prefetch) and a vector gather out of SMEM
    # is not expressible, so it is selected with a static unroll over the
    # (small, compile-time) chunk length — padded rows keep -1.
    rows = jax.lax.broadcasted_iota(jnp.int32, (gp, 1), 0) // n_groups
    q_pos = jnp.full((gp, 1), -1, jnp.int32)
    for cc in range(n_chunk):
        q_pos = jnp.where(rows == cc, pos_ref[b * n_chunk + cc], q_pos)
    k_pos = j * keys + jax.lax.broadcasted_iota(jnp.int32, (gp, keys), 1)
    # three-part visibility (see kernels.ref.chunk_visibility_ref):
    #   committed pages — causal watermark test against the row base
    #   (everything below pos[b, 0] is committed KV), optionally bounded
    #   below by the sliding window on the row's *logical* position;
    #   in-span keys — the explicit [C, C] ancestor-mask block, routed to
    #   the key columns by a one-hot [C, ppb·P] of slot offsets on the MXU
    #   (no VMEM gathers on TPU; exact, one 1 at most per key column);
    #   padding rows (q_pos = -1) — masked everywhere. Keys past the
    #   bucket (a last block that overhangs it) lie above the watermark
    #   and outside the span, so they are masked with the rest.
    base = pos_ref[b * n_chunk]
    committed = k_pos < base
    if window:
        r_pos = jnp.full((gp, 1), -1, jnp.int32)
        for cc in range(n_chunk):
            r_pos = jnp.where(rows == cc, rpos_ref[b * n_chunk + cc], r_pos)
        committed = committed & (k_pos > r_pos - window)
    offset = (j * keys - base
              + jax.lax.broadcasted_iota(jnp.int32, (n_chunk, keys), 1))
    onehot = (offset == jax.lax.broadcasted_iota(
        jnp.int32, (n_chunk, keys), 0)).astype(jnp.float32)  # [C, ppb·P]
    in_span = jax.lax.dot_general(
        am_ref[0], onehot, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) > 0.5          # [gp, ppb·P]
    visible = (q_pos >= 0) & (committed | in_span)

    # the block's codes, every head's, cast to f32 and laid side by side
    # once per step (one read of each page)
    kb = jnp.concatenate([r[0].astype(jnp.float32) for r in k_refs],
                         axis=1)                           # [Hkv, ppb·P, hd]
    vb = jnp.concatenate([r[0].astype(jnp.float32) for r in v_refs], axis=1)
    # fused dequant, VMEM-only: the per-(head, position) scale is constant
    # along hd, so it scales score columns (K) and probability columns (V)
    # as a [1, ppb·P] row instead of rescaling the [ppb·P, hd] code block
    ks_rows = jnp.concatenate([r[0] for r in ks_refs],
                              axis=1) * scale              # [Hkv, ppb·P]
    vs_rows = jnp.concatenate([r[0] for r in vs_refs], axis=1)

    for h in range(hkv):
        q = q_ref[0, h].astype(jnp.float32)                # [gp, hd]
        s = jax.lax.dot_general(q, kb[h],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * ks_rows[h:h + 1]
        s = jnp.where(visible, s, NEG_INF)

        m_prev = m_ref[h]                                  # [gp, 128] replicated
        l_prev = l_ref[h]
        m_cur = jnp.max(s, axis=1)[:, None]                # [gp, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        # masked entries must contribute exactly 0: for a fully masked
        # row (padding query, q_pos = -1) m_new is still NEG_INF, so the
        # plain exp(s - m) would be exp(0) = 1 per key and the row would
        # silently average v. Valid rows are unchanged (exp(NEG_INF - m)
        # underflows to 0 anyway); fully masked rows keep l = 0 and flush
        # to 0.
        p = jnp.where(s > NEG_INF * 0.5,
                      jnp.exp(s - m_new[:, :1]), 0.0)      # [gp, ppb·P]
        l_ref[h] = l_prev * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1)[:, None], l_prev.shape)
        acc_ref[h] = acc_ref[h] * alpha[:, :1] + jax.lax.dot_general(
            p * vs_rows[h:h + 1], vb[h],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        for h in range(hkv):
            l = l_ref[h][:, :1]
            l = jnp.where(l == 0.0, 1.0, l)                # fully masked row
            o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


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

    # one grid step per (row, block of ppb pages) over every KV head; the
    # pools are passed once per page of the block, each with its own
    # page-table index map, so the pipeline fetches the block's pages
    # (whole [Hkv, P, hd] pages and [Hkv, P] scale strips: the last two
    # block dims are the array's own, as Mosaic requires) while the step
    # before computes. (The pools cannot stay in HBM behind copies started
    # by hand: Mosaic refuses to slice a page out of an HBM ref whose last
    # dim is under its 128-lane tile — hd 64, and the P = 16 scale
    # strips; `tests/test_tpu_compile.py` keeps that refusal.) A last
    # block that overhangs the bucket re-reads the row's last page,
    # masked inside the kernel.
    ppb = pages_per_block(n_blocks, page_size, hkv, hd, gp)
    n_steps = pl.cdiv(n_blocks, ppb)

    # (index maps are traced once per operand and step variant: lax ops
    # keep that cheap, and only a page that can overhang is clamped)
    def page_spec(i, shape):
        overhangs = (n_steps - 1) * ppb + i >= n_blocks

        def index(bi, ji, tables, pos_, rpos_):
            col = jax.lax.add(jax.lax.mul(ji, ppb), i)
            if overhangs:
                col = jax.lax.min(col, n_blocks - 1)
            return ((tables[jax.lax.add(jax.lax.mul(bi, n_blocks), col)],)
                    + (0,) * (len(shape) - 1))
        return pl.BlockSpec(shape, index)

    def page_specs(i):                                   # k, ks, v, vs
        kv = page_spec(i, (1, hkv, page_size, hd))
        sc = page_spec(i, (1, hkv, page_size))
        return [kv, sc, kv, sc]

    kernel = functools.partial(_paged_attn_kernel, page_size=page_size,
                               ppb=ppb, n_chunk=c,
                               n_groups=g, scale=scale, window=window)
    row_spec = pl.BlockSpec((1, hkv, gp, hd),
                            lambda bi, ji, tables, pos_, rpos_:
                            (bi, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_steps),
        in_specs=[row_spec, *page_specs(0),
                  pl.BlockSpec((1, gp, c),
                               lambda bi, ji, tables, pos_, rpos_:
                               (bi, 0, 0)),
                  *(s for i in range(1, ppb) for s in page_specs(i))],
        out_specs=row_spec,
        scratch_shapes=[pltpu.VMEM((hkv, gp, 128), jnp.float32),
                        pltpu.VMEM((hkv, gp, 128), jnp.float32),
                        pltpu.VMEM((hkv, gp, hd), jnp.float32)],
    )
    extra = [k_pool, ks, v_pool, vs] * (ppb - 1)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, hd), jnp.float32),
        interpret=interpret,
        name="paged_attention_chunk",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(page_table.reshape(-1).astype(jnp.int32),
      pos.reshape(-1).astype(jnp.int32),
      rpos.reshape(-1).astype(jnp.int32),
      qr, k_pool, ks, v_pool, vs, am, *extra)
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

    KV heads are independent throughout — the online softmax and the
    dequant run per KV head, and the watermark/ancestor mask depends on
    the row alone — so each mesh shard simply runs the unmodified kernel
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
