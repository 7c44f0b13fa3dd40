"""Attention: GQA/MQA/MHA, sliding-window, chunked-long-seq, decode caches.

Execution regimes:

  * train/prefill — q-chunked attention (`attn_chunk` queries at a time, full
    key rows per chunk) so 32k-token prefill never materializes an S×S score
    matrix. Softmax rows are complete per chunk → exact, no online rescaling.
  * decode (full cache) — single-token GEMV attention against a
    ``[B, S_max, Hkv, hd]`` cache. The cache is **sequence-sharded over the
    `model` mesh axis** (SP-decode, DESIGN.md §5); the masked softmax reduces
    over the sharded axis, which XLA lowers to two small all-reduces.
  * decode (ring cache) — sliding-window layers keep a ``[B, W, Hkv, hd]``
    ring buffer; slot ``s`` holds absolute position ``p - ((p - s) mod W)``,
    reconstructed in closed form for masking.
  * paged chunk (serving) — `attention_chunk_paged`: the engine's unified
    prefill/decode step over the page pools (scatter the block's K/V, then
    attend causally per token); single-token paged decode is its C = 1 form.

Everything runs through `layers.linear`, so all four projections quantize
through the paper's AWQ pipeline untouched.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed import constrain
from repro.models import layers
from repro.models.layers import apply_rope, linear, rmsnorm, rope_cos_sin


def attn_init(key, cfg, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "wq": layers.linear_init(ks[0], d, cfg.q_dim, bias=cfg.qkv_bias,
                                 dtype=dtype),
        "wk": layers.linear_init(ks[1], d, cfg.kv_dim, bias=cfg.qkv_bias,
                                 dtype=dtype),
        "wv": layers.linear_init(ks[2], d, cfg.kv_dim, bias=cfg.qkv_bias,
                                 dtype=dtype),
        "wo": layers.linear_init(ks[3], cfg.q_dim, d, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = layers.norm_init(cfg.head_dim, dtype=dtype,
                                       plus_one=cfg.rms_plus_one)
        p["k_norm"] = layers.norm_init(cfg.head_dim, dtype=dtype,
                                       plus_one=cfg.rms_plus_one)
    return p


def _rope_theta(cfg, window: int) -> float:
    if window > 0 and cfg.local_rope_theta:
        return cfg.local_rope_theta
    return cfg.rope_theta


def _rot_dim(cfg) -> int:
    rd = int(cfg.head_dim * cfg.rope_fraction)
    return rd - rd % 2


def _project_qkv(p, x, cfg, positions, window, name):
    """x [..., D] -> q [..., H, hd], k/v [..., Hkv, hd], rope'd + qk-norm'd."""
    nm = (lambda s: None) if name is None else name
    lead = x.shape[:-1]
    q = linear(p["wq"], x, nm("wq")).reshape(*lead, cfg.num_heads,
                                             cfg.head_dim)
    k = linear(p["wk"], x, nm("wk")).reshape(*lead, cfg.num_kv_heads,
                                             cfg.head_dim)
    v = linear(p["wv"], x, nm("wv")).reshape(*lead, cfg.num_kv_heads,
                                             cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, eps=cfg.norm_eps,
                    plus_one=cfg.rms_plus_one)
        k = rmsnorm(p["k_norm"], k, eps=cfg.norm_eps,
                    plus_one=cfg.rms_plus_one)
    rd = _rot_dim(cfg)
    if rd:
        cos, sin = rope_cos_sin(positions, rd, _rope_theta(cfg, window))
        q = apply_rope(q, cos, sin, rd)
        k = apply_rope(k, cos, sin, rd)
    return q, k, v


def _sdpa(q, k, v, q_pos, k_pos, *, causal: bool, window: int,
          scale: float, vis: jax.Array | None = None) -> jax.Array:
    """Grouped scaled-dot-product attention over full key rows.

    q [B, C, Hkv, G, hd]; k/v [B, S, Hkv, hd]; *_pos [B, C]/[B, S] absolute
    positions (k_pos < 0 ⇒ invalid slot). Returns [B, C, Hkv, G, hd].

    An explicit ``vis [B, C, S]`` boolean mask overrides the positional
    causal/window mask entirely (the generalized ancestor-mask read);
    rows whose mask is empty then produce exactly 0, matching the Pallas
    kernel's ``l == 0`` flush.
    """
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    if vis is not None:
        vism = vis[:, None, None, :, :]
        scores = jnp.where(vism, scores, -1e30)
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.where(vism, jnp.exp(scores - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        probs = (p / jnp.where(l == 0.0, 1.0, l)).astype(v.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    mask = k_pos[:, None, :] >= 0
    if causal:
        mask &= k_pos[:, None, :] <= q_pos[:, :, None]
    if window:
        mask &= k_pos[:, None, :] > q_pos[:, :, None] - window
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)


def attention(p, x, cfg, *, positions, window: int = 0, causal: bool = True,
              name=None) -> jax.Array:
    """Train/prefill attention. x [B, S, D] -> [B, S, D]."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions, window, name)
    g = cfg.num_heads // cfg.num_kv_heads
    q = q.reshape(b, s, cfg.num_kv_heads, g, cfg.head_dim)
    q = constrain(q, ("batch", None, "kv_heads", "q_groups", None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    scale = cfg.head_dim ** -0.5

    chunk = cfg.attn_chunk
    msize = 1
    from repro.distributed.sharding import current_mesh
    mesh = current_mesh()
    if mesh is not None:
        msize = mesh.shape.get("model", 1)
    # §Perf C2: when heads don't divide the model axis (smollm 15H, hymba
    # 25H, gemma 8H, …) head-sharding falls back to replication — every
    # model rank would redo the full O(S²) attention. Instead shard the
    # QUERY CHUNKS over `model`: each rank attends its chunks against the
    # (replicated) K/V; the only added comm is the [B,S,q_dim] output
    # gather, ~16× smaller than the replicated compute it removes.
    shard_chunks = (msize > 1 and cfg.num_heads % msize != 0
                    and s % chunk == 0 and (s // chunk) % msize == 0)
    if shard_chunks:
        n_chunks = s // chunk
        qc = q.reshape(b, n_chunks, chunk, cfg.num_kv_heads, g, cfg.head_dim)
        qc = constrain(qc, ("batch", "model", None, None, None, None))
        pc = positions.reshape(b, n_chunks, chunk)
        out = jax.vmap(
            lambda q_i, p_i: _sdpa(q_i, k, v, p_i, positions, causal=causal,
                                   window=window, scale=scale),
            in_axes=(1, 1), out_axes=1)(qc, pc)
        out = out.reshape(b, s, cfg.q_dim)
    elif s > chunk and s % chunk == 0:
        n_chunks = s // chunk
        qc = q.reshape(b, n_chunks, chunk, cfg.num_kv_heads, g, cfg.head_dim)
        qc = jnp.moveaxis(qc, 1, 0)                       # [nc, B, C, ...]
        pc = jnp.moveaxis(positions.reshape(b, n_chunks, chunk), 1, 0)

        def body(_, qp):
            q_i, p_i = qp
            o = _sdpa(q_i, k, v, p_i, positions, causal=causal,
                      window=window, scale=scale)
            return None, o

        _, out = jax.lax.scan(body, None, (qc, pc))
        out = jnp.moveaxis(out, 0, 1).reshape(b, s, cfg.q_dim)
    else:
        out = _sdpa(q, k, v, positions, positions, causal=causal,
                    window=window, scale=scale).reshape(b, s, cfg.q_dim)
    nm = (lambda s_: None) if name is None else name
    return linear(p["wo"], out, nm("wo"))


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------

def init_kv_cache(cfg, batch: int, max_seq: int, window: int,
                  dtype=jnp.bfloat16):
    s = min(window, max_seq) if window else max_seq
    shape = (batch, s, cfg.num_kv_heads, cfg.head_dim)
    if cfg.kv_quant == "int8":
        # paper's bandwidth argument applied to the cache: INT8 codes +
        # per-(position, head) absmax scale — 2.1× fewer cache bytes/step.
        sshape = (batch, s, cfg.num_kv_heads)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros(sshape, jnp.float32),
                "vs": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _kv_quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x [..., hd] → (int8 codes, per-[...] absmax scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequant(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(dtype)


def _ring_positions(pos: jax.Array, w: int) -> jax.Array:
    """Absolute position held by each ring slot; <0 ⇒ not yet written.

    Slot s (0..W-1) at current position ``pos`` (the token being written)
    holds the newest absolute position p ≤ pos with p ≡ s (mod W).
    """
    slots = jnp.arange(w)[None, :]
    p = pos[:, None]
    return p - ((p - slots) % w)


def fill_cache_from_prefill(cache, k, v, positions, window: int):
    """Write prefill keys/values [B, S, ...] into a fresh decode cache."""
    b, s = k.shape[0], k.shape[1]
    quant = "ks" in cache
    if quant:
        k, ks = _kv_quantize(k)
        v, vs = _kv_quantize(v)
    if not window or s <= window:
        out = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
        }
        if quant:
            out["ks"] = jax.lax.dynamic_update_slice(cache["ks"], ks,
                                                     (0, 0, 0))
            out["vs"] = jax.lax.dynamic_update_slice(cache["vs"], vs,
                                                     (0, 0, 0))
        return out
    # ring: keep the last W tokens at slot = pos % W
    kw, vw = k[:, -window:], v[:, -window:]
    pw = positions[:, -window:] % window                  # [B, W]
    bidx = jnp.arange(b)[:, None]
    out = {"k": cache["k"].at[bidx, pw].set(kw.astype(cache["k"].dtype)),
           "v": cache["v"].at[bidx, pw].set(vw.astype(cache["v"].dtype))}
    if quant:
        out["ks"] = cache["ks"].at[bidx, pw].set(ks[:, -window:])
        out["vs"] = cache["vs"].at[bidx, pw].set(vs[:, -window:])
    return out


def init_paged_kv_cache(cfg, num_pages: int, page_size: int,
                        dtype=jnp.bfloat16, kv_quant: str | None = None):
    """Page pool for one layer: ``[num_pages, Hkv, page_size, hd]``.

    Head-major, so one (page, head) is a contiguous ``[P, hd]`` tile — the
    block the paged kernel reads per grid cell.

    Physical pages are normally owned by one request slot; prefix sharing
    lets several slots alias read-only pages (the pager refcounts them).
    Logical order is reconstructed at read time by gathering through the
    per-slot page table. Page 0 is the pager's scratch page — inactive
    slots keep scattering into it so the jit'd decode step never
    re-specializes on batch composition.

    ``kv_quant`` overrides ``cfg.kv_quant`` for the pool only (the serving
    engine uses this to hold int8 pages under a float model config —
    quantize-on-commit / dequant-on-gather): int8 codes plus per-(head,
    position) float32 absmax scale strips ``ks``/``vs`` ``[N, Hkv, P]``.
    """
    kv_quant = cfg.kv_quant if kv_quant is None else kv_quant
    shape = (num_pages, cfg.num_kv_heads, page_size, cfg.head_dim)
    if kv_quant == "int8":
        sshape = (num_pages, cfg.num_kv_heads, page_size)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros(sshape, jnp.float32),
                "vs": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attention_chunk_paged(p, pool, page_table, x, cfg, *, pos, rpos=None,
                          amask=None, window: int = 0, name=None):
    """Token-budget chunk step against a paged KV pool — the unified
    prefill/decode execution path.

    x ``[B, C, D]`` — each batch row is one request slot's contribution to
    this step: a prefill chunk of up to C tokens, a speculation tree, a
    single decode token (remaining positions padded), or nothing (all
    padding). pos ``[B, C]`` int32 absolute KV **slot** positions, ``-1``
    marking padding tokens (in-span tokens always occupy contiguous slots
    from the committed watermark ``pos[b, 0]``); page_table
    ``[B, pages_per_slot]`` int32 (row = slot). Returns (y [B, C, D],
    new pool).

    ``rpos`` is the **logical** position (RoPE angle + window anchor),
    defaulting to ``pos`` — the two differ only for tree-speculation
    rows, where sibling branches share a depth but not a slot. ``amask``
    ``[B, C, C]`` is the explicit intra-chunk ancestor-mask block (plain
    causality when ``None``); ``window`` masks committed positions that
    have slid out of a local-attention layer's window (their pages stay
    resident — the mask, not eviction, enforces locality, which is what
    lets windowed layers share the paged pools with global layers).

    Execution order is scatter-then-gather: every valid token's K/V is
    written into ``pool[table[b, pos // P], :, pos % P]`` first (padding
    redirected to the reserved scratch page 0), then each token attends
    over its slot's pages under the three-part visibility rule of
    `kernels.ref.chunk_visibility_ref`: committed pages pass the causal
    watermark (+ window) test — decode tokens, earlier chunks, and
    **aliased shared-prefix pages**, which are therefore read, never
    recomputed (prefix sharing saves prefill FLOPs, not just memory) —
    and in-span keys route through ``amask``. Stale table entries hold
    positions beyond the watermark + span and are always masked.

    Int8 pools quantize each token on write with the per-(position, head)
    absmax codec — identical to one-shot quantize-on-commit, so chunked
    and one-shot commits produce bit-identical pages — and dequantize at
    the point of use: on TPU via the fused multi-query Pallas kernel
    (`kernels.paged_attention.paged_attention_chunk` — page table in
    scalar-prefetch memory, dequant in VMEM, one page read amortized over
    the whole chunk), elsewhere via the jnp gather below, which doubles
    as the kernel's reference semantics.
    """
    b, c, _ = x.shape
    page_size = pool["k"].shape[2]
    valid = pos >= 0
    logical = pos if rpos is None else rpos
    rope_pos = jnp.where(valid, logical, 0)
    if amask is not None and window:
        # a supplied ancestor mask is authoritative for in-span keys (the
        # kernel applies ``window`` only to committed pages), so fold the
        # in-span locality bound in here — once, above both read paths.
        # Logical positions anchor the bound: tree siblings share a depth.
        amask = (amask.astype(jnp.bool_)
                 & (rope_pos[:, None, :] > rope_pos[:, :, None] - window))
    q, k1, v1 = _project_qkv(p, x, cfg, rope_pos, window,
                             name)                        # [B, C, H(kv), hd]
    k1 = constrain(k1, ("batch", None, "kv_heads", None))
    v1 = constrain(v1, ("batch", None, "kv_heads", None))
    slot_pos = jnp.where(valid, pos, 0)
    phys = jnp.take_along_axis(page_table, slot_pos // page_size, axis=1)
    phys = jnp.where(valid, phys, 0)          # padding → scratch page 0
    offset = jnp.where(valid, slot_pos % page_size, 0)
    fp, fo = phys.reshape(-1), offset.reshape(-1)
    quant = "ks" in pool
    new_pool = {}
    if quant:
        k1, ks1 = _kv_quantize(k1)
        v1, vs1 = _kv_quantize(v1)
        new_pool["ks"] = pool["ks"].at[fp, :, fo].set(
            ks1.reshape(b * c, cfg.num_kv_heads))
        new_pool["vs"] = pool["vs"].at[fp, :, fo].set(
            vs1.reshape(b * c, cfg.num_kv_heads))
    kv_shape = (b * c, cfg.num_kv_heads, cfg.head_dim)
    new_pool["k"] = pool["k"].at[fp, :, fo].set(
        k1.reshape(kv_shape).astype(pool["k"].dtype))
    new_pool["v"] = pool["v"].at[fp, :, fo].set(
        v1.reshape(kv_shape).astype(pool["v"].dtype))
    # keep the pool mesh-sharded through the scatter (pools stripe over KV
    # heads on the `model` axis — `distributed.paged_cache_pspec`); without
    # the constraint GSPMD may gather the whole pool onto every device
    new_pool["k"] = constrain(new_pool["k"], (None, "kv_heads", None, None))
    new_pool["v"] = constrain(new_pool["v"], (None, "kv_heads", None, None))
    if quant:
        new_pool["ks"] = constrain(new_pool["ks"], (None, "kv_heads", None))
        new_pool["vs"] = constrain(new_pool["vs"], (None, "kv_heads", None))

    g = cfg.num_heads // cfg.num_kv_heads
    nm = (lambda s_: None) if name is None else name
    if quant:
        from repro.distributed.sharding import current_mesh
        from repro.kernels import paged_attention as paged_kernel
        if paged_kernel.supported():
            qk = q.reshape(b, c, cfg.num_kv_heads, g, cfg.head_dim)
            mesh = current_mesh()
            if (mesh is not None and mesh.shape.get("model", 1) > 1
                    and cfg.num_kv_heads % mesh.shape["model"] == 0):
                # tensor-parallel: shard_map over the head axis — each
                # device runs the unmodified kernel on its local heads
                out = paged_kernel.paged_attention_chunk_sharded(
                    qk, new_pool["k"], new_pool["ks"], new_pool["v"],
                    new_pool["vs"], page_table, pos, mesh=mesh,
                    rpos=rpos, amask=amask, window=window,
                    scale=cfg.head_dim ** -0.5)
            else:
                out = paged_kernel.paged_attention_chunk(
                    qk, new_pool["k"], new_pool["ks"], new_pool["v"],
                    new_pool["vs"], page_table, pos,
                    rpos=rpos, amask=amask, window=window,
                    scale=cfg.head_dim ** -0.5)
            out = out.reshape(b, c, cfg.q_dim).astype(
                jnp.dtype(cfg.activation_dtype))
            return linear(p["wo"], out, nm("wo")), new_pool

    # gather-based read: page table → logical [B, S_slot, Hkv, hd] view
    # (the gathered view inherits the pool's head sharding, so each device
    # gathers and attends only its local heads — the reference semantics
    # of the shard_map'd kernel above)
    from repro.kernels.ref import gather_pages
    s_slot = page_table.shape[1] * page_size
    ck = gather_pages(new_pool["k"], page_table)
    cv = gather_pages(new_pool["v"], page_table)
    ck = constrain(ck, ("batch", None, "kv_heads", None))
    cv = constrain(cv, ("batch", None, "kv_heads", None))
    adt = jnp.dtype(cfg.activation_dtype)
    if quant:
        ks = gather_pages(new_pool["ks"], page_table)
        vs = gather_pages(new_pool["vs"], page_table)
        ck = _kv_dequant(ck, ks, adt)
        cv = _kv_dequant(cv, vs, adt)
    k_pos = jnp.broadcast_to(jnp.arange(s_slot)[None, :], (b, s_slot))
    qg = q.reshape(b, c, cfg.num_kv_heads, g, cfg.head_dim)
    qg = constrain(qg, ("batch", None, "kv_heads", None, None))
    if rpos is None and amask is None and not window:
        # plain linear chunk: the arange causal mask is exact (see above)
        out = _sdpa(qg, ck, cv, pos, k_pos, causal=True, window=0,
                    scale=cfg.head_dim ** -0.5)
    else:
        from repro.kernels.ref import chunk_visibility_ref
        vis = chunk_visibility_ref(pos, s_slot=s_slot, rpos=rpos,
                                   amask=amask, window=window)
        out = _sdpa(qg, ck, cv, pos, k_pos, causal=True, window=0,
                    scale=cfg.head_dim ** -0.5, vis=vis)
    out = out.reshape(b, c, cfg.q_dim)
    y = linear(p["wo"], out, nm("wo"))
    return y, new_pool


def attention_decode_paged(p, pool, page_table, x, cfg, *, pos,
                           window: int = 0, name=None):
    """Single-token decode against a paged KV pool: the C = 1 form of
    `attention_chunk_paged` (one implementation serves both regimes).

    pool leaves ``[num_pages, P, ...]``; page_table ``[B, pages_per_slot]``
    int32; x ``[B, D]``, pos ``[B]``. Returns (y [B, D], new pool). The
    chunk path's causal arange mask reduces to exactly the old
    ``k_pos <= pos`` decode mask at C = 1, so the gathered logical view
    stays laid out like the dense ``[B, S, Hkv, hd]`` cache and paged and
    dense decode produce bitwise-identical attention outputs (same kv
    regime). A sliding ``window`` masks committed positions at or below
    ``pos - window`` in the paged read (pages stay resident).
    """
    y, new_pool = attention_chunk_paged(p, pool, page_table, x[:, None],
                                        cfg, pos=pos[:, None], window=window,
                                        name=name)
    return y[:, 0], new_pool


def attention_decode(p, cache, x, cfg, *, pos, window: int = 0, name=None):
    """Single-token decode. x [B, D], pos [B] -> (y [B, D], new cache).

    Cache layout + sharding: see module docstring. The update is a per-sample
    scatter (continuous batching keeps per-request positions).
    """
    b = x.shape[0]
    q, k1, v1 = _project_qkv(p, x, cfg, pos, window, name)  # [B, H(kv), hd]
    slot = (pos % window) if window else pos
    bidx = jnp.arange(b)
    quant = "ks" in cache
    new_cache = {}
    if quant:
        k1, ks1 = _kv_quantize(k1)
        v1, vs1 = _kv_quantize(v1)
        new_cache["ks"] = constrain(
            cache["ks"].at[bidx, slot].set(ks1),
            ("batch", "cache_seq", None))
        new_cache["vs"] = constrain(
            cache["vs"].at[bidx, slot].set(vs1),
            ("batch", "cache_seq", None))
    ck = cache["k"].at[bidx, slot].set(k1.astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, slot].set(v1.astype(cache["v"].dtype))
    ck = constrain(ck, ("batch", "cache_seq", None, None))
    cv = constrain(cv, ("batch", "cache_seq", None, None))
    new_cache["k"], new_cache["v"] = ck, cv
    adt = jnp.dtype(cfg.activation_dtype)
    if quant:
        # dequant at point of use — on TPU this fuses into the attention
        # dots (same role as the AWQ weight dequant in the MAC pipeline)
        ck = _kv_dequant(ck, new_cache["ks"], adt)
        cv = _kv_dequant(cv, new_cache["vs"], adt)

    if window:
        k_pos = _ring_positions(pos, ck.shape[1])
    else:
        s_max = ck.shape[1]
        k_pos = jnp.where(jnp.arange(s_max)[None, :] <= pos[:, None],
                          jnp.arange(s_max)[None, :], -1)

    g = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(b, 1, cfg.num_kv_heads, g, cfg.head_dim)
    out = _sdpa(qg, ck, cv, pos[:, None], k_pos, causal=bool(window),
                window=window, scale=cfg.head_dim ** -0.5)
    out = out.reshape(b, cfg.q_dim)
    nm = (lambda s_: None) if name is None else name
    y = linear(p["wo"], out, nm("wo"))
    return y, new_cache
