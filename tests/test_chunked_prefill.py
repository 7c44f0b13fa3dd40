"""Chunked prefill: token identity vs one-shot prefill and sequential
generate() across chunk sizes, multi-query kernel vs oracle, scheduler
token-budget semantics against a fake executor."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.kernels.paged_attention import (paged_attention_chunk,
                                           pages_per_block)
from repro.kernels.ref import paged_attention_chunk_ref
from repro.models import build_model
from repro.models.attention import _kv_quantize
from repro.serving import GenerationEngine
from repro.serving.kv_pager import KVPager, PagerConfig
from repro.serving.scheduler import Request, Scheduler


@pytest.fixture(scope="module")
def model_and_params():
    cfg = C.get_smoke_config("qwen25-05b")
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _engine(m, params, **kw):
    kw.setdefault("max_seq", 64)
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    return GenerationEngine(m, params, **kw)


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


# ---------------------------------------------------------------------------
# End-to-end identity: chunked ≡ one-shot ≡ sequential generate()
# ---------------------------------------------------------------------------

# page_size=8: page-aligned chunk, two non-aligned chunks, chunk > prompt
@pytest.mark.parametrize("chunk", [8, 3, 5, 64])
def test_chunked_matches_oneshot_and_generate(model_and_params, chunk):
    cfg, m, params = model_and_params
    prompts = _prompts(cfg, (5, 12, 9, 17, 7, 21), seed=1)

    def serve(**kw):
        eng = _engine(m, params, **kw)
        rids = [eng.submit(p, 8) for p in prompts]
        out = eng.drain()
        assert eng._scheduler.pager.pages_in_use == 0
        return [list(out[r]) for r in rids], eng

    chunked, eng_c = serve(prefill_chunk=chunk)
    oneshot, eng_o = serve(chunked_prefill=False)
    assert chunked == oneshot
    assert eng_c._scheduler.chunked and not eng_o._scheduler.chunked
    # every prompt token ran through the model exactly once (no sharing)
    assert eng_c._scheduler.stats.prefill_tokens == sum(map(len, prompts))
    assert eng_c._scheduler.stats.prefill_tokens_skipped == 0
    for p, stream in zip(prompts, chunked):
        ref = eng_o.generate({"tokens": jnp.asarray(p)[None, :]}, 8)[0]
        np.testing.assert_array_equal(stream, ref[: len(stream)])


def test_chunked_shared_prefix_identical_and_skips_flops(model_and_params):
    """Chunks straddling the shared-prefix boundary: the follower starts
    mid-page after its aliased pages and its streams stay token-identical
    to unshared chunked and to one-shot serving."""
    cfg, m, params = model_and_params
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, cfg.vocab_size, (19,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(0, cfg.vocab_size, (t,)
                                            ).astype(np.int32)])
               for t in (6, 3, 9, 5)]

    def serve(prefix_id, **kw):
        eng = _engine(m, params, **kw)
        rids = [eng.submit(p, 6, prefix_id=prefix_id) for p in prompts]
        out = eng.drain()
        return [list(out[r]) for r in rids], eng._scheduler.stats

    # chunk 5 with page 8: chunk boundaries straddle both page boundaries
    # and the 16-token (2-page) shared-prefix boundary
    shared, st_s = serve("sys", prefill_chunk=5)
    unshared, st_u = serve(None, prefill_chunk=5)
    oneshot, _ = serve("sys", chunked_prefill=False)
    assert shared == unshared == oneshot
    # the 3 followers each alias 2 full pages = 16 tokens of prefill FLOPs
    assert st_s.prefix_shared_pages == 6
    assert st_s.prefill_tokens_skipped == 3 * 16
    assert st_u.prefill_tokens_skipped == 0
    assert st_s.prefill_tokens < st_u.prefill_tokens


def test_fully_aliased_page_aligned_prompt(model_and_params):
    """A page-aligned prompt fully covered by the prefix index still
    samples its first token (the final prompt token re-runs, writing
    identical bytes into the shared page)."""
    cfg, m, params = model_and_params
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)  # 2 pages
    eng = _engine(m, params, prefill_chunk=6)
    r0 = eng.submit(prompt, 4, prefix_id="sys")
    r1 = eng.submit(prompt.copy(), 4, prefix_id="sys")
    out = eng.drain()
    assert list(out[r0]) == list(out[r1])
    st = eng._scheduler.stats
    assert st.prefix_shared_pages == 2
    assert st.prefill_tokens_skipped == 15      # all but the final token
    ref = eng.generate({"tokens": jnp.asarray(prompt)[None, :]}, 4)[0]
    np.testing.assert_array_equal(out[r0], ref)


def test_chunked_int8_deterministic(model_and_params):
    """Int8 chunked serving: deterministic run-to-run; chunk size does not
    change the committed pages (the per-(pos, head) codec is
    chunk-invariant), so streams agree across chunk sizes."""
    cfg, m, params = model_and_params
    prompts = _prompts(cfg, (5, 12, 9), seed=5)

    def serve(chunk):
        eng = _engine(m, params, kv_quant="int8", prefill_chunk=chunk)
        rids = [eng.submit(p, 6) for p in prompts]
        out = eng.drain()
        return [list(out[r]) for r in rids]

    a, b = serve(4), serve(4)
    assert a == b                       # deterministic
    assert serve(16) == a               # chunk-size invariant


def test_chunked_mixes_prefill_and_decode_in_one_dispatch(model_and_params):
    """A long prompt admitted next to decoding requests must not stall
    them: dispatches interleave its chunks with their decode tokens."""
    cfg, m, params = model_and_params
    eng = _engine(m, params, max_seq=64, prefill_chunk=4)
    short = _prompts(cfg, (4, 3), seed=6)
    long_p = _prompts(cfg, (33,), seed=7)[0]
    r_a = eng.submit(short[0], 12)
    r_b = eng.submit(short[1], 12)
    eng.step()                          # shorts finish prefill, start decode
    r_c = eng.submit(long_p, 4)
    mixed_steps = 0
    while not eng.idle:
        ev = eng.step()
        rids = {r for r, _ in ev}
        if r_c not in rids and eng.num_active == 3 and ev:
            mixed_steps += 1            # decode progressed mid-prefill
    # 33 tokens at 2 free rows × chunk 4 = 8/step → 4 mid-prefill steps,
    # each of which also decoded the two short requests
    assert mixed_steps >= 4
    out = eng.collect()
    ref = eng.generate({"tokens": jnp.asarray(long_p)[None, :]}, 4)[0]
    np.testing.assert_array_equal(out[r_c], ref)
    for rid, p in zip((r_a, r_b), short):
        ref = eng.generate({"tokens": jnp.asarray(p)[None, :]}, 12)[0]
        np.testing.assert_array_equal(out[rid], ref)


def test_bounded_compile_family_for_all_prompt_lengths(model_and_params):
    """The chunked path compiles one step function per context bucket ×
    width bucket — independent of the prompt-length mix (the
    jit-per-prompt-length family is gone). At max_seq 64 there is a
    single 8-page context bucket, so the compile count is bounded by the
    run-length packer's width family ({1, 2, 4, 8} at chunk 8) for any
    number of prompt lengths."""
    cfg, m, params = model_and_params
    eng = _engine(m, params, prefill_chunk=8)
    for p in _prompts(cfg, (3, 7, 11, 19, 26), seed=8):
        eng.submit(p, 2)
    eng.drain()
    assert eng._scheduler.width_buckets == [1, 2, 4, 8]
    assert 2 <= eng._chunk_greedy._cache_size() \
        <= len(eng._scheduler.width_buckets)
    assert not hasattr(eng, "_prefill_fused")   # the per-length family


# ---------------------------------------------------------------------------
# Multi-query kernel vs oracle (interpret mode, TPU-shaped inputs)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,c,hkv,g,hd,page,nblk,npages", [
    (2, 5, 2, 4, 64, 8, 4, 12),     # GQA chunk, several pages
    (1, 16, 1, 1, 128, 16, 3, 8),   # MQA, page-sized chunk
    (3, 3, 2, 9, 64, 8, 5, 20),     # row dim not a sublane multiple (pad)
    (2, 1, 4, 2, 64, 16, 2, 40),    # decode form (C = 1)
    (4, 16, 4, 7, 128, 16, 21, 90),  # 7B heads, a last block of 5 pages
    (4, 3, 2, 7, 64, 8, 19, 80),    # 0.5B heads, verify width, last block 3
    (3, 1, 1, 7, 128, 8, 19, 60),   # decode, one KV head, last block 3
])
def test_chunk_kernel_matches_oracle(b, c, hkv, g, hd, page, nblk, npages):
    """Kernel ≡ oracle over contiguous chunks at assorted watermarks: a
    fully padded row (exact zeros), a context that ends in the first page,
    a span that straddles a block of pages (a tree ancestor mask on odd
    rows, plain causality on even ones), stale table entries past every
    watermark, a padded chunk tail; with and without a sliding window."""
    rng = np.random.default_rng(hash((b, c, hkv, g, nblk)) % 2**31)
    k, ks, v, vs = _rand_pool(rng, npages, page, hkv, hd)
    q = jnp.asarray(rng.normal(size=(b, c, hkv, g, hd)), jnp.float32)
    table = jnp.asarray(rng.integers(1, npages, (b, nblk)), jnp.int32)
    span = page * nblk
    block = page * pages_per_block(nblk, page, hkv, hd, max(8, c * g))
    base = rng.integers(0, span - c + 1, b)
    base[-1] = min(page // 2, span - c)  # context ends in the first page
    if b > 2:                            # span straddles a block edge
        edge = block if block < span else page * (nblk // 2)
        base[1] = min(max(edge - c // 2 - 1, 0), span - c)
    pos = base[:, None] + np.arange(c)[None, :]
    if c > 1:                            # padding queries output zero
        pos[:, -1] = -1
    if b > 1:                            # a fully padded row
        pos[0] = -1
    amask = np.tril(np.ones((c, c), bool))[None] & (pos >= 0)[:, None, :]
    for r in range(1, b, 2):             # random trees on odd rows
        amask[r] &= rng.random((c, c)) < 0.6
        amask[r][np.diag_indices(c)] = pos[r] >= 0
    pos, amask = jnp.asarray(pos, jnp.int32), jnp.asarray(amask)
    for window in (0, page + 3):
        out = paged_attention_chunk(q, k, ks, v, vs, table, pos,
                                    amask=amask, window=window,
                                    interpret=True)
        ref = paged_attention_chunk_ref(q, k, ks, v, vs, table, pos,
                                        amask=amask, window=window)
        assert float(jnp.abs(out - ref).max()) < 1e-5
        if c > 1:
            assert float(jnp.abs(out[:, -1]).max()) == 0.0
        if b > 1:
            assert float(jnp.abs(out[0]).max()) == 0.0
    # positions drawn per query anywhere in the bucket, not as a chunk
    pos = jnp.asarray(rng.integers(0, span, (b, c)), jnp.int32)
    if c > 1:
        pos = pos.at[:, -1].set(-1)
    out = paged_attention_chunk(q, k, ks, v, vs, table, pos, interpret=True)
    ref = paged_attention_chunk_ref(q, k, ks, v, vs, table, pos)
    assert float(jnp.abs(out - ref).max()) < 1e-5
    if c > 1:
        assert float(jnp.abs(out[:, -1]).max()) == 0.0


def test_chunk_kernel_causal_within_chunk():
    """Intra-chunk causality: query at position p must ignore chunk
    tokens at positions > p even though their KV is already written."""
    rng = np.random.default_rng(0)
    npages, page, hkv, g, hd, nblk = 6, 8, 2, 2, 64, 2
    kf = jnp.asarray(rng.normal(size=(npages, hkv, page, hd)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(npages, hkv, page, hd)), jnp.float32)
    k, ks = _kv_quantize(kf)
    v, vs = _kv_quantize(vf)
    table = jnp.asarray([[2, 4]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(1, 3, hkv, g, hd)), jnp.float32)
    pos = jnp.asarray([[4, 5, 6]], jnp.int32)
    out = paged_attention_chunk(q, k, ks, v, vs, table, pos, interpret=True)
    # each query must equal its own single-query call (same mask)
    for i in range(3):
        solo = paged_attention_chunk(q[:, i:i + 1], k, ks, v, vs, table,
                                     pos[:, i:i + 1], interpret=True)
        np.testing.assert_allclose(np.asarray(out[:, i]),
                                   np.asarray(solo[:, 0]), atol=1e-6)


# ---------------------------------------------------------------------------
# Ancestor-mask edge cases (tree-speculation mask semantics)
# ---------------------------------------------------------------------------

def _rand_pool(rng, npages, page, hkv, hd):
    kf = jnp.asarray(rng.normal(size=(npages, hkv, page, hd)) * 2,
                     jnp.float32)
    vf = jnp.asarray(rng.normal(size=(npages, hkv, page, hd)), jnp.float32)
    k, ks = _kv_quantize(kf)
    v, vs = _kv_quantize(vf)
    return k, ks, v, vs


def test_chunk_kernel_all_masked_row_exact_zero():
    """A valid (non-padding) query whose ancestor-mask row is empty and
    that sits at watermark 0 (no committed span) sees nothing — the
    kernel's l == 0 flush must produce exactly 0, not NaN or softmax
    garbage, and other rows in the batch are unaffected."""
    rng = np.random.default_rng(20)
    npages, page, hkv, g, hd, nblk = 6, 8, 2, 2, 64, 2
    k, ks, v, vs = _rand_pool(rng, npages, page, hkv, hd)
    c = 4
    q = jnp.asarray(rng.normal(size=(2, c, hkv, g, hd)), jnp.float32)
    table = jnp.asarray([[2, 3], [4, 5]], jnp.int32)
    # row 0: fresh slot at watermark 0, all-false amask → nothing visible
    # row 1: ordinary causal chunk at watermark 4 → unaffected control
    pos = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    amask = np.zeros((2, c, c), bool)
    amask[1] = np.tril(np.ones((c, c), bool))
    out = paged_attention_chunk(q, k, ks, v, vs, table, pos,
                                amask=jnp.asarray(amask), interpret=True)
    ref = paged_attention_chunk_ref(q, k, ks, v, vs, table, pos,
                                    amask=jnp.asarray(amask))
    assert float(jnp.abs(out[0]).max()) == 0.0
    assert float(jnp.abs(ref[0]).max()) == 0.0
    assert np.isfinite(np.asarray(out)).all()
    plain = paged_attention_chunk(q[1:], k, ks, v, vs, table[1:], pos[1:],
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(plain[0]),
                               atol=1e-6)


def test_chunk_kernel_tree_mask_straddles_page_boundary():
    """A speculation tree whose in-span slots straddle a page boundary:
    the ancestor mask must keep following node indices while the keys
    come from two different physical pages. Kernel ≡ oracle, and each
    node attends exactly to its ancestor chain."""
    rng = np.random.default_rng(21)
    npages, page, hkv, g, hd, nblk = 8, 8, 2, 2, 64, 2
    k, ks, v, vs = _rand_pool(rng, npages, page, hkv, hd)
    c = 5                                   # root + 4 tree nodes
    q = jnp.asarray(rng.normal(size=(1, c, hkv, g, hd)), jnp.float32)
    table = jnp.asarray([[3, 6]], jnp.int32)
    # watermark 6 → slots 6..10 span page 3 (slots 6, 7) and page 6 (8..10)
    pos = jnp.asarray([[6, 7, 8, 9, 10]], jnp.int32)
    # tree: root → a → (b, c_sib); b → d   (two siblings share depth 2)
    #   in-row:    0     1    2  3       4
    parents = [-1, 0, 1, 1, 2]
    depth = [0, 1, 2, 2, 3]
    rpos = jnp.asarray([[6 + d for d in depth]], jnp.int32)
    amask = np.zeros((1, c, c), bool)
    for i, par in enumerate(parents):
        amask[0, i, i] = True
        j = par
        while j >= 0:
            amask[0, i, j] = True
            j = parents[j]
    out = paged_attention_chunk(q, k, ks, v, vs, table, pos,
                                rpos=rpos, amask=jnp.asarray(amask),
                                interpret=True)
    ref = paged_attention_chunk_ref(q, k, ks, v, vs, table, pos,
                                    rpos=rpos, amask=jnp.asarray(amask))
    assert float(jnp.abs(out - ref).max()) < 1e-5
    # corrupt sibling b's KV slot (slot 8 = page 6, offset 0, the first
    # slot past the page boundary): only b itself (node 2) and its child d
    # (node 4) may change — sibling c_sib (node 3) and the b-free prefix
    # must be bit-identical, proving the ancestor mask holds across pages
    k2 = k.at[6, :, 0].set(127)
    v2 = v.at[6, :, 0].set(127)
    ks2 = ks.at[6, :, 0].set(50.0)
    vs2 = vs.at[6, :, 0].set(50.0)
    out2 = paged_attention_chunk(q, k2, ks2, v2, vs2, table, pos,
                                 rpos=rpos, amask=jnp.asarray(amask),
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(out[:, :2]),
                                  np.asarray(out2[:, :2]))
    np.testing.assert_array_equal(np.asarray(out[:, 3]),
                                  np.asarray(out2[:, 3]))
    assert float(jnp.abs(out[:, 2] - out2[:, 2]).max()) > 1e-3
    assert float(jnp.abs(out[:, 4] - out2[:, 4]).max()) > 1e-3


def test_chunk_kernel_single_node_tree_equals_linear():
    """A degenerate tree (every node's parent is its predecessor — one
    chain) with rpos == pos and a lower-triangular ancestor mask is
    bit-for-bit the plain linear speculation row (amask=None)."""
    rng = np.random.default_rng(22)
    npages, page, hkv, g, hd, nblk = 8, 8, 2, 4, 64, 3
    k, ks, v, vs = _rand_pool(rng, npages, page, hkv, hd)
    c = 6
    q = jnp.asarray(rng.normal(size=(2, c, hkv, g, hd)), jnp.float32)
    table = jnp.asarray(rng.integers(1, npages, (2, nblk)), jnp.int32)
    pos = np.stack([np.arange(5, 5 + c), np.arange(12, 12 + c)]).astype(
        np.int32)
    pos[1, -2:] = -1                        # padding tail on one row
    pos = jnp.asarray(pos)
    tri = np.broadcast_to(np.tril(np.ones((c, c), bool)), (2, c, c)).copy()
    tri[1, :, -2:] = False                  # padding is never an ancestor
    out_tree = paged_attention_chunk(q, k, ks, v, vs, table, pos,
                                     rpos=pos, amask=jnp.asarray(tri),
                                     interpret=True)
    out_lin = paged_attention_chunk(q, k, ks, v, vs, table, pos,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(out_tree), np.asarray(out_lin))


# ---------------------------------------------------------------------------
# Scheduler token-budget semantics against a fake executor
# ---------------------------------------------------------------------------

class _FakeChunkExec:
    """Echo executor: sampled token = 100 + the row's sample-index token."""

    def __init__(self):
        self.dispatches = []           # (valid_token_count, rows_used)

    def run_batch(self, tokens, pos, row_slots, sample_idx, temps, topks):
        valid = (pos >= 0)
        self.dispatches.append((int(valid.sum()),
                                int((valid.any(axis=1)).sum())))
        out = np.zeros(tokens.shape[0], np.int32)
        for r in range(tokens.shape[0]):
            out[r] = 100 + tokens[r, sample_idx[r]]
        return out


def _sched(num_slots=2, pages_per_slot=4, page_size=4, chunk=3):
    ex = _FakeChunkExec()
    pager = KVPager(PagerConfig(num_pages=num_slots * pages_per_slot + 1,
                                page_size=page_size, num_slots=num_slots,
                                pages_per_slot=pages_per_slot))
    return Scheduler(pager, run_batch=ex.run_batch, chunk_size=chunk), ex


def test_chunked_scheduler_prefills_in_chunks_then_decodes():
    sched, ex = _sched(chunk=3)
    sched.submit(Request(rid=0, tokens=np.arange(7, dtype=np.int32),
                         max_new_tokens=3))
    # both idle rows go to the lone prefilling request: 2 chunks × 3 tokens
    ev = sched.step()                  # chunks [0,3) + [3,6): mid-prefill
    assert ev == []
    assert sched.slots[0].committed == 6
    assert ex.dispatches[-1] == (6, 2)
    ev = sched.step()                  # final chunk [6,7) → first token
    assert ev == [(0, 106)]            # 100 + last prompt token (6)
    out = sched.run()
    assert list(out[0]) == [106, 206, 306]   # decode echoes 100+prev
    assert sched.stats.prefill_chunks == 3
    assert sched.stats.prefill_tokens == 7
    assert sched.pager.pages_in_use == 0


def test_chunked_scheduler_packs_mixed_rows():
    sched, ex = _sched(num_slots=2, chunk=4)
    sched.submit(Request(rid=0, tokens=np.arange(4, dtype=np.int32),
                         max_new_tokens=4))
    sched.step()                       # rid 0 finishes prefill (4 ≤ chunk)
    sched.submit(Request(rid=1, tokens=np.arange(9, dtype=np.int32),
                         max_new_tokens=2))
    ev = sched.step()                  # rid 0 decodes + rid 1 chunk 1
    assert (1 + 4, 2) == ex.dispatches[-1]   # 5 valid tokens on 2 rows
    assert [r for r, _ in ev] == [0]
    out = sched.run()
    assert len(out[0]) == 4 and len(out[1]) == 2


def test_chunked_scheduler_first_token_eos_finishes_at_prefill_end():
    sched, ex = _sched(chunk=8)
    sched.submit(Request(rid=0, tokens=np.asarray([1, 2], np.int32),
                         max_new_tokens=8, eos_id=102))
    out = sched.run()
    assert list(out[0]) == [102]       # first sampled token is its eos
    assert sched.pager.pages_in_use == 0
    assert sched.stats.finished == 1
