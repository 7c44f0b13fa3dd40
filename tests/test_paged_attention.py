"""Fused dequant+paged-attention kernel vs. oracle + int8 page round-trips."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import paged_attention_ref
from repro.models import build_model
from repro.models.attention import _kv_dequant, _kv_quantize
from repro.serving.kv_pager import commit_prefill


@pytest.mark.parametrize("b,hkv,g,hd,page,nblk,npages", [
    (3, 2, 4, 64, 8, 4, 12),     # GQA, several pages
    (2, 1, 1, 128, 16, 2, 6),    # MQA, single group
    (4, 2, 9, 64, 8, 3, 20),     # group dim not a sublane multiple (pad)
    (1, 4, 2, 64, 16, 5, 40),
    (3, 4, 7, 128, 16, 21, 90),  # 7B heads, a last block of 5 pages
    (4, 2, 7, 64, 8, 19, 60),    # 0.5B heads, a last block of 3 pages
])
def test_kernel_matches_oracle(b, hkv, g, hd, page, nblk, npages):
    """Kernel ≡ oracle on ``b`` rows at random positions in the bucket,
    plus two rows more: a padding row (pos -1) that reads exact zeros and
    a context that ends in the first page. Whatever the table holds past
    a row's watermark — inside the watermark's block of pages or beyond
    it — leaves the output bit-identical."""
    rng = np.random.default_rng(hash((b, hkv, g)) % 2**31)
    kf = jnp.asarray(rng.normal(size=(npages, hkv, page, hd)) * 2,
                     jnp.float32)
    vf = jnp.asarray(rng.normal(size=(npages, hkv, page, hd)), jnp.float32)
    k, ks = _kv_quantize(kf)
    v, vs = _kv_quantize(vf)
    q = rng.normal(size=(b, hkv, g, hd))
    table = rng.integers(1, npages, (b, nblk))
    pos = rng.integers(0, page * nblk, (b,))
    # the extra rows: a padding row, a context that ends in the first page
    q = jnp.asarray(np.concatenate([q, rng.normal(size=(2, hkv, g, hd))]),
                    jnp.float32)
    table = np.concatenate([table, rng.integers(1, npages, (2, nblk))])
    pos = np.concatenate([pos, [-1, page // 2]])
    b += 2
    out = paged_attention(q, k, ks, v, vs, jnp.asarray(table, jnp.int32),
                          jnp.asarray(pos, jnp.int32), interpret=True)
    ref = paged_attention_ref(q, k, ks, v, vs, jnp.asarray(table, jnp.int32),
                              jnp.asarray(pos, jnp.int32))
    live = pos >= 0                      # the oracle knows no padding
    assert float(jnp.abs(out[live] - ref[live]).max()) < 1e-5
    assert float(jnp.abs(out[~live]).max(initial=0.0)) == 0.0
    stale = table.copy()
    for r in range(b):                   # redraw every page past pos
        past = np.arange(nblk) > max(pos[r], 0) // page
        stale[r, past] = rng.integers(1, npages, past.sum())
    out2 = paged_attention(q, k, ks, v, vs, jnp.asarray(stale, jnp.int32),
                           jnp.asarray(pos, jnp.int32), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_kernel_masks_stale_table_entries():
    """Table slots past the valid range point at the scratch page; their
    positions exceed pos so they must never leak into the softmax."""
    rng = np.random.default_rng(0)
    npages, page, hkv, hd, nblk = 8, 8, 2, 64, 4
    kf = jnp.asarray(rng.normal(size=(npages, hkv, page, hd)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(npages, hkv, page, hd)), jnp.float32)
    k, ks = _kv_quantize(kf)
    v, vs = _kv_quantize(vf)
    q = jnp.asarray(rng.normal(size=(1, hkv, 2, hd)), jnp.float32)
    pos = jnp.asarray([5], jnp.int32)                    # page 0 only
    t_clean = jnp.asarray([[3, 0, 0, 0]], jnp.int32)
    t_stale = jnp.asarray([[3, 7, 6, 5]], jnp.int32)     # garbage beyond pos
    a = paged_attention(q, k, ks, v, vs, t_clean, pos, interpret=True)
    b = paged_attention(q, k, ks, v, vs, t_stale, pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_paged_jnp_path_matches_kernel():
    """The module's gather+dequant fallback ≡ the fused kernel (same math,
    online-softmax reassociation only)."""
    from repro.models import attention as attn_mod

    cfg = C.get_smoke_config("qwen25-05b")
    rng = np.random.default_rng(3)
    npages, page, nblk = 9, 8, 3
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    g = cfg.num_heads // hkv
    kf = jnp.asarray(rng.normal(size=(npages, hkv, page, hd)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(npages, hkv, page, hd)), jnp.float32)
    k, ks = _kv_quantize(kf)
    v, vs = _kv_quantize(vf)
    q = jnp.asarray(rng.normal(size=(2, hkv, g, hd)), jnp.float32)
    table = jnp.asarray(rng.integers(1, npages, (2, nblk)), jnp.int32)
    pos = jnp.asarray([7, 19], jnp.int32)
    out = paged_attention(q, k, ks, v, vs, table, pos, interpret=True)
    # reproduce the fallback's gather math
    s_slot = nblk * page
    ck = _kv_dequant(k[table].reshape(2, s_slot, hkv, hd),
                     ks[table].reshape(2, s_slot, hkv), jnp.float32)
    cv = _kv_dequant(v[table].reshape(2, s_slot, hkv, hd),
                     vs[table].reshape(2, s_slot, hkv), jnp.float32)
    k_pos = jnp.where(jnp.arange(s_slot)[None, :] <= pos[:, None],
                      jnp.arange(s_slot)[None, :], -1)
    ref = attn_mod._sdpa(q[:, None].reshape(2, 1, hkv, g, hd), ck, cv,
                         pos[:, None], k_pos, causal=False, window=0,
                         scale=hd ** -0.5)
    assert float(jnp.abs(out - ref[:, 0]).max()) < 1e-5


# ---------------------------------------------------------------------------
# Int8 page round-trips through commit_prefill (quantize-on-commit)
# ---------------------------------------------------------------------------

def _int8_pool(layers, n_pages, page, heads, hd):
    return {"k": jnp.zeros((layers, n_pages, heads, page, hd), jnp.int8),
            "v": jnp.zeros((layers, n_pages, heads, page, hd), jnp.int8),
            "ks": jnp.zeros((layers, n_pages, heads, page), jnp.float32),
            "vs": jnp.zeros((layers, n_pages, heads, page), jnp.float32)}


def test_commit_quantizes_float_prefill_into_int8_pages():
    """bf16 prefill cache → int8 pool: per-(pos, head) round-trip error is
    bounded by half the absmax scale, zero rows stay exact."""
    layers, page, heads, hd, s = 2, 4, 2, 8, 10   # 2 full pages + 2-tok tail
    rng = np.random.default_rng(1)
    k = rng.normal(size=(layers, 1, s, heads, hd)).astype(np.float32) * 4
    k[0, 0, 3] = 0.0                              # a zero row
    v = rng.normal(size=(layers, 1, s, heads, hd)).astype(np.float32)
    cache = {"seg_0": {"kv_pool": _int8_pool(layers, 7, page, heads, hd)}}
    prefill = {"seg_0": {"kv": {"k": jnp.asarray(k), "v": jnp.asarray(v)}}}
    phys = jnp.asarray([4, 2, 6], jnp.int32)
    out = commit_prefill(cache, prefill, jnp.int32(0), phys, page_size=page)
    pool = out["seg_0"]["kv_pool"]
    table = np.asarray([4, 2, 6])
    for name, scale_name, ref in (("k", "ks", k), ("v", "vs", v)):
        codes = np.asarray(pool[name])[:, table].swapaxes(2, 3).reshape(
            layers, -1, heads, hd)
        scales = np.asarray(pool[scale_name])[:, table].swapaxes(
            2, 3).reshape(layers, -1, heads)
        deq = codes.astype(np.float32) * scales[..., None]
        err = np.abs(deq[:, :s] - ref[:, 0])
        bound = scales[:, :s, :, None] * 0.5 + 1e-6
        assert (err <= bound).all(), (name, err.max())
    # the zero row round-trips exactly
    deq_k = (np.asarray(pool["k"])[0, 4, :, 3].astype(np.float32)
             * np.asarray(pool["ks"])[0, 4, :, 3][..., None])
    assert np.abs(deq_k).max() == 0.0


def test_commit_matches_decode_write_codec():
    """Quantize-on-commit and the decode write path use the same codec: a
    token committed by prefill equals the same token written by decode."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 1, 4, 2, 8)),
                    jnp.float32)
    q_commit, s_commit = _kv_quantize(x)
    q_tok, s_tok = _kv_quantize(x[0, 0])
    np.testing.assert_array_equal(np.asarray(q_commit)[0, 0],
                                  np.asarray(q_tok))
    np.testing.assert_array_equal(np.asarray(s_commit)[0, 0],
                                  np.asarray(s_tok))


def test_int8_engine_decode_close_to_bf16():
    """Serving with int8 pages degrades logit fidelity gracefully: greedy
    streams run end-to-end and the first sampled token (prefill, float
    path) is identical; decode tokens may differ only via quantization."""
    cfg = C.get_smoke_config("qwen25-05b")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    from repro.serving import GenerationEngine
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 12, 9)]
    outs = {}
    for quant in ("none", "int8"):
        eng = GenerationEngine(m, params, max_seq=64, num_slots=4,
                               page_size=8, kv_quant=quant)
        rids = [eng.submit(p, 6) for p in prompts]
        out = eng.drain()
        outs[quant] = [list(out[r]) for r in rids]
        assert all(len(o) == 6 for o in outs[quant])
        assert eng._scheduler.pager.pages_in_use == 0
    # first token comes from the float prefill logits in both regimes
    for a, b in zip(outs["none"], outs["int8"]):
        assert a[0] == b[0]
    # int8 serving is deterministic: a second run reproduces the streams
    eng = GenerationEngine(m, params, max_seq=64, num_slots=4,
                           page_size=8, kv_quant="int8")
    rids = [eng.submit(p, 6) for p in prompts]
    out = eng.drain()
    assert [list(out[r]) for r in rids] == outs["int8"]
