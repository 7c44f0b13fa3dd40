"""Plain reference of the Qwen2 architecture (Qwen2ForCausalLM), in float32.

Written from the published description alone: RMSNorm before attention
and before the MLP, grouped-query attention with a bias on q, k and v,
rotary embeddings on the two halves of each head (``rotate_half``),
causal softmax, a SwiGLU MLP, a final RMSNorm and the vocabulary
projection (the embedding's transpose when tied). Each int4 linear is
dequantized here, from the benchmark's own weight layout (see
``bench/archs/qwen2.py``): ``w = (code - zero) * scale``.

No kernels, no cache, no batching tricks: one layer at a time over the
whole sequence, each matmul at ``precision="highest"``. It imports
nothing of the program.

``control=True`` computes every matmul from operands rounded to float8
(e4m3, each row scaled to its absolute maximum): the precision below the
configuration's bfloat16 that the comparison must tell apart.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _fp8(a, axis=-1):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    s = jnp.where(amax == 0, 1.0, amax / 448.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _dot(x, w, control, spec="...k,kn->...n"):
    if control:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum(spec, x, w, precision="highest",
                      preferred_element_type=F32)


def dequant(p: dict, group_size: int) -> jax.Array:
    """[K/8, N] int32 words of 4-bit codes → [K, N] float32 weight."""
    words = p["qweight"].astype(jnp.uint32)
    kp, n = words.shape
    shifts = 4 * jnp.arange(8, dtype=jnp.uint32)
    codes = (words[:, None, :] >> shifts[None, :, None]) & 0xF
    codes = codes.reshape(kp * 8, n).astype(F32)
    g = codes.shape[0] // group_size
    z = p["zeros"].astype(F32)[:, None, :]
    s = p["scales"].astype(F32)[:, None, :]
    return ((codes.reshape(g, group_size, n) - z) * s).reshape(kp * 8, n)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def _rope(x, pos, theta):
    """x [S, H, hd]; rotate_half convention."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def _layer(x, layers, i, *, cfg, control):
    """One decoder layer over one sequence x [S, D] (float32)."""
    c = dict(cfg)
    lw = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), layers)
    gs, eps = c["group_size"], c["rms_norm_eps"]
    s = x.shape[0]
    h, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])

    def lin(name, v):
        y = _dot(v, dequant(lw[name], gs), control)
        if "bias" in lw[name]:
            y = y + lw[name]["bias"].astype(F32)
        return y

    pos = jnp.arange(s)
    a = _rms(x, lw["attn_norm"], eps)
    q = _rope(lin("wq", a).reshape(s, h, hd), pos, c["rope_theta"])
    k = _rope(lin("wk", a).reshape(s, hkv, hd), pos, c["rope_theta"])
    v = lin("wv", a).reshape(s, hkv, hd)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    sc = _dot(q, k, control, "qhd,khd->hqk") / hd ** 0.5
    causal = pos[None, :, None] >= pos[None, None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = _dot(p, v, control, "hqk,khd->qhd").reshape(s, h * hd)
    x = x + lin("wo", o)
    m = _rms(x, lw["mlp_norm"], eps)
    return x + lin("down", jax.nn.silu(lin("gate", m)) * lin("up", m))


@functools.partial(jax.jit, static_argnames=("eps", "control", "tied"))
def _score(x, rows, cands, g, head, *, eps, control, tied):
    hn = _rms(x[rows], g, eps)
    w = head.astype(F32)
    lg = _dot(hn, w, control, "sd,vd->sv") if tied else _dot(hn, w, control)
    picked = jnp.take_along_axis(lg[None], cands[..., None], -1)[..., 0]
    return lg.max(-1), picked, jnp.argmax(lg, -1).astype(jnp.int32)


def score(w: dict, cfg: dict, tokens: np.ndarray, rows: np.ndarray,
          cands: np.ndarray, control: bool = False):
    """Read the logits at positions ``rows`` of one sequence.

    Returns, per row, the best logit, the logits of the candidate tokens
    ``cands`` [n, len(rows)], and the top token. The sequence and the rows
    are padded to multiples of 256 so that few shapes compile; padding
    sits after every row read, and attention is causal, so it changes
    nothing."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    frozen = tuple((k, cfg[k]) for k in keys) + (
        ("group_size", cfg["quantization"]["group_size"]),)
    n, r = len(tokens), len(rows)
    padded = np.zeros(-(-n // 256) * 256, np.int32)
    padded[:n] = tokens
    rp = -(-r // 256) * 256
    rows_p = np.full(rp, rows[-1], np.int32)
    rows_p[:r] = rows
    cands_p = np.zeros((len(cands), rp), np.int32)
    cands_p[:, :r] = cands
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], jnp.asarray(padded), axis=0).astype(F32)
        for i in range(cfg["num_hidden_layers"]):
            x = _layer(x, w["layers"], jnp.int32(i), cfg=frozen,
                       control=control)
        tied = cfg["tie_word_embeddings"]
        best, picked, top = _score(
            x, jnp.asarray(rows_p), jnp.asarray(cands_p), w["final_norm"],
            w["embed"] if tied else w["lm_head"], eps=cfg["rms_norm_eps"],
            control=control, tied=tied)
    return (np.asarray(best)[:r], np.asarray(picked)[:, :r],
            np.asarray(top)[:r])
