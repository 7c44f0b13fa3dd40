"""Random weights for one configuration, made on the device from the seed.

The benchmark's own layout, read by the plain reference and handed to the
system under test (`program_params`):

    embed [V, D] bf16, final_norm [D] bf16, lm_head [D, V] bf16 (untied only)
    layers/attn_norm, layers/mlp_norm [L, D] bf16
    layers/<linear> for wq wk wv wo gate up down:
        qweight [L, K/8, N] int32   eight 4-bit codes a word, nibble j of
                                    word w holding row 8w + j
        scales  [L, K/G, N] f32     one per group of G rows and column
        zeros   [L, K/G, N] int8    the group's zero code
        bias    [L, N] bf16         q, k and v only (Qwen2)

A weight is ``(code - zero) * scale``. Codes are uniform over 0..15,
zeros 7 or 8 and scales near ``1 / (4.6 sqrt(K))``, so a dequantized
column has about unit gain. Everything is drawn in one jitted call from
``--seed``; the same seed gives the same arrays bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.work import linear_dims

BIASED = ("wq", "wk", "wv")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number up to 2**64 (seeds may not fit in
    32 bits)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside 0 .. 2**64 - 1")
    return jax.random.fold_in(jax.random.key(seed >> 32), seed & 0xFFFFFFFF)


def _packed(key, n_layers: int, k: int, n: int, gs: int, bias: bool):
    kq, ks, kz, kb = jax.random.split(key, 4)
    words = jax.random.bits(kq, (n_layers, k // 8, n), jnp.uint32)
    scale = 1.0 / (4.6 * k ** 0.5)
    out = {
        "qweight": jax.lax.bitcast_convert_type(words, jnp.int32),
        "scales": scale * jax.random.uniform(
            ks, (n_layers, k // gs, n), jnp.float32, 0.75, 1.25),
        "zeros": jax.random.randint(kz, (n_layers, k // gs, n), 7, 9,
                                    jnp.int8),
    }
    if bias:
        out["bias"] = (0.1 * jax.random.normal(kb, (n_layers, n))
                       ).astype(jnp.bfloat16)
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, frozen_cfg):
    cfg = dict(frozen_cfg)
    n_layers, d, v = (cfg["num_hidden_layers"], cfg["hidden_size"],
                      cfg["vocab_size"])
    gs = cfg["group_size"]
    dims = linear_dims(cfg)
    keys = jax.random.split(key, 6 + len(dims))

    def gain(k, shape):
        return (1.0 + 0.1 * jax.random.normal(k, shape)).astype(jnp.bfloat16)

    w = {
        "embed": (0.02 * jax.random.normal(keys[0], (v, d))
                  ).astype(jnp.bfloat16),
        "final_norm": gain(keys[1], (d,)),
        "layers": {"attn_norm": gain(keys[2], (n_layers, d)),
                   "mlp_norm": gain(keys[3], (n_layers, d))},
    }
    if not cfg["tie_word_embeddings"]:
        w["lm_head"] = (jax.random.normal(keys[4], (d, v)) / d ** 0.5
                        ).astype(jnp.bfloat16)
    for i, (name, (k, n)) in enumerate(dims.items()):
        w["layers"][name] = _packed(keys[6 + i], n_layers, k, n, gs,
                                    name in BIASED)
    return w


def make_weights(cfg: dict, seed: int) -> dict:
    """All weights of ``cfg`` (a configuration file's dict) on the default
    device, in the layout of this module's docstring."""
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "tie_word_embeddings")
    frozen = tuple((k, cfg[k]) for k in keys) + (
        ("group_size", cfg["quantization"]["group_size"]),)
    return _make(seed_key(seed), frozen)
