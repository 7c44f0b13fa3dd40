"""Random weights for one configuration, made on the device from the seed.

Each architecture lays out its own weights (``make_weights`` of
``bench/archs/<arch>.py``, found by the configuration's ``"reference"``
key); its plain reference reads that layout, and its ``program_params``
hands the same arrays to the system under test. The helpers here are the
ones layouts share: the key of a seed, and a stack of AWQ int4 linears.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import plugins


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number up to 2**64 (seeds may not fit in
    32 bits)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside 0 .. 2**64 - 1")
    return jax.random.fold_in(jax.random.key(seed >> 32), seed & 0xFFFFFFFF)


def packed(key, n_layers: int, k: int, n: int, gs: int, bias: bool):
    """``n_layers`` stacked AWQ int4 linears ``[K, N]`` of group size
    ``gs``: ``qweight`` (int32 words of eight 4-bit codes), ``scales``
    (f32) and ``zeros`` (int8) per group and column, and a bf16 ``bias``
    where asked. Codes are uniform over 0..15, zeros 7 or 8 and scales
    near ``1 / (4.6 sqrt(K))``, so a dequantized column has about unit
    gain."""
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


def make_weights(cfg: dict, seed: int) -> dict:
    """All weights of ``cfg`` (a configuration file's dict) on the default
    device, in its architecture module's layout, in one jitted call."""
    return plugins.arch(cfg).make_weights(cfg, seed)
