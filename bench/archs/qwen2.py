"""The Qwen2 architecture (Qwen2ForCausalLM) in the benchmark: its weight
layout, the program's model and parameter tree over those weights, and
its model FLOPs per token. A configuration file's ``"reference": "qwen2"``
names this module, as it names the plain reference
``bench/reference/qwen2.py``.

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

Like `bench/sut.py`, `model_config` and `program_params` import the
program (``src/repro``); nothing else here does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights
from bench.sut import _import_program

BIASED = ("wq", "wk", "wv")


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
        w["layers"][name] = weights.packed(keys[6 + i], n_layers, k, n, gs,
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
    return _make(weights.seed_key(seed), frozen)


def model_config(cfg: dict):
    """The program's `ModelConfig` for a configuration file's dict: one
    uniform segment of dense layers, as `program_params` lays them out."""
    _import_program()
    from repro.configs.base import ModelConfig
    if cfg["architectures"] != ["Qwen2ForCausalLM"]:
        raise ValueError(f"no program mapping for {cfg['architectures']}")
    mcfg = ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        act=cfg["hidden_act"], mlp_type="glu", norm_type="rmsnorm",
        norm_eps=cfg["rms_norm_eps"], qkv_bias=cfg["attention_bias"],
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"],
        max_seq_len=cfg["max_position_embeddings"],
        param_dtype=cfg["torch_dtype"], activation_dtype=cfg["torch_dtype"])
    if len(mcfg.segments()) != 1:
        raise ValueError("expected one uniform layer segment")
    return mcfg


def program_params(cfg: dict, w: dict) -> dict:
    """The program's parameter tree over the same device arrays (no copy):
    every linear a stacked `PackedLinear` with ``input_scale`` ones."""
    _import_program()
    from repro.core.packing import PackedLinear
    from repro.models.stack import seg_name

    gs = cfg["quantization"]["group_size"]
    lw = w["layers"]

    def packed(name):
        p = lw[name]
        n_layers, kp, _ = p["qweight"].shape
        return PackedLinear(
            qweight=p["qweight"], scales=p["scales"], zeros=p["zeros"],
            input_scale=jnp.ones((n_layers, kp * 8), jnp.float32),
            bias=p.get("bias"), group_size=gs)

    seg = {"pre_norm": {"gamma": lw["attn_norm"]},
           "attn": {k: packed(k) for k in ("wq", "wk", "wv", "wo")},
           "mlp_norm": {"gamma": lw["mlp_norm"]},
           "mlp": {k: packed(k) for k in ("gate", "up", "down")}}
    params = {"embed": {"table": w["embed"]},
              "final_norm": {"gamma": w["final_norm"]},
              "segments": {seg_name(0): seg}}
    if "lm_head" in w:
        params["lm_head"] = {"w": w["lm_head"]}
    return params


def linear_dims(cfg: dict) -> dict[str, tuple[int, int]]:
    """(K, N) of each linear of one Qwen2 layer."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    f = cfg["intermediate_size"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "gate": (d, f), "up": (d, f), "down": (f, d)}


def token_flops(cfg: dict) -> int:
    """Model FLOPs of one token through the layers' linears (attention
    scores and the head excluded; see `head_flops`)."""
    per_layer = sum(2 * k * n for k, n in linear_dims(cfg).values())
    return cfg["num_hidden_layers"] * per_layer


def head_flops(cfg: dict) -> int:
    """FLOPs of the vocabulary projection of one sampled row."""
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]
