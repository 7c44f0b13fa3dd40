"""The system under test: the program's engine (or a Router over one-chip
replicas) built from a configuration file and the benchmark's weights.

This is the one module of the benchmark that imports the program
(``src/repro``); the reference, the traffic and the metrics do not.
"""
from __future__ import annotations

import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"


def _import_program():
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"the program is not at {SRC}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def model_config(cfg: dict):
    """The program's `ModelConfig` for a configuration file's dict."""
    _import_program()
    from repro.configs.base import ModelConfig
    if cfg["architectures"] != ["Qwen2ForCausalLM"]:
        raise ValueError(f"no program mapping for {cfg['architectures']}")
    return ModelConfig(
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


def program_params(cfg: dict, w: dict) -> dict:
    """The program's parameter tree over the same device arrays (no copy):
    every linear a stacked `PackedLinear` with ``input_scale`` ones."""
    _import_program()
    import jax.numpy as jnp

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


def build(cfg: dict, w: dict):
    """→ (server, engines): the engine, or a Router over
    ``serving.replicas`` one-chip replicas, and the list of its engines."""
    _import_program()
    from repro.models import build_model

    mcfg = model_config(cfg)
    if len(mcfg.segments()) != 1:
        raise ValueError("expected one uniform layer segment")
    model = build_model(mcfg)
    params = program_params(cfg, w)
    s = cfg["serving"]
    if s["sampling"] != "greedy":
        raise ValueError("only greedy serving is benchmarked")
    kwargs = dict(max_seq=s["max_seq"], num_slots=s["num_slots"],
                  page_size=s["page_size"], prefill_chunk=s["prefill_chunk"],
                  kv_quant=s["kv_cache"])
    if s.get("replicas", 1) == 1:
        from repro.serving import GenerationEngine
        engine = GenerationEngine(model, params, **kwargs)
        return engine, [engine]
    from repro.launch.specs import FleetSpec, ReplicaSpec
    router = FleetSpec(replicas=s["replicas"], replica=ReplicaSpec(
        mesh_axis=1, engine_kwargs=kwargs)).build(model, params)
    return router, list(router.replicas)
