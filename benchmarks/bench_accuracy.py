"""Paper Table III (accuracy): WNLI 64.79% → 61.97% under AWQ GS=64.

WNLI is not available offline, so the proxy is held-out cross-entropy on
the synthetic Markov stream with a briefly-trained qwen25-05b smoke model:

  * fp32 baseline,
  * AWQ GS=64 (the paper's pick), AWQ GS=128 (AWQ default),
  * plain round-to-nearest (no activation-aware scale) GS=64.

Expected ordering (the paper's qualitative claims): AWQ ≪ RTN degradation,
and GS=64 ≤ GS=128 degradation. The accuracy *ratio* (quantized/baseline,
via exp(-ΔCE) perplexity ratio) feeds Eq. (1) in bench_throughput.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.core import (AWQConfig, CalibrationCapture, QuantConfig,
                        quantize_params)
from repro.core.qlinear import set_execution_config
from repro.data import make_dataset
from repro.models import build_model
from repro.training import AdamWConfig, TrainConfig, make_train_step
from repro.training.train_step import init_train_state

_CACHE: dict = {}


def _trained_model(steps=150):
    if "model" in _CACHE:
        return _CACHE["model"]
    cfg = C.get_smoke_config("qwen25-05b")
    m = build_model(cfg)
    state = init_train_state(m, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(m, TrainConfig(optimizer=AdamWConfig(
        lr=3e-3, warmup_steps=5, decay_steps=steps, weight_decay=0.0))))
    ds = make_dataset(cfg, 16, 64)
    for i in range(steps):
        state, _ = step(state, {k: jnp.asarray(v)
                                for k, v in ds.batch_at(i).items()})
    _CACHE["model"] = (cfg, m, state["params"])
    return _CACHE["model"]


def _eval_ce(m, params, cfg, n_batches=4) -> float:
    ds = make_dataset(cfg, 16, 64, seed=999)  # held out
    set_execution_config(impl="ref", compute_dtype=jnp.float32)
    tot = 0.0
    for i in range(n_batches):
        loss, _ = jax.jit(m.loss)(params, {
            k: jnp.asarray(v) for k, v in ds.batch_at(i).items()})
        tot += float(loss)
    return tot / n_batches


def run(csv_rows: list) -> dict:
    cfg, m, params = _trained_model()
    ds = make_dataset(cfg, 4, 64, seed=123)
    with CalibrationCapture() as cap:   # 2 calib batches, 512 rows/linear
        for i in range(2):
            m.loss(params, {k: jnp.asarray(v)
                            for k, v in ds.batch_at(i).items()})

    ce = {"fp32": _eval_ce(m, params, cfg)}
    variants = {
        "awq_gs64": AWQConfig(quant=QuantConfig(group_size=64)),
        "awq_gs128": AWQConfig(quant=QuantConfig(group_size=128)),
    }
    for tag, qcfg in variants.items():
        qp, _ = quantize_params(params, cap.stats, qcfg)
        ce[tag] = _eval_ce(m, qp, cfg)
    qp_rtn, _ = quantize_params(params, None,
                                AWQConfig(quant=QuantConfig(group_size=64)))
    ce["rtn_gs64"] = _eval_ce(m, qp_rtn, cfg)

    for tag, v in ce.items():
        csv_rows.append((f"accuracy/ce_{tag}", f"{v:.4f}",
                         f"delta={v-ce['fp32']:+.4f}"))
    # qualitative claims
    csv_rows.append(("accuracy/awq_beats_rtn",
                     str(ce["awq_gs64"] <= ce["rtn_gs64"] + 1e-3),
                     "paper Fig.2 claim"))
    csv_rows.append(("accuracy/gs64_vs_gs128",
                     str(ce["awq_gs64"] <= ce["awq_gs128"] + 1e-3),
                     "paper §III-A GS choice"))
    _CACHE["acc_ratio"] = float(np.exp(-(ce["awq_gs64"] - ce["fp32"])))
    csv_rows.append(("accuracy/eq1_acc_ratio", f"{_CACHE['acc_ratio']:.4f}",
                     "exp(-dCE); paper 61.97/64.79=0.956"))
    return ce


def acc_ratio_cached() -> float:
    if "acc_ratio" not in _CACHE:
        run([])
    return _CACHE["acc_ratio"]


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    print(run(rows))
    for r in rows:
        print(",".join(r))
