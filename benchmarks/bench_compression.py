"""Paper Table III (model size): 988 MB → 443.81 MB (55.1% reduction).

Byte-exact accounting of the full qwen2.5-0.5b config through the paper's
AWQ_MACRO serialization (GS=64): every quantizable linear at 4.5 bits/weight
(GS·8 INT4 qweights + 8 FP16 scales + 128-bit zeros strip per macro),
everything else fp16. Nothing is materialized — shapes come from
`jax.eval_shape` over the real `model.init`.

Also reports GS=128 and the per-component split, plus the same accounting
for every assigned architecture (compression is arch-agnostic — DESIGN §4).
"""
from __future__ import annotations

import jax

import repro.configs as C
from repro.core.pipeline import model_size_bytes
from repro.models import build_model

PAPER_BASELINE_MB = 988.0
PAPER_AWQ_MB = 443.81


def sizes_for(arch: str) -> dict:
    cfg = C.get_config(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    base = model_size_bytes(shapes, quantized=False)
    q64 = model_size_bytes(shapes, quantized=True)
    from repro.core.quantize import QuantConfig
    q128 = model_size_bytes(shapes, quantized=True,
                            cfg=QuantConfig(group_size=128))
    return {"baseline_mb": base / 1e6, "awq_gs64_mb": q64 / 1e6,
            "awq_gs128_mb": q128 / 1e6,
            "reduction_pct": 100 * (1 - q64 / base)}


def run(csv_rows: list) -> dict:
    out = {}
    r = sizes_for("qwen25-05b")
    out["qwen25-05b"] = r
    csv_rows.append(("compression/qwen25-05b_baseline_mb",
                     f"{r['baseline_mb']:.2f}",
                     f"paper={PAPER_BASELINE_MB}"))
    csv_rows.append(("compression/qwen25-05b_awq_gs64_mb",
                     f"{r['awq_gs64_mb']:.2f}", f"paper={PAPER_AWQ_MB}"))
    csv_rows.append(("compression/qwen25-05b_reduction_pct",
                     f"{r['reduction_pct']:.2f}", "paper=55.1"))
    csv_rows.append(("compression/qwen25-05b_awq_gs128_mb",
                     f"{r['awq_gs128_mb']:.2f}",
                     "GS=128 (AWQ default; paper chose 64)"))
    for arch in C.ASSIGNED_ARCHS:
        r = sizes_for(arch)
        out[arch] = r
        csv_rows.append((f"compression/{arch}_reduction_pct",
                         f"{r['reduction_pct']:.2f}",
                         f"{r['baseline_mb']:.0f}->{r['awq_gs64_mb']:.0f}MB"))
    return out


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    run(rows)
    for r in rows:
        print(",".join(r))
