"""Paper Table III (throughput): 2.8 → 5.1 tok/s on the KV260.

Three views:
 1. KV260 weight-stream roofline — decode is weight-bandwidth-bound on the
    19.2 GB/s DDR: tok/s ≤ BW / weight-bytes-per-token. The INT4 AWQ_MACRO
    stream cuts bytes/token 988 MB → 444 MB (the paper's own argument for
    why compression ≈ doubles decode throughput: 5.1/2.8 = 1.82×).
 2. TPU v5e decode roofline from the analytic cost model (serve dry-run
    terms), float vs AWQ — the adapted large-scale version of the claim.
 3. Measured wall-clock on this CPU host: smoke-scale qwen25 decode, float
    vs AWQ-ref path (same code path the container can actually execute).

Plus the paper's Eq. (1) composite score re-computed from our ratios.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

import repro.configs as C
from repro.configs import SHAPES
from repro.core import quantize_params
from repro.core.qlinear import set_execution_config
from repro.data import make_dataset
from repro.models import build_model
from repro.roofline.analysis import HBM_BW, PEAK_FLOPS
from repro.roofline.costmodel import cell_costs
from repro.serving import GenerationEngine

KV260_BW = 19.2e9  # paper §II-B


def kv260_model(csv_rows: list) -> dict:
    from benchmarks.bench_compression import sizes_for
    s = sizes_for("qwen25-05b")
    tps_fp16 = KV260_BW / (s["baseline_mb"] * 1e6)
    tps_awq = KV260_BW / (s["awq_gs64_mb"] * 1e6)
    csv_rows.append(("throughput/kv260_weightstream_fp16_tps",
                     f"{tps_fp16:.2f}", "bandwidth bound (paper meas 2.8)"))
    csv_rows.append(("throughput/kv260_weightstream_awq_tps",
                     f"{tps_awq:.2f}", "bandwidth bound (paper meas 5.1)"))
    csv_rows.append(("throughput/kv260_speedup", f"{tps_awq/tps_fp16:.2f}x",
                     "paper 1.82x"))
    return {"speedup": tps_awq / tps_fp16}


def v5e_roofline(csv_rows: list) -> dict:
    cfg = C.get_config("qwen25-05b")
    cell = SHAPES["decode_32k"]
    out = {}
    for quant in (False, True):
        cc = cell_costs(cfg, cell, quant)
        step = max(cc.flops / PEAK_FLOPS, cc.total_bytes / HBM_BW)  # 1 chip
        tps = cell.global_batch / step
        tag = "awq" if quant else "fp16"
        out[tag] = tps
        csv_rows.append((f"throughput/v5e_decode32k_{tag}_tps_per_chip",
                         f"{tps:.0f}",
                         f"w={cc.weight_bytes/1e9:.2f}GB "
                         f"cache={cc.cache_bytes/1e9:.2f}GB/step"))
    csv_rows.append(("throughput/v5e_decode_speedup",
                     f"{out['awq']/out['fp16']:.2f}x",
                     "batch128/32k-ctx (cache-dominated)"))
    # batch-1 serving: the paper's actual regime, weights dominate
    import dataclasses
    cell1 = dataclasses.replace(cell, global_batch=1, seq_len=1024)
    for quant in (False, True):
        cc = cell_costs(cfg, cell1, quant)
        tps = 1.0 / max(cc.flops / PEAK_FLOPS, cc.total_bytes / HBM_BW)
        out[f"b1_{'awq' if quant else 'fp16'}"] = tps
    csv_rows.append(("throughput/v5e_decode_b1_speedup",
                     f"{out['b1_awq']/out['b1_fp16']:.2f}x",
                     "batch1/1k-ctx (weight-dominated — paper's regime)"))
    return out


def measured_cpu(csv_rows: list) -> dict:
    cfg = C.get_smoke_config("qwen25-05b")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    ds = make_dataset(cfg, 4, 32)
    prompt = {"tokens": jnp.asarray(ds.batch_at(0)["tokens"])}
    out = {}
    for tag, p in (("fp32", params), ("awq", quantize_params(params)[0])):
        set_execution_config(impl="ref", compute_dtype=jnp.float32)
        eng = GenerationEngine(m, p, max_seq=96)
        eng.generate(prompt, 4)  # warmup/compile
        t0 = time.perf_counter()
        toks = eng.generate(prompt, 32)
        dt = time.perf_counter() - t0
        out[tag] = toks.size / dt
        csv_rows.append((f"throughput/cpu_smoke_{tag}_tps",
                         f"{out[tag]:.1f}", "wall-clock, ref path"))
    return out


def eq1_score(csv_rows: list, acc_ratio=0.9565) -> dict:
    """Paper Eq. (1): 0.4·acc + 0.2·mem + 0.2·tp_prefill + 0.2·tp_decode,
    each normalized by the max across systems. Baseline fp16 system scores
    0.4 by construction (acc=1, others → baseline=1 is the max denominator
    only for accuracy)."""
    from benchmarks.bench_compression import sizes_for
    s = sizes_for("qwen25-05b")
    mem_ratio = s["baseline_mb"] / s["awq_gs64_mb"]   # >1 for ours
    cfg = C.get_config("qwen25-05b")
    cc_f = cell_costs(cfg, SHAPES["decode_32k"], False)
    cc_q = cell_costs(cfg, SHAPES["decode_32k"], True)
    tp_d = (cc_f.total_bytes) / (cc_q.total_bytes)
    cc_fp = cell_costs(cfg, SHAPES["prefill_32k"], False)
    cc_qp = cell_costs(cfg, SHAPES["prefill_32k"], True)
    tp_p = max(cc_fp.flops / PEAK_FLOPS, cc_fp.total_bytes / HBM_BW) / \
        max(cc_qp.flops / PEAK_FLOPS, cc_qp.total_bytes / HBM_BW)
    # normalize per Eq. 1: MAX over {baseline, ours} of each ratio
    ours = 0.4 * (acc_ratio / 1.0) + 0.2 * (mem_ratio / mem_ratio) \
        + 0.2 * (tp_p / max(tp_p, 1.0)) + 0.2 * (tp_d / max(tp_d, 1.0))
    base = 0.4 * 1.0 + 0.2 * (1.0 / mem_ratio) \
        + 0.2 * (1.0 / max(tp_p, 1.0)) + 0.2 * (1.0 / max(tp_d, 1.0))
    csv_rows.append(("throughput/eq1_score_ours", f"{ours:.3f}",
                     "paper 0.55"))
    csv_rows.append(("throughput/eq1_score_baseline", f"{base:.3f}",
                     "paper 0.40"))
    return {"ours": ours, "baseline": base}


def run(csv_rows: list) -> dict:
    out = {"kv260": kv260_model(csv_rows),
           "v5e": v5e_roofline(csv_rows),
           "cpu": measured_cpu(csv_rows)}
    from benchmarks.bench_accuracy import acc_ratio_cached
    out["eq1"] = eq1_score(csv_rows, acc_ratio_cached())
    return out


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    run(rows)
    for r in rows:
        print(",".join(r))
