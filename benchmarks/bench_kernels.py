"""Kernel-level benchmark: the fused dequant-MAC unit (paper §III-B).

No TPU in this container, so the numbers that matter are STRUCTURAL (the
same quantities Table II's synthesis reports): bytes streamed per weight,
VMEM working set per grid step, MXU tile alignment — plus interpret-mode
correctness timing as a smoke signal.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.packing import pack_linear
from repro.core.quantize import QuantConfig, quantize_groupwise
from repro.kernels.ops import awq_matmul, choose_blocks
from repro.kernels.ref import awq_matmul_ref

# paper-relevant shapes: qwen25-05b decode GEMV + prefill GEMM per linear
SHAPES = [
    ("decode_qkv", 1, 896, 1152),
    ("decode_ffn_gate", 1, 896, 4864),
    ("decode_ffn_down", 1, 4864, 896),
    ("prefill_ffn_gate", 256, 896, 4864),
]


def run(csv_rows: list) -> dict:
    out = {}
    gs = 64
    for name, m, k, n in SHAPES:
        w = jax.random.normal(jax.random.PRNGKey(0), (k, n)) * 0.05
        cfg = QuantConfig(group_size=gs)
        p = pack_linear(*quantize_groupwise(w, cfg), None, None, cfg)
        bm, bn, bk = choose_blocks(m, k, n, gs)
        # streamed bytes per weight (the paper's bandwidth argument)
        wbytes = p.qweight.size * 4 + p.scales.size * 4 + p.zeros.size
        bits_per_w = wbytes * 8 / (k * n)
        vmem = bm * bk * 4 + bk // 8 * bn * 4 + 2 * (bk // gs) * bn * 4 \
            + bm * bn * 4
        x = jax.random.normal(jax.random.PRNGKey(1), (m, k))
        t0 = time.perf_counter()
        y = awq_matmul(x, p, compute_dtype=jnp.float32, interpret=True)
        jax.block_until_ready(y)
        t_int = (time.perf_counter() - t0) * 1e6
        ref = awq_matmul_ref(x, p.qweight, p.scales, p.zeros, gs)
        err = float(jnp.abs(y - ref).max())
        csv_rows.append((f"kernel/{name}", f"{t_int:.0f}",
                         f"blocks=({bm},{bn},{bk}) vmem={vmem/2**20:.2f}MB "
                         f"bits/w={bits_per_w:.2f} err={err:.1e}"))
        out[name] = {"vmem_mb": vmem / 2 ** 20, "bits_per_w": bits_per_w,
                     "err": err}
        assert err < 1e-4
        assert vmem < 16 * 2 ** 20
        assert bn % 8 == 0 and bk % gs == 0
    return out


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    run(rows)
    for r in rows:
        print(",".join(r))
