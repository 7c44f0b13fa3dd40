"""The yardstick: published chip peaks, and the operations and bytes of
each kernel call and of a model token, computed from shapes alone.

Peaks come from one table keyed by ``device_kind``; a chip that is not in
it is an error, never a default. The peak figures were first written in
the program's roofline module (``src/repro/roofline``); they are kept
here so that no change to the program can move them. A kernel's module
(``bench/kernels/<op>.py``) reads a call's shapes and counts it with the
formulas here; a model token's FLOPs are its architecture module's
(``bench/archs/<arch>.py``).
"""
from __future__ import annotations

from bench import plugins

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}: add them to bench/work.py "
                         f"with their source") from None


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peak["flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def awq_matmul(m: int, k: int, n: int, group_size: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one fused int4 matmul ``x [M, K] @ W [K, N]``:
    bf16 activations in, f32 out, the weight read once as int4 codes with
    one f32 scale and one int8 zero per group and column."""
    flops = 2 * m * k * n
    nbytes = (m * k * 2 + k * n // 2 + (k // group_size) * n * (4 + 1)
              + m * n * 4)
    return flops, nbytes


def paged_attention_chunk(b: int, c: int, hkv: int, g: int, hd: int,
                          n_pages: int, page: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one paged-attention call over int8 pages as the
    call is shaped: ``b`` rows of ``c`` queries, each row reading
    ``n_pages`` pages of ``page`` positions for each of ``hkv`` KV heads
    (one int8 code per element of K and V, one f32 scale per position
    and head of each), the ``c * g`` query rows of a head padded to at
    least 8; bf16 queries in, f32 out."""
    rows = max(8, c * g)
    keys = n_pages * page
    flops = 4 * b * hkv * rows * keys * hd
    nbytes = (b * hkv * keys * (2 * hd + 2 * 4)
              + b * hkv * rows * hd * (2 + 4))
    return flops, nbytes


def token_flops(cfg: dict) -> int:
    """Model FLOPs of one token through the layers' linears, as the
    configuration's architecture module counts them: active FLOPs, so
    only the experts a token is routed to (attention scores and the head
    excluded; see `head_flops`)."""
    return plugins.arch(cfg).token_flops(cfg)


def head_flops(cfg: dict) -> int:
    """FLOPs of the vocabulary projection of one sampled row."""
    return plugins.arch(cfg).head_flops(cfg)
