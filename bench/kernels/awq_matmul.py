"""One call of the fused AWQ int4 matmul (`kernels/awq_matmul.py` of the
program), counted from its HLO instruction text."""
from bench.trace import shapes
from bench.work import awq_matmul


def work(text: str, cfg: dict) -> tuple[int, int]:
    """(FLOPs, bytes) of the call: out f32[M, N], operands x bf16[M, K],
    qweight s32[K/8, N], ..."""
    (_, out), (_, x) = shapes(text)[:2]
    return awq_matmul(x[0], x[1], out[1], cfg["quantization"]["group_size"])
