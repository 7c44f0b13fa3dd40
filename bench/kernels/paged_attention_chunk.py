"""One call of the int8 paged-attention kernel (`kernels/paged_attention.py`
of the program), counted from its HLO instruction text."""
from bench.trace import shapes
from bench.work import paged_attention_chunk


def work(text: str, cfg: dict) -> tuple[int, int]:
    """(FLOPs, bytes) of the call: out f32[B, Hkv, rows, hd]; operands
    tables, pos, rpos, q, k pool, ..."""
    sh = shapes(text)
    b, hkv, _, hd = sh[0][1]
    n_pages = sh[1][1][0] // b
    c = sh[2][1][0] // b
    page = sh[5][1][2]
    g = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return paged_attention_chunk(b, c, hkv, g, hd, n_pages, page)
