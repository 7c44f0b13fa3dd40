"""The int8 paged-attention kernel's share of its roofline in the traced
part of the window, as for `awq_matmul_roofline`."""


def read(ctx):
    tr = ctx["trace"]
    return None if tr is None else tr.roofline_share("paged_attention_chunk")
