"""The AWQ int4 matmul kernel's share of its roofline in the traced part
of the window: the least time of all its calls' FLOPs and bytes at the
chip's peaks (`bench/work.py`), over the sum of their device durations."""


def read(ctx):
    tr = ctx["trace"]
    return None if tr is None else tr.roofline_share("awq_matmul")
