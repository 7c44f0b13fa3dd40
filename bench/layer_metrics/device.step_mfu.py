"""Model FLOPs of the tokens the engine processed in the traced part of
the window, over its length and the chip's peak: prompt tokens run
through the model and decode rows count the layers' linears, emitted
tokens the vocabulary projection (`bench/work.py`). Attention scores are
left out, so this is a lower bound of the step's share of the peak."""
from bench import work


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.window_s:
        return None
    cfg = ctx["cfg"]
    flops = (tr.tokens_processed * work.token_flops(cfg)
             + tr.tokens_emitted * work.head_flops(cfg))
    peak = work.peaks(ctx["device"]["kind"])["flops"]
    return 100.0 * flops / tr.window_s / (peak * ctx["device"]["count"])
