"""Share of prompt tokens that aliased resident prefix pages instead of
running through the model, over the window (`EngineStats`)."""


def read(ctx):
    run = sum(s.prefill_tokens for s in ctx["stats"])
    skipped = sum(s.prefill_tokens_skipped for s in ctx["stats"])
    if not run + skipped:
        return None
    return 100.0 * skipped / (run + skipped)
