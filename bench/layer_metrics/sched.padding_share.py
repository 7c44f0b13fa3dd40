"""Share of the dispatched token positions that held padding, over the
window (`EngineStats.padding_waste`, weighted by each engine's dispatches
when a Router holds several)."""


def read(ctx):
    st = ctx["stats"]
    n = sum(s.dispatches for s in st)
    if not n:
        return None
    return 100.0 * sum(s.padding_waste * s.dispatches for s in st) / n
