"""Mean share of the KV pool's usable pages drawn (held by slots or by
the prefix index) at the engine steps' dispatches over the window
(`EngineStats.step_times`)."""
from bench import program_spans


def read(ctx):
    ts = program_spans.step_times(ctx)
    usable = sum(t.dispatches * t.pool_pages_usable for t in ts)
    if not usable:
        return None
    return 100.0 * sum(t.pool_pages_drawn for t in ts) / usable
