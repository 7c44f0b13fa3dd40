"""Mean time from admission into a slot to the end of the engine step
that returned the request's first token, over the requests given their
first token in the window (`EngineStats.step_times`)."""
from bench import program_spans


def read(ctx):
    ts = program_spans.step_times(ctx)
    n = sum(t.prefills for t in ts)
    return 1e3 * sum(t.prefill_s for t in ts) / n if n else None
