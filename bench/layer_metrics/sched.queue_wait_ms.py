"""Mean time from submit to admission into a slot, over the requests the
engines admitted in the window (`EngineStats.step_times`)."""
from bench import program_spans


def read(ctx):
    ts = program_spans.step_times(ctx)
    n = sum(t.queue_waits for t in ts)
    return 1e3 * sum(t.queue_wait_s for t in ts) / n if n else None
