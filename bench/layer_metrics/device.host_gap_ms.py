"""Device-idle time in the traced part of the window that lies inside
the program's host phases (its `serve.*` spans other than `serve.step`
and `serve.wait`), per `bench.step` span (`bench/program_spans.py`)."""
from bench import program_spans


def read(ctx):
    if ctx["trace"] is None:
        return None
    ph = program_spans.read_trace()
    if ph is None or not ph.steps:
        return None
    return 1e3 * ph.host_idle_s / ph.steps
