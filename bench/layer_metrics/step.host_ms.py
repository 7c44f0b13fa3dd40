"""Host time per engine step over the window: the program's `serve.step`
spans less the `serve.wait` spans inside them (the host blocked on the
device's result), per `serve.step` (`EngineStats.step_times`)."""
from bench import program_spans


def read(ctx):
    sp = [t.spans for t in program_spans.step_times(ctx)]
    n = sum(s[program_spans.STEP][0] for s in sp if program_spans.STEP in s)
    if not n:
        return None
    host = sum(s.get(program_spans.STEP, (0, 0.0))[1]
               - s.get(program_spans.WAIT, (0, 0.0))[1] for s in sp)
    return 1e3 * host / n
