"""Mean wall time of the engine steps that carried only decode rows over
the window (the program's `serve.step` spans of kind "decode",
`EngineStats.step_times`)."""
from bench import program_spans


def read(ctx):
    return program_spans.step_kind_ms(ctx, "decode")
