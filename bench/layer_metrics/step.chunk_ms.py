"""Mean wall time of the engine steps whose block carried a prompt chunk
over the window (the program's `serve.step` spans of kind "chunk",
`EngineStats.step_times`)."""
from bench import program_spans


def read(ctx):
    return program_spans.step_kind_ms(ctx, "chunk")
