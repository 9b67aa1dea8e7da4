"""Device-idle time a decode tick while the host was outside the engine: in a
`bench.*` span's own time (the load generator) or under no span at all."""
from benchmark.harness import program_spans

NAME = "serve.idle_outside_step_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    return program_spans.serve_idle_ms_per_tick(ctx, "outside_step")
