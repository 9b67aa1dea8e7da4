"""Device-idle time a decode tick while the host was inside
`engine.add_request`, `engine.expire`, `engine.admit`, or in
`engine.step` itself outside every child span."""
from benchmark.harness import program_spans

NAME = "serve.idle_schedule_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    return program_spans.serve_idle_ms_per_tick(ctx, "schedule")
