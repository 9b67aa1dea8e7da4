"""Device-idle time a decode tick while the host was inside
`engine.decode.dispatch` (slot scan, variant pick, the executable call) or
its child `engine.flush_state` (dirty-row merge, block-table upload)."""
from benchmark.harness import program_spans

NAME = "serve.idle_dispatch_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    return program_spans.serve_idle_ms_per_tick(ctx, "dispatch")
