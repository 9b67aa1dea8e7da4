"""Device-idle time a decode tick while the host was inside
`engine.ensure_pages` or `engine.bookkeeping` (audit, counters, gauges,
histograms, compile accounting)."""
from benchmark.harness import program_spans

NAME = "serve.idle_bookkeeping_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    return program_spans.serve_idle_ms_per_tick(ctx, "bookkeeping")
