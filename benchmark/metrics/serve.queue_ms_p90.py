"""90th percentile of the time a request waited for a slot: the QUEUED
spans of Output.spans, over requests finished ok in the window."""
from benchmark.harness import stats

NAME = "serve.queue_ms_p90"
UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    return stats.tail_or_none(ctx.samples["queue_ms"], 90)
