"""Median length of a PREFILL span (Output.spans) over requests finished ok
in the window: one monolithic prefill call, dispatch to first token."""
from benchmark.harness import stats

NAME = "serve.prefill_ms_p50"
UNIT = "ms"
BETTER = "lower"
LAYER = "model step"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"


def compute(ctx):
    return stats.median(ctx.samples["prefill_ms"])
