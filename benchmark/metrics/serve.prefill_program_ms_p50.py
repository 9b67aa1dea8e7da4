"""Median device time of one execution of a prefill program
(`serve_prefill_<bucket>`, all buckets of the traced stretch together): the
program alone, where `serve.prefill_ms_p50` is the request's PREFILL span,
which also holds the decode program dispatched ahead of it."""
from benchmark.harness import program_spans

NAME = "serve.prefill_program_ms_p50"
UNIT = "ms"
BETTER = "lower"
LAYER = "model step"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"


def compute(ctx):
    ps = program_spans.for_ctx(ctx)
    return None if ps is None else ps.program_ms_p50("serve_prefill_")
