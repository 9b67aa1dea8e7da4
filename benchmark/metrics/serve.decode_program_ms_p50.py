"""Median device time of one execution of a decode program (`serve_decode_*`
on the `XLA Modules` line of the traced stretch): the program alone, where
`serve.decode_tick_ms_p50` is the host's clock around the whole step."""
from benchmark.harness import program_spans

NAME = "serve.decode_program_ms_p50"
UNIT = "ms"
BETTER = "lower"
LAYER = "model step"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"


def compute(ctx):
    ps = program_spans.for_ctx(ctx)
    return None if ps is None else ps.program_ms_p50("serve_decode_")
