"""Device-idle time a decode tick while the host was already waiting for the
device (`engine.decode.wait`, `engine.prefill.wait`): runtime latency
between a program's end and the host's wake-up, and gaps inside programs."""
from benchmark.harness import program_spans

NAME = "serve.idle_wait_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    return program_spans.serve_idle_ms_per_tick(ctx, "wait")
