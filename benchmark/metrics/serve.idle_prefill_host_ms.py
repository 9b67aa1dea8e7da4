"""Device-idle time a decode tick while the host was inside `engine.prefill`
but not in its `engine.prefill.wait`: page allocation, building and
uploading the chunk, the executable call, the first token's bookkeeping."""
from benchmark.harness import program_spans

NAME = "serve.idle_prefill_host_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    return program_spans.serve_idle_ms_per_tick(ctx, "prefill_host")
