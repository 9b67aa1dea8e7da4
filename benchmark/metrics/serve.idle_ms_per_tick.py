"""Device-idle time of the traced stretch over the decode programs executed in
it (`serve_decode_*` on the `XLA Modules` line): what a decode tick loses to
everything that is not the device working. The seven `serve.idle_*_ms`
buckets add up to it."""
from benchmark.harness import program_spans

NAME = "serve.idle_ms_per_tick"
UNIT = "ms"
BETTER = "lower"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    return program_spans.serve_idle_ms_per_tick(ctx)
