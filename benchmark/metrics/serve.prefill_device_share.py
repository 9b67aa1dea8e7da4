"""Share of device-busy time of the traced stretch spent inside prefill
programs (`serve_prefill_*`): what prefill takes from the decoders."""
from benchmark.harness import program_spans

NAME = "serve.prefill_device_share"
UNIT = "%"
BETTER = "lower"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def compute(ctx):
    ps = program_spans.for_ctx(ctx)
    if ps is None:
        return None
    return 100.0 * ps.program_busy_s("serve_prefill_") / ctx.trace.busy_s
