"""Share of device busy time spent in the Pallas paged-decode kernel
(`paged_decode`: the full layers' pages and the sliding layers' rings,
every layer's calls), from the device trace of the traced stretch of the
loop. None where the program has no kernel of that name."""
from benchmark.harness import gqa_window_bytes

KERNEL = "paged_decode"

NAME = "serve.gqa_decode_time_share"
UNIT = "%"
BETTER = "lower"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def compute(ctx):
    ps = gqa_window_bytes.traced(ctx)
    if ps is None or not gqa_window_bytes.has_kernel(ps, KERNEL):
        return None
    return 100.0 * ps.kernel_s(KERNEL) / ctx.trace.busy_s
