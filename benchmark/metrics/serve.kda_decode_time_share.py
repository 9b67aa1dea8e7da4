"""Share of device busy time spent in the Pallas KDA decode kernel
(`kda_decode`, every layer's calls), from the device trace of the traced
stretch of the loop. None where the program has no kernel of that name
(the parent of the PR that added it)."""
from benchmark.harness import kda_bytes

KERNEL = "kda_decode"

NAME = "serve.kda_decode_time_share"
UNIT = "%"
BETTER = "lower"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def compute(ctx):
    ps = kda_bytes.traced(ctx)
    if ps is None or not kda_bytes.has_kernel(ps, KERNEL):
        return None
    return 100.0 * ps.kernel_s(KERNEL) / ctx.trace.busy_s
