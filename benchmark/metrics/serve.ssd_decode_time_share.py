"""Share of device busy time spent in a state-space mixer's one-token
step (every op of a decode program that touches a block's whole state
array: `harness/ssd_bytes.py` `state_update_s`; every block's), from the
device trace of the traced stretch of the loop. None where the program
has no such op (another configuration; the parent of the PR that added
this one)."""
from benchmark.harness import ssd_bytes

NAME = "serve.ssd_decode_time_share"
UNIT = "%"
BETTER = "lower"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def compute(ctx):
    ps = ssd_bytes.traced(ctx)
    took_s = ps and ssd_bytes.state_update_s(ctx, ps)
    return 100.0 * took_s / ctx.trace.busy_s if took_s else None
