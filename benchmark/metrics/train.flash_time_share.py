"""Share of device busy time spent in the three flash-attention kernels
(forward, dq, dkv), from the device trace of the traced steps."""
# The Pallas kernels carry no name of their own in the trace (no
# `pl.pallas_call(name=...)` in the program): a kernel is a device op whose
# HLO text holds this target, and in this cell's programs every such op
# is one of this layer's kernels. A program that mixes kernel families
# needs stable names first (PERF.md, Open questions).
KERNELS = ('custom_call_target="tpu_custom_call"',)

NAME = "train.flash_time_share"
UNIT = "%"
BETTER = "lower"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def compute(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.kernel_s(KERNELS) / ctx.trace.busy_s
