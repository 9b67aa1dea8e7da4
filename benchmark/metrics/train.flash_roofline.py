"""Roofline share of the flash-attention kernels: the least time the chip
could take for the FLOPs the algorithm needs in the traced steps (7 matmuls
over the causal, windowed scores; compute-bound, the bytes are a few
percent of that time) over the kernels' summed device time."""
from benchmark.harness import flops
# The Pallas kernels carry no name of their own in the trace (no
# `pl.pallas_call(name=...)` in the program): a kernel is a device op whose
# HLO text holds this target, and in this cell's programs every such op
# is one of this layer's kernels. A program that mixes kernel families
# needs stable names first (PERF.md, Open questions).
KERNELS = ('custom_call_target="tpu_custom_call"',)

NAME = "train.flash_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def compute(ctx):
    if ctx.trace is None:
        return None
    s = ctx.samples
    need = s["traced_steps"] * flops.flash_flops_per_step(
        ctx.cell.config, s["batch"], s["seq"])
    least_s = need / (ctx.peak["bf16_flops_per_s"] * ctx.cell.chips)
    return 100.0 * least_s / ctx.trace.kernel_s(KERNELS)
