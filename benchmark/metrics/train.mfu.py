"""Model FLOP/s utilisation of the measured window: tokens/s x the FLOPs a
token needs forward and backward (benchmark/harness/flops.py, nothing
recomputed is counted) over the chips' published bf16 peak."""
from benchmark.harness import flops

NAME = "train.mfu"
UNIT = "%"
BETTER = "higher"
LAYER = "trainer step"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def compute(ctx):
    s = ctx.samples
    rate = len(s["step_s"]) * s["tokens_per_step"] / s["window_s"]
    need = flops.train_flops_per_token(ctx.cell.config, s["seq"])
    return 100.0 * rate * need / (ctx.peak["bf16_flops_per_s"] * ctx.cell.chips)
