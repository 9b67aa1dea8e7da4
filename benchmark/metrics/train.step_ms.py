"""Median time of one training step in the measured window: the benchmark's
clock from one loss fetch to the next (dispatch, next batch, value fetch)."""
from benchmark.harness import stats

NAME = "train.step_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "trainer step"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def compute(ctx):
    return 1e3 * stats.median(ctx.samples["step_s"])
