"""Median time of an Engine.step() that began with nothing waiting and
nothing prefilling: one decode dispatch, host scheduling and harvest."""
from benchmark.harness import stats

NAME = "serve.decode_tick_ms_p50"
UNIT = "ms"
BETTER = "lower"
LAYER = "model step"
MOVES = "tpot_p90_ms"
SOURCE = "host_clock"


def compute(ctx):
    pure = [1e3 * k[1] for k in ctx.samples["ticks"] if k[3] == 0 and k[4] == 0]
    return stats.median(pure) if pure else None
