"""99th percentile of a step()'s wall time (`wall_ms` of the engine's
step record) over the window's rows."""
from benchmark.harness import stats, step_record

NAME = "serve.step_ms_p99"
UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    rows = step_record.for_ctx(ctx)
    if not rows:
        return None
    return stats.quantile([r["wall_ms"] for r in rows], 0.99)
