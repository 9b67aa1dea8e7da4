"""90th percentile over the window's rows of the engine's step record of
`waiting + prefilling` on entry: requests that hold no decoding lane.
Beside `serve.queue_ms_p90` it says whether a queue was a standing
backlog or one parked cohort."""
from benchmark.harness import stats, step_record

NAME = "serve.backlog_lanes_p90"
UNIT = "lanes"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    rows = step_record.for_ctx(ctx)
    if not rows:
        return None
    return stats.quantile([step_record.backlog(r) for r in rows], 0.9)
