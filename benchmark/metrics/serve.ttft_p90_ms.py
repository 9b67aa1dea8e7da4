"""90th percentile over requests finished ok in the window of the time
from when a request was due (closed loop: the moment its client's last
request returned) to its first token: generator lateness + Output.ttft_ms.
A per-layer metric in a closed loop at saturation, where it is set by
which admissions share a tick and spreads by 5-6% from run to run
(PERF.md, PR 25); an open-loop cell below capacity carries it end to end."""
from benchmark.harness import stats

NAME = "serve.ttft_p90_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "host_clock"


def compute(ctx):
    return stats.tail_or_none(ctx.samples["ttft_ms"], 90)
