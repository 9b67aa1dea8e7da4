"""Milliseconds of the window spent in SLOW steps: the sum of `wall_ms`
over the rows of the engine's step record that the engine's own rule
marked (`slow`: longer than max(250 ms, 8 x the median of the 256 steps
before)). 0.0 in a clean run; the stall as a number beside the
tokens/s it cost."""
from benchmark.harness import step_record

NAME = "serve.slow_step_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    rows = step_record.for_ctx(ctx)
    if not rows:
        return None
    return float(sum(r["wall_ms"] for r in rows if r["slow"]))
