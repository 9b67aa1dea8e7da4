"""Preemptions suffered by the requests that finished in the window
(sum of Output.preemptions)."""

NAME = "serve.preemptions"
UNIT = "count"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "tpot_p90_ms"
SOURCE = "program_counter"


def compute(ctx):
    return ctx.samples["preemptions"]
