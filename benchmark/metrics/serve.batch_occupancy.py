"""Mean number of decoding slots (Engine.num_active, read before each
step() of the window) over max_slots."""

NAME = "serve.batch_occupancy"
UNIT = "%"
BETTER = "higher"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def compute(ctx):
    active = [k[2] for k in ctx.samples["ticks"]]
    return 100.0 * sum(active) / len(active) / ctx.samples["max_slots"]
