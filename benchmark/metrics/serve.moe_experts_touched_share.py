"""Share of the held experts that got at least one token, a decode tick
and expert layer (`serving.moe.experts_touched` over the held experts x
`serving.moe.layer_ticks`). What is left to 100 is what a grouped matmul
that reads only touched experts' weights saves of the expert weight
stream at this batch; at 48 slots x 8 picks x 32/256 = 48 picks on 32
experts, 1 - (31/32)**48 = 78 is expected."""

NAME = "serve.moe_experts_touched_share"
UNIT = "%"
BETTER = "lower"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def compute(ctx):
    moe = ctx.samples.get("moe")
    if not moe or not moe.get("layer_ticks"):
        return None
    return 100.0 * moe["experts_touched"] \
        / (moe["held"] * moe["layer_ticks"])
