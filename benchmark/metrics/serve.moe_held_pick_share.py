"""Share of the router's token-expert picks that fell on experts this
chip holds, over the run's decode ticks (`serving.moe.picks_held` /
`serving.moe.picks_total`, counted inside the decode program and added up
by the engine). With 32 of 256 experts held and an unbiased router it
reads 12.5; a router collapsing onto or off the share moves it, and with
it the work the expert layers do here."""

NAME = "serve.moe_held_pick_share"
UNIT = "%"
BETTER = "higher"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def compute(ctx):
    moe = ctx.samples.get("moe")
    if not moe or not moe.get("picks_total"):
        return None
    return 100.0 * moe["picks_held"] / moe["picks_total"]
