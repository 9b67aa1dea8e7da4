"""How many routing groups a live token's picks fall in, an expert layer
and decode tick (`serving.moe.groups_reached` / `serving.moe.tokens_routed`,
counted inside the decode program and added up by the engine). The
router computes every expert's score on every chip, so the count is
exact: it is how many chips of the stated deployment (one group a chip)
the token's dispatch reaches. DeepSeek-V2's group limit caps it at
`topk_group` (3); six plain picks of 160 would spread over ~4.4 of 8.
None where the program counts no groups (a router without them)."""

NAME = "serve.moe_groups_per_token"
UNIT = "groups"
BETTER = "lower"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def compute(ctx):
    moe = ctx.samples.get("moe")
    if not moe or not moe.get("tokens_routed"):
        return None
    return moe["groups_reached"] / moe["tokens_routed"]
