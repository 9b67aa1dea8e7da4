"""What the engine holds by SLOT rather than by page, in 1e9 bytes: the
`serving.state.bytes` gauge (every state layer's arrays, all slots), set
once when the engine is built. None for a program or a model that keeps
no such state."""

NAME = "serve.state_gb"
UNIT = "GB"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def compute(ctx):
    state = ctx.samples.get("state")
    if not state or not state.get("bytes"):
        return None
    return state["bytes"] / 1e9
