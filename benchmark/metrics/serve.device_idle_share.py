"""Share of the traced stretch of the loop in which no operation ran on the
device: 1 - union of device-op intervals / traced window."""

NAME = "serve.device_idle_share"
UNIT = "%"
BETTER = "lower"
LAYER = "device"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def compute(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
