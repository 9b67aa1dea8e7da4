"""Share of the traced steps' wall time in which no operation ran on the
device: 1 - union of device-op intervals / traced window."""

NAME = "train.device_idle_share"
UNIT = "%"
BETTER = "lower"
LAYER = "device"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def compute(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
