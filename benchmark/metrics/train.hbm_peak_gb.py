"""Peak device memory in use up to the end of the measured window
(memory_stats()['peak_bytes_in_use'] on the fullest chip), in 1e9 bytes."""

NAME = "train.hbm_peak_gb"
UNIT = "GB"
BETTER = "lower"
LAYER = "device"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def compute(ctx):
    return ctx.samples["hbm_peak_bytes"] / 1e9
