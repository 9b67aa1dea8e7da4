"""Roofline share of the Pallas paged-decode kernel (`paged_decode`) in a
decoder of full and sliding-window GQA layers: the least time the chip
could take to read the K and V rows the traced ticks needed
(memory-bound: `harness/gqa_window_bytes.py` x the `ctx_tokens` and
`win_tokens` the engine's `engine.decode.dispatch` spans carry, over the
HBM peak) over the kernel's summed device time, every layer's calls.

The bytes are those the ALGORITHM needs (a full layer its whole context,
a sliding layer the rows inside its window), so the share reads the same
whatever implements the kernel and can only read low: the kernel that
ships reads a ring or a page whole.

A program without the `win_tokens` span argument (the parent of the PR
that added this configuration) gives None."""
from benchmark.harness import gqa_window_bytes

KERNEL = "paged_decode"

NAME = "serve.gqa_decode_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    ps = gqa_window_bytes.traced(ctx)
    if ps is None or not gqa_window_bytes.has_kernel(ps, KERNEL):
        return None
    need = sum(gqa_window_bytes.decode_bytes(
        ctx.cell.config, int(s.stats["ctx_tokens"]),
        int(s.stats["win_tokens"]), int(s.stats["slots"]))
        for s in ps.named("engine.decode.dispatch")
        if "win_tokens" in s.stats)
    if not need:
        return None
    least_s = need / (ctx.peak["hbm_bytes_per_s"] * ctx.cell.chips)
    return 100.0 * least_s / ps.kernel_s(KERNEL)
