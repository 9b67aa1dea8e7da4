"""Roofline share of the Pallas paged-decode kernel (`paged_decode`) in a
decoder whose every block holds a paged GQA cache beside something else
(Falcon-H1: 20 query / 4 KV heads of 128, a group of 5): the least time
the chip could take to read the K and V rows the traced ticks needed
(memory-bound: `harness/paged_gqa_bytes.py` x the `ctx_tokens` and
`slots` the engine's `engine.decode.dispatch` spans carry, over the HBM
peak) over the kernel's summed device time, every block's calls.

`serve.paged_decode_roofline` divides hidden_size by the head count for
a head's width (256 here, not 128) and moves `tpot_p90_ms`;
`serve.gqa_decode_roofline` reads `layer_types`. The bytes are those the
ALGORITHM needs, so the share can only read low: the kernel reads a page
whole.

A configuration without `head_dim`, or a program without the kernel,
gives None."""
from benchmark.harness import paged_gqa_bytes

KERNEL = "paged_decode"

NAME = "serve.paged_gqa_decode_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    ps = paged_gqa_bytes.traced(ctx)
    if ps is None or "head_dim" not in ctx.cell.config \
            or not paged_gqa_bytes.has_kernel(ps, KERNEL):
        return None
    need = sum(paged_gqa_bytes.decode_bytes(
        ctx.cell.config, int(s.stats["ctx_tokens"]), int(s.stats["slots"]))
        for s in ps.named("engine.decode.dispatch")
        if "ctx_tokens" in s.stats)
    if not need:
        return None
    least_s = need / (ctx.peak["hbm_bytes_per_s"] * ctx.cell.chips)
    return 100.0 * least_s / ps.kernel_s(KERNEL)
