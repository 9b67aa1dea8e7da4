"""Roofline share of the Pallas paged-decode kernel: the least time the chip
could take to read the K and V rows the traced ticks needed (memory-bound:
`harness/paged_bytes.py` x the context tokens the engine's
`engine.decode.dispatch` spans carry, over the HBM peak) over the kernel's
summed device time.

Both sums run over the traced window: the spans that BEGAN in it, the kernel
time clipped to it. A dispatch on the window's edge is counted on one side
only: one tick of ~90, about 1%."""
from benchmark.harness import paged_bytes, program_spans

# the kernel's `pl.pallas_call(name=...)`: on the trace, the name of its HLO
# instruction (program_spans.py says what was seen); a program whose kernels
# carry no name is matched by every Pallas call of the cell, which is the
# same set here (its prefill programs hold none)
KERNEL = "paged_decode"

NAME = "serve.paged_decode_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "tpot_p90_ms"
SOURCE = "program_span"


def compute(ctx):
    ps = program_spans.for_ctx(ctx)
    if ps is None:
        return None
    tokens = sum(paged_bytes.decode_context_tokens(
        int(s.stats["ctx_tokens"]), int(s.stats["slots"]),
        int(s.stats.get("ticks", 1)))
        for s in ps.named("engine.decode.dispatch") if "ctx_tokens" in s.stats)
    if not tokens:
        return None
    least_s = tokens * paged_bytes.kv_bytes_per_token(ctx.cell.config) \
        / (ctx.peak["hbm_bytes_per_s"] * ctx.cell.chips)
    return 100.0 * least_s / ps.kernel_s(KERNEL)
