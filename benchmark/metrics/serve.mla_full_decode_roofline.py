"""Roofline share of the Pallas paged latent decode kernel
(`paged_mla_decode`) on layers that attend EVERY row of their context (no
selection, no window): the least time the chip could take to read and
score the latent rows the traced ticks needed (`harness/mla_full_cost.py`:
the larger of the bytes over the HBM peak and the FLOPs over the bf16
peak, a row sitting at the v5e's ridge at 128 heads) x the `ctx_tokens`
+ `slots` of the engine's `engine.decode.dispatch` spans x the layers,
over the kernel's summed device time.

`serve.mla_decode_roofline` reads `layer_types` and the selected rows;
this one reads every row. Lane padding and whole pages are not counted,
so the share can only read low. A program without the kernel, or a run
without dispatch spans, gives None."""
from benchmark.harness import mla_bytes, mla_full_cost

KERNEL = "paged_mla_decode"

NAME = "serve.mla_full_decode_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    ps = mla_bytes.traced(ctx)
    if ps is None or not mla_bytes.has_kernel(ps, KERNEL):
        return None
    rows = sum(mla_full_cost.decode_rows(
        ctx.cell.config, int(s.stats["ctx_tokens"]), int(s.stats["slots"]))
        for s in ps.named("engine.decode.dispatch")
        if "ctx_tokens" in s.stats)
    if not rows:
        return None
    least_s = mla_full_cost.least_seconds(ctx.cell.config, ctx.peak, rows,
                                          ctx.cell.chips)
    return 100.0 * least_s / ps.kernel_s(KERNEL)
