"""Roofline share of the Pallas paged LATENT decode kernel
(`paged_mla_decode`): the least time the chip could take to read the
latent rows the traced ticks needed (memory-bound: `harness/mla_bytes.py`
x the `sel_tokens` and `win_tokens` the engine's `engine.decode.dispatch`
spans carry, over the HBM peak) over the kernel's summed device time.

The bytes are those the ALGORITHM needs (at most `index_topk` rows a slot
on a full layer, the window's rows on a sliding one, unpadded), so the
share reads the same whatever implements the kernel and can only read
low: the kernel that ships streams every page of a full layer's context
and masks what was not selected.

A program without these span arguments (the parent of the PR that added
them) gives None."""
from benchmark.harness import mla_bytes

KERNEL = "paged_mla_decode"

NAME = "serve.mla_decode_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    ps = mla_bytes.traced(ctx)
    if ps is None:
        return None
    spans = [s for s in ps.named("engine.decode.dispatch")
             if "sel_tokens" in s.stats and "win_tokens" in s.stats]
    if not spans:
        return None
    need = sum(mla_bytes.decode_attention_bytes(
        ctx.cell.config, int(s.stats["sel_tokens"]),
        int(s.stats["win_tokens"])) for s in spans)
    least_s = need / (ctx.peak["hbm_bytes_per_s"] * ctx.cell.chips)
    return 100.0 * least_s / ps.kernel_s(KERNEL)
