"""Roofline share of the Pallas KDA decode kernel (`kda_decode`): the
least time the chip could take to read and write the state the traced
ticks had to update (memory-bound: `harness/kda_bytes.py` x the
`state_slots` the engine's `engine.decode.dispatch` spans carry, over the
HBM peak) over the kernel's summed device time.

The bytes are those the ALGORITHM needs (each decoding slot's `S` once in
and once out a layer, and its vectors), so the share reads the same
whatever implements the kernel and can only read low: the kernel that
ships also copies the rows of slots that are not decoding.

A program without this span argument or this kernel (the parent of the PR
that added them) gives None."""
from benchmark.harness import kda_bytes

KERNEL = "kda_decode"

NAME = "serve.kda_decode_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    ps = kda_bytes.traced(ctx)
    if ps is None or not kda_bytes.has_kernel(ps, KERNEL):
        return None
    slots = [int(s.stats["state_slots"])
             for s in ps.named("engine.decode.dispatch")
             if "state_slots" in s.stats]
    if not slots:
        return None
    need = sum(kda_bytes.decode_bytes(ctx.cell.config, n) for n in slots)
    least_s = need / (ctx.peak["hbm_bytes_per_s"] * ctx.cell.chips)
    return 100.0 * least_s / ps.kernel_s(KERNEL)
