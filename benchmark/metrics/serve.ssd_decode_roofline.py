"""Roofline share of a state-space mixer's one-token step in the decode
programs (`ssd_step_arrays`: XLA ops, one fusion a block on the chip):
the least time the chip could take to read and write the state the
traced ticks had to update (memory-bound: `harness/ssd_bytes.py` x the
`state_slots` the engine's `engine.decode.dispatch` spans carry, over the
HBM peak) over the summed device time of every op of a decode program
that touches a block's whole state array.

The bytes are those the ALGORITHM needs (each decoding slot's `S` once in
and once out a block, and its vectors) and the time is every pass the
program makes over a state, so the share reads the same whatever
implements the step and can only read low: the program that ships also
passes through the rows of slots that are not decoding.

A program without this span argument or such an op (another
configuration; the parent of the PR that added this one) gives None."""
from benchmark.harness import ssd_bytes

NAME = "serve.ssd_decode_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(ctx):
    ps = ssd_bytes.traced(ctx)
    took_s = ps and ssd_bytes.state_update_s(ctx, ps)
    if not took_s:
        return None
    slots = [int(s.stats["state_slots"])
             for s in ps.named("engine.decode.dispatch")
             if "state_slots" in s.stats]
    if not slots:
        return None
    need = sum(ssd_bytes.decode_bytes(ctx.cell.config, n) for n in slots)
    least_s = need / (ctx.peak["hbm_bytes_per_s"] * ctx.cell.chips)
    return 100.0 * least_s / took_s
