"""Bytes the one-token step of a state-space (Mamba-2) mixer has to
move, from the configuration's shapes, and the device time that step
took in a traced run. The mixer keeps one float32 matrix `S`
[mamba_d_head, mamba_d_state] a head and slot; a decode tick reads it
and writes it, once each, for every slot whose state the dispatched
program updates (`state_slots` of the engine's `engine.decode.dispatch`
span), on every block. Beside it the step reads, float32, a head's x
row and its dt and decay scalars and a GROUP's B and C rows (once a
group, not once a head), and writes a head's y row. The convolution's
tail is read and written by other ops and is not counted. The counts are
of what the ALGORITHM needs: a slot that is not decoding has its rows
passed through by the program that ships and is counted here as nothing,
which makes the roofline share smaller, never larger.

The step is XLA ops (`kernels/ssd.py` `ssd_step_arrays`), which the
compiler fuses: no instruction on the trace carries a name of the
program's choosing. What marks it is what it touches: inside a decode
program, every op whose HLO text names a block's WHOLE state array
`f32[max_slots, heads, d_head, d_state]`, as a result or as an operand,
is a pass over a state, the update itself and any copy or second pass a
compiler may add; `state_update_s` sums their SELF time."""
from __future__ import annotations

import bisect
from typing import Optional

# a traced run's spans and programs: the same reader the latent cell's
# metric files come through
from .mla_bytes import traced  # noqa: F401
from .trace_reduce import self_times

F32 = 4
DECODE_PROGRAM = "serve_decode_"


def state_bytes_per_slot_layer(config: dict) -> int:
    """One slot's `S` on one block."""
    return F32 * int(config["mamba_n_heads"]) * int(config["mamba_d_head"]) \
        * int(config["mamba_d_state"])


def decode_bytes(config: dict, state_slots: int) -> int:
    """What the step moves for one dispatch over `state_slots` decoding
    slots, all blocks: S read + S written; x in and y out (mamba_d_head
    a head), dt and the decay (a scalar a head), B and C (mamba_d_state
    a group)."""
    heads = int(config["mamba_n_heads"])
    rows = F32 * (2 * heads * int(config["mamba_d_head"]) + 2 * heads
                  + 2 * int(config["mamba_n_groups"])
                  * int(config["mamba_d_state"]))
    return int(config["num_hidden_layers"]) * int(state_slots) * (
        2 * state_bytes_per_slot_layer(config) + rows)


def state_array(config: dict) -> Optional[str]:
    """A block's whole state array as an op's HLO text names it; None
    for a configuration that keeps no such state."""
    if "mamba_d_state" not in config:
        return None
    return "f32[{},{},{},{}]".format(
        int(config["engine"]["max_slots"]), int(config["mamba_n_heads"]),
        int(config["mamba_d_head"]), int(config["mamba_d_state"]))


def state_update_s(ctx, ps) -> Optional[float]:
    """Summed SELF device time, first chip, of the ops inside the traced
    window's decode programs whose HLO text names a block's whole state
    array. None where no op does (another configuration, a program that
    keeps its state otherwise)."""
    needle = state_array(ctx.cell.config)
    runs = sorted((m.start_ns, m.end_ns) for m in ps.programs(DECODE_PROGRAM))
    if needle is None or not runs:
        return None
    starts = [a for a, _ in runs]
    ops = ctx.trace._ops[ctx.trace.planes[0]]
    total, found = 0.0, False
    for op, own in zip(ops, self_times(ops)):
        i = bisect.bisect_right(starts, op.start_ns) - 1
        if needle in op.name and i >= 0 and op.end_ns <= runs[i][1]:
            total, found = total + own, True
    return total / 1e9 if found else None
