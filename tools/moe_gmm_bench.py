"""Times the grouped matmuls of `kernels.moe.grouped_ffn_gated` on the chip,
call by call: `jax.lax.ragged_dot` (XLA's grouped matmul, K in tiles of 512)
against the Pallas kernel `kernels.moe.gmm` (K untiled) at several column
tiles, on the slabs the three expert cells of the benchmark run.

    chiprun -- python tools/moe_gmm_bench.py            # the table
    python tools/moe_gmm_bench.py --describe            # compile only, no chip

A row of the table is one call: milliseconds as the mean of ``--calls``
dispatches queued back to back (the device never waits for the host at
0.5 ms a call), the bytes the call needs (the held experts' matrices that
have a row, the rows once, the output once) and that over the time as a
share of the HBM peak. `--describe` compiles every variant for a described
v5e instead (what Mosaic refuses, it refuses here) and times nothing.
Writes chiprun_out/moe_gmm_bench.json.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import moe

HBM_BYTES_PER_S = 819e9         # TPU v5e (Google Cloud documentation)

# cell: hidden, expert width, experts routed over (an eighth held), tokens
# of the prefill program and lanes of the decode program, slab rows of each
# (MoELayer._slab_rows(tokens * 8, 8))
CELLS = {
    "mixed128": (6144, 2048, 128, {"prefill": (1536, 3200),
                                   "decode": (128, 288)}),
    "chat96": (4096, 1280, 320, {"prefill": (1024, 2176),
                                 "decode": (96, 224)}),
    "notes48": (5120, 1536, 256, {"prefill": (2048, 4224),
                                  "decode": (48, 96)}),
}
TOP_K, SHARE = 8, 8


def held_sizes(rng, tokens, experts, slab):
    """Group sizes of the first slab under a uniform router: each token
    picks TOP_K distinct experts of `experts`; this chip holds the first
    eighth."""
    held = experts // SHARE
    picks = np.stack([rng.choice(experts, TOP_K, replace=False)
                      for _ in range(tokens)])
    counts = np.bincount(picks[picks < held], minlength=held)
    ends = np.minimum(np.cumsum(counts), slab)
    return np.diff(ends, prepend=0).astype(np.int32)


def column_tiles(n):
    return [n // parts for parts in (1, 2, 3, 4, 6)
            if n % parts == 0 and (n // parts) % 128 == 0
            and n // parts >= 256]


def variants(m, k, n):
    """name -> fn(lhs, w, w2, sizes) for one [m, k] x [E, k, n] call."""
    tm = moe.gmm_row_tile(m)

    def ragged(lhs, w, w2, gs):
        return jax.lax.ragged_dot(lhs, w, gs,
                                  preferred_element_type=lhs.dtype)

    out = {"ragged_dot": ragged}
    for tn in column_tiles(n):
        if moe.gmm_tiles(m, k, tn, jnp.bfloat16)[1] != tn:
            continue            # the block does not fit the VMEM budget

        def kernel(lhs, w, w2, gs, tn=tn):
            return moe.gmm(lhs, (w,), moe.gmm_metadata(gs, m, tm), tn=tn)
        out[f"gmm_tn{tn}"] = kernel
    return out


def gate_up_variants(m, k, n):
    """silu(lhs·w) * (lhs·w2): two ragged_dots + the XLA pass, two kernel
    calls + the pass, one fused kernel call at several column tiles."""
    tm = moe.gmm_row_tile(m)

    def pass_(g, u):
        return (jax.nn.silu(g.astype(jnp.float32))
                * u.astype(jnp.float32)).astype(g.dtype)

    def ragged(lhs, w, w2, gs):
        dt = lhs.dtype
        return pass_(jax.lax.ragged_dot(lhs, w, gs, preferred_element_type=dt),
                     jax.lax.ragged_dot(lhs, w2, gs,
                                        preferred_element_type=dt))

    def two(lhs, w, w2, gs):
        md = moe.gmm_metadata(gs, m, tm)
        return pass_(moe.gmm(lhs, (w,), md), moe.gmm(lhs, (w2,), md))

    out = {"gate_up_ragged": ragged, "gate_up_2gmm": two}
    for tn in column_tiles(n):
        if moe.gmm_tiles(m, k, tn, jnp.bfloat16, 2)[1] != tn:
            continue

        def fused(lhs, w, w2, gs, tn=tn):
            return moe.gmm(lhs, (w, w2), moe.gmm_metadata(gs, m, tm), tn=tn)
        out[f"gate_up_fused_tn{tn}"] = fused
    return out


def time_call(fn, args, calls):
    out = fn(*args)
    out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / calls, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--cells", default=",".join(CELLS))
    a = ap.parse_args()
    if a.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: a time from another backend is no "
                 "measurement (use --describe to compile only)")
    rng = np.random.default_rng(a.seed)
    rows_out = []
    for cell in a.cells.split(","):
        h, f, experts, programs = CELLS[cell]
        held = experts // SHARE
        for program, (tokens, slab) in programs.items():
            sizes = held_sizes(rng, tokens, experts, slab)
            for shape, k, n, table in (("gate", h, f, variants),
                                       ("down", f, h, variants),
                                       ("gate_up", h, f, gate_up_variants)):
                specs = [((slab, k), jnp.bfloat16), ((held, k, n), jnp.bfloat16),
                         ((held, k, n), jnp.bfloat16), ((held,), jnp.int32)]
                n_w = 2 if shape == "gate_up" else 1
                need = 2 * (n_w * int((sizes > 0).sum()) * k * n
                            + slab * k + slab * n)
                ref = None
                for name, fn in table(slab, k, n).items():
                    row = dict(cell=cell, program=program, shape=shape,
                               rows=slab, live=int(sizes.sum()), k=k, n=n,
                               groups=held, variant=name, need_mb=need / 1e6)
                    if a.describe:
                        jax.jit(fn).lower(*[
                            jax.ShapeDtypeStruct(s, d, sharding=chip)
                            for s, d in specs]).compile()
                        row["compiled"] = True
                    else:
                        keys = jax.random.split(jax.random.PRNGKey(a.seed), 3)
                        args = [jax.random.normal(keys[0], specs[0][0],
                                                  jnp.bfloat16)]
                        args += [jax.random.normal(kk, specs[1][0],
                                                   jnp.bfloat16) * k ** -0.5
                                 for kk in keys[1:]]
                        args.append(jnp.asarray(sizes))
                        sec, out = time_call(jax.jit(fn), args, a.calls)
                        live = np.asarray(out[:int(sizes.sum())], np.float32)
                        if ref is None:
                            ref = live
                        row.update(ms=sec * 1e3,
                                   hbm_share=need / sec / HBM_BYTES_PER_S,
                                   max_diff_vs_ragged=float(
                                       np.abs(live - ref).max()))
                        del args, out
                    rows_out.append(row)
                    print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_gmm_bench.json", "w") as fh:
        json.dump(rows_out, fh, indent=1)


if __name__ == "__main__":
    main()
