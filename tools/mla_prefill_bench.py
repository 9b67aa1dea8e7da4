"""Times a full latent layer's chunked prefill attention on the chip, piece
by piece, at the shapes `serve-dots3-5l-notes48` runs (128 heads of 128 +
64, values of 128, the indexer's 64 heads of 128 and top-2,048): the XLA
blocks of `kernels.mla_prefill.mla_block_xla` against the Pallas kernel
`mla_flash_prefill`, and what neither replaces (the indexer's products,
`topk_mask`, the gather and the K/V expansion).

    chiprun -- python tools/mla_prefill_bench.py            # the table
    python tools/mla_prefill_bench.py --describe            # compile only, no chip

A row of the table is one piece at one (chunk tokens s, gathered keys L),
the chunk being the LAST s positions of the L (the fewest key tiles the
kernel may skip): milliseconds as the mean of ``--calls`` dispatches queued
back to back; for the two attention variants the dense matmul FLOPs
(2 x 128 heads x s x L x (192 + 128), what the XLA blocks multiply) over the
time as a share of the MXU's peak, and the share of key tiles the kernel
visits. `layer_*` is the whole layer's chunk step through
`Dots3LatentAttention.forward` with its cache (projections, paged write,
gather, the switch over key lengths, attention, output projection) on
either route. `--describe` compiles every piece for a described v5e
instead (what Mosaic refuses, it refuses here) and times nothing.
Writes chiprun_out/mla_prefill_bench.json.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import place
from paddle_tpu.jit.functional import functional_call, get_params
from paddle_tpu.kernels import mla_prefill as mp
from paddle_tpu.kernels import paged_attention as paged
from paddle_tpu.nn.initializer.lazy_init import LazyGuard
from paddle_tpu.nn.layer.layers import param_dtype
from paddle_tpu.text.models.dots3_note import (FULL, Dots3LatentAttention,
                                               Dots3NoteConfig, topk_mask)

MXU_FLOPS_PER_S = 197e12        # TPU v5e, bf16 (Google Cloud documentation)
# (chunk tokens, gathered keys): the cell's buckets against its key lengths
SHAPES = ((512, 1024), (2048, 2048), (1024, 3072), (2048, 4096),
          (512, 5120), (2048, 5120))
# (query tile, key tile) beside the rule's 256 x 512, at the two 2,048-token
# chunks
SWEEP = ((128, 512), (512, 512), (256, 256), (256, 1024), (512, 1024))
PAGE, PAGES, BT = 128, 1921, 40         # the cell's pools and block table
BF = jnp.bfloat16


def pieces(attn, s, L):
    """name -> (fn, argument specs) of one (s, L); every fn is jitted
    alone, its inputs already on the device."""
    H, dk, dv = attn.heads, attn.d_nope + attn.d_rope, attn.d_v
    J, dI, qb = attn.idx_heads, attn.idx_dim, attn.q_block
    nblk, pos0 = s // qb, L - s

    def blocks(a):                   # [1, s, ...] -> [nblk, 1, qb, ...]
        return jnp.moveaxis(a.reshape((1, nblk, qb) + a.shape[2:]), 1, 0)

    def causal():
        return (jnp.arange(L)[None, None]
                <= pos0 + jnp.arange(s)[None, :, None])

    def expand(pool, bt, w_kvb):
        rows = paged.gather_rows(pool, bt[:, :L // PAGE])
        w = w_kvb.reshape(attn.kv_rank, H, attn.d_nope + dv)
        c_kv = rows[..., :attn.kv_rank]
        k_nope = jnp.einsum("bLc,chd->bhLd", c_kv, w[..., :attn.d_nope])
        k_rope = rows[..., attn.kv_rank:attn.kv_rank + attn.d_rope]
        return (jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, None], k_nope.shape[:3] + (attn.d_rope,))], -1),
            jnp.einsum("bLc,chd->bhLd", c_kv, w[..., attn.d_nope:]))

    def indexer(qI, wI, kI):
        return jax.lax.map(lambda a: jnp.einsum(
            "bqj,bqjL->bqL", a[1], jax.nn.relu(jnp.einsum(
                "bqjd,bLd->bqjL", a[0], kI,
                preferred_element_type=jnp.float32))),
            (blocks(qI), blocks(wI)))

    def select(I):                   # [nblk, 1, qb, L] -> keep [1, s, L]
        keep = jax.lax.map(
            lambda a: a[1] & topk_mask(
                jnp.where(a[1], a[0], -jnp.inf).reshape(qb, L),
                attn.topk).reshape(1, qb, L),
            (I, blocks(causal())))
        return jnp.moveaxis(keep, 0, 1).reshape(1, s, L).astype(jnp.int8)

    def xla_blocks(q, k, v, keep):   # tokens-major, as the XLA route has it
        out = jax.lax.map(
            lambda a: mp.mla_block_xla(a[0], k, v, a[1] != 0, attn.scale),
            (blocks(q), blocks(keep)))
        return jnp.moveaxis(out, 0, 1).reshape(1, s, H * dv)

    def flash(q, k, v, keep, tiles=None):        # heads-major
        return mp.mla_flash_prefill(q, k, v, keep, attn.scale, tiles=tiles)

    def layer(params, u, bt, pool, ki_pool):
        (out, cache), _ = functional_call(
            attn, params, {}, (u,), dict(
                kv_cache=(pool, ki_pool, bt),
                cache_index=jnp.full((1,), pos0, jnp.int32)))
        return out, cache[0], cache[1]

    pool = ((PAGES, PAGE, attn.row), BF)
    qkv_t = [((1, s, H, dk), BF), ((1, L, H, dk), BF), ((1, L, H, dv), BF)]
    qkv_h = [((1, H, s, dk), BF), ((1, H, L, dk), BF), ((1, H, L, dv), BF)]
    keep = ((1, s, L), jnp.int8)
    out = {
        "expand": (expand, [pool, ((1, BT), jnp.int32), (
            (attn.kv_rank, H * (attn.d_nope + dv)), BF)]),
        "indexer": (indexer, [((1, s, J, dI), BF), ((1, s, J), jnp.float32),
                              ((1, L, dI), BF)]),
        "xla_blocks": (xla_blocks, qkv_t + [keep]),
        "flash": (flash, qkv_h + [keep]),
        "layer": (layer, [
            {k: (v.shape, v.dtype) for k, v in get_params(attn).items()},
            ((1, s, attn.hidden), BF), ((1, BT), jnp.int32), pool,
            ((PAGES, PAGE, dI), BF)]),
    }
    for tiles in SWEEP if s == 2048 and L <= 4096 else ():
        out["flash_q%d_k%d" % tiles] = (
            functools.partial(flash, tiles=tiles), qkv_h + [keep])
    if L > attn.topk:
        out["topk_mask"] = (select, [((nblk, 1, qb, L), jnp.float32)])
    return out


def make(spec, key, s, L):
    """A seeded array of a spec: block tables count pages from 1, a mask is
    the chunk's causal one with every other earlier key dropped, floats
    are N(0, 1) (weights, by their rank, N(0, 0.02))."""
    if isinstance(spec, dict):
        keys = jax.random.split(key, len(spec))
        return {n: make(sd, k, s, L) for (n, sd), k in zip(spec.items(), keys)}
    shape, dtype = spec
    if dtype == jnp.int32:
        return jnp.arange(1, 1 + np.prod(shape), dtype=jnp.int32).reshape(shape)
    if dtype == jnp.int8:
        q, k = (L - s + np.arange(s))[:, None], np.arange(L)[None]
        return jnp.asarray(((k == q) | ((k < q) & (k % 2 == 0)))[None],
                           jnp.int8)
    x = jax.random.normal(key, shape, jnp.float32)
    if len(shape) == 1:
        return jnp.ones(shape, dtype)                   # a norm's weight
    return (x * (0.02 if len(shape) == 2 else 1.0)).astype(dtype)


def time_call(fn, args, calls, carried):
    """Mean seconds of a dispatch; outputs 1.. go back in as the donated
    arguments `carried` (the layer's pools, written where they lie)."""
    def once():
        out = fn(*args)
        for i, a in zip(carried, out[1:] if carried else ()):
            args[i] = a
        return out
    jax.block_until_ready(once())
    t0 = time.perf_counter()
    for _ in range(calls):
        out = once()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls


def attention_shares(row, attn, keep, tiles):
    """Dense matmul FLOPs over the row's time as a share of the MXU's
    peak and, for the kernel at `tiles`, the share of key tiles it
    visits."""
    (_, s, L), H = keep.shape, attn.heads
    flops = 2 * H * s * L * (attn.d_nope + attn.d_rope + attn.d_v)
    row["mxu_share"] = flops / (row["ms"] / 1e3) / MXU_FLOPS_PER_S
    if tiles is not None:
        trips = mp.key_tile_trips(keep, *tiles)
        row["tiles_visited"] = float(
            trips.sum() / (trips.size * (L // tiles[1])))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--shapes", default=",".join(f"{s}:{L}" for s, L in SHAPES))
    ap.add_argument("--pieces", default="")
    a = ap.parse_args()
    if a.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])
        place.accelerator_available = lambda: True
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: a time from another backend is no "
                 "measurement (use --describe to compile only)")
    with LazyGuard(), param_dtype("bfloat16"):
        attn = Dots3LatentAttention(Dots3NoteConfig(), FULL)
    dk, dv = attn.d_nope + attn.d_rope, attn.d_v
    # the layer's chunk step on the XLA blocks: what the chip would run if
    # the shapes did not tile
    xla_route = mock.patch.object(mp, "mla_prefill_requirements",
                                  lambda *a: "the bench's XLA route")
    rows_out = []

    for s, L in (tuple(map(int, x.split(":"))) for x in a.shapes.split(",")):
        for name, (fn, specs) in pieces(attn, s, L).items():
            if a.pieces and name not in a.pieces.split(","):
                continue
            routes = [("layer_xla", xla_route),
                      ("layer_flash", contextlib.nullcontext())] \
                if name == "layer" else [(name, contextlib.nullcontext())]
            carried = (3, 4) if name == "layer" else ()
            for label, route in routes:
                row = dict(s=s, L=L, piece=label)
                # a function of its own a route: one trace each
                jitted = jax.jit(lambda *x, fn=fn: fn(*x),
                                 donate_argnums=carried)
                with route:
                    if a.describe:
                        structs = jax.tree_util.tree_map(
                            lambda sd: jax.ShapeDtypeStruct(
                                sd[0], sd[1], sharding=chip), specs,
                            is_leaf=lambda x: isinstance(x, tuple))
                        text = jitted.lower(*structs).compile().as_text()
                        row["kernels"] = text.count("tpu_custom_call")
                    else:
                        keys = jax.random.split(
                            jax.random.PRNGKey(a.seed), len(specs))
                        args = [make(sd, k, s, L)
                                for sd, k in zip(specs, keys)]
                        try:
                            row["ms"] = time_call(jitted, args, a.calls,
                                                  carried) * 1e3
                            if name == "xla_blocks":
                                attention_shares(row, attn, args[3], None)
                            elif name.startswith("flash"):
                                attention_shares(
                                    row, attn, args[3],
                                    getattr(fn, "keywords", {}).get("tiles")
                                    or mp.mla_prefill_tiles(
                                        s, L, dk, dv, BF)[:2])
                        except Exception as e:  # noqa: BLE001 — a sweep's
                            # variant the compiler refuses is a row too
                            row["error"] = str(e)[:300]
                        del args
                rows_out.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mla_prefill_bench.json", "w") as fh:
        json.dump(rows_out, fh, indent=1)


if __name__ == "__main__":
    main()
