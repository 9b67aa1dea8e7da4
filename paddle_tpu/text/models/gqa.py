"""Softmax grouped-query attention as the decoders that keep paged keys
and values with heads share it: the causal softmax of a chunk in blocks
of queries (`gqa_attend`, with an optional window's band), one token a
sequence on the paged pools (`paged_decode_or_gather`: the Pallas
kernel on a TPU, the XLA gather elsewhere), and write-then-attend on
the pools for a step or a chunk (`paged_gqa`). No layer, no parameter:
`solar_open2.py`, `k_exaone.py` and `falcon_h1.py` project, rotate and
gate around these themselves.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ... import monitor
from ...core import place
from ...core.dispatch import unwrap
from ...kernels import paged_attention as paged

NEG_INF = -1e30


def gqa_attend(q, kk, vv, q_pos, k_pos, q_block, window=None):
    """Causal softmax of s queries over L keys, `q_block` queries at a
    time. q [b, s, H, d]; kk, vv [b, L, G, d]; q_pos [b, s]; k_pos
    [b, L]. Returns [b, s, H, d] float32.

    With `window`, a query keeps the keys at 0 <= q_pos - k_pos <
    window that exist (k_pos >= 0), and kk, vv hold `window` rows in
    front of the queries' own: L = window + s, row window + i the key of
    query i, positions consecutive. A block of queries then scores only
    the band of q_block + window rows its window can reach, where that
    is fewer than all of them."""
    b, s, H, d = q.shape
    G, L = kk.shape[2], kk.shape[1]
    cdt = kk.dtype
    scale = jnp.float32(1.0 / math.sqrt(d))
    qb = q_block if s % q_block == 0 else s
    nblk = s // qb
    band = L if window is None else qb + window
    if window is not None:
        monitor.counter("kernels.prefill.gqa_band" if band < L else
                        "kernels.prefill.gqa_whole").increase()

    def block(args):
        qq, qp, i = args
        k_blk, v_blk, kp = kk, vv, k_pos
        if band < L:
            k_blk, v_blk, kp = (jax.lax.dynamic_slice_in_dim(
                a, i * qb, band, 1) for a in (kk, vv, k_pos))
        sc = jnp.einsum("bqgrd,bLgd->bgrqL",
                        qq.reshape(b, qb, G, H // G, d), k_blk,
                        preferred_element_type=jnp.float32) * scale
        keep = kp[:, None, :] <= qp[:, :, None]              # [b, qb, L]
        if window is not None:
            keep &= (qp[:, :, None] - kp[:, None, :] < window) \
                & (kp[:, None, :] >= 0)
        sc = jnp.where(keep[:, None, None], sc, NEG_INF)
        p = jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True))
        out = jnp.einsum("bgrqL,bLgd->bqgrd", p.astype(cdt), v_blk,
                         preferred_element_type=jnp.float32)
        den = jnp.moveaxis(jnp.sum(p, axis=-1), 3, 1)        # [b, qb, G, r]
        return (out / den[..., None]).reshape(b, qb, H, d)

    def split(x):
        return jnp.moveaxis(x.reshape((b, nblk, qb) + x.shape[2:]), 1, 0)

    xs = (split(q.astype(cdt)), split(q_pos), jnp.arange(nblk))
    out = jax.lax.map(block, xs) if nblk > 1 else \
        block(jax.tree_util.tree_map(lambda x: x[0], xs))[None]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, H, d)


def paged_decode_or_gather(q, kc, vc, bt, ctx):
    """One token a sequence against paged (k, v) pools: the Pallas
    kernel on a TPU (raising for a geometry it cannot take), the XLA
    gather elsewhere; counted. q [b, H, d] -> [b, H, d]."""
    on_chip = place.accelerator_available()
    why = paged.paged_pallas_requirements(q.shape[-1], kc.shape[2], kc.dtype)
    if on_chip and why:
        raise ValueError(f"the GQA layer's pools cannot take paged_decode: "
                         f"{why}")
    if on_chip:
        monitor.counter("kernels.decode.paged_pallas").increase()
        return paged.paged_decode_pallas(q, kc, vc, bt, ctx)
    monitor.counter("kernels.decode.paged_xla_gather_step").increase()
    return paged.paged_attention_arrays(q, kc, vc, bt, ctx)


def paged_gqa(q, k, v, kv_cache, cache_index, q_block):
    """Causal GQA of a step or a chunk on the paged (k, v) pools with
    heads: write, then attend. q [b, s, H, d]; k, v [b, s, G, d];
    kv_cache (k pool, v pool, block table). Returns ([b, s, H, d]
    float32, the new cache)."""
    b, s = q.shape[:2]
    kc, vc, bt = kv_cache
    pos0 = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(
        unwrap(cache_index), jnp.int32)), (b,))
    # the in-place paged write of docs/DECODE.md
    kc, vc = paged.paged_write_arrays(k, v, kc, vc, bt, pos0)
    if s == 1:
        out = paged_decode_or_gather(q[:, 0], kc, vc, bt, pos0 + 1)
        return out[:, None].astype(jnp.float32), (kc, vc, bt)
    monitor.counter("kernels.decode.paged_xla_gather").increase()
    q_pos = pos0[:, None] + jnp.arange(s, dtype=jnp.int32)[None]

    def carried(kc, vc):
        """Keys and values gathered from the block table's pages: what
        came before the chunk, and the chunk."""
        n = bt.shape[1] * kc.shape[2]
        k_pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None],
                                 (b, n))
        return gqa_attend(q, paged.gather_pages(kc, bt),
                          paged.gather_pages(vc, bt), q_pos, k_pos, q_block)

    def first(kc, vc):
        """A chunk at position 0 attends itself alone: the rows it has
        just written, without the gather and the keys past its own
        length."""
        return gqa_attend(q, k.astype(kc.dtype), v.astype(vc.dtype), q_pos,
                          q_pos, q_block)

    out = jax.lax.cond(jnp.all(pos0 == 0), first, carried, kc, vc)
    return out, (kc, vc, bt)
