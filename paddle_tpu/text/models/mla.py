"""Latent attention (MLA): the block the latent decoders share.

What the block computes, with ``u`` the RMS-normed layer input, ``t`` a
query and ``s`` a key position (DeepSeek-V2's equations):
``c_q = RMSNorm(u W_qa)``, ``q = c_q W_qb`` -> H x (nope + rope);
``[c_kv ; k_r] = u W_kva``, ``c_kv = RMSNorm(c_kv)``, ``k_rope =
RoPE(k_r)`` shared by all heads; ``[k_nope ; v] = c_kv W_kvb``; scores
``(q_nope.k_nope + RoPE(q_rope).k_rope) * scale`` over the keys ``s <= t``;
the heads' outputs side by side through ``W_o``.

What differs between models is an argument of the block, never a
switch read by name:

* ``rope`` — the position encoding of the rope dims, a function
  ``(x, pos, n=None)`` that rotates the first ``n`` (default all) entries
  of the last axis: `rope` below at a ``theta`` (rotate-half pairs), or
  at YaRN's frequencies in the published interleaved pairing (`yarn`);
* ``scale`` — the softmax scale (``1 / sqrt(nope + rope)``, or YaRN's);
* ``q_rescale`` / ``kv_rescale`` — factors on ``c_q`` / ``c_kv`` after
  their norms (LongCat-Flash's lora rescale), or None;
* ``gate`` — a head-wise output gate ``sigmoid(u W_g)``, one scalar a
  head, on each head's output before ``W_o``;
* ``window`` — keys ``0 <= t - s < window`` only;
* ``indexer`` — DeepSeek-V3.2's learned sparse selection:
  ``qI = c_q W_Iq`` -> J x dI, ``kI = LayerNorm(u W_Ik)``, the rope on the
  first rope dims of both, ``w = u W_Iw / sqrt(J dI)``, ``I[t,s] = sum_j
  w[t,j] relu(qI[t,j].kI[s])``; the ``topk`` positions ``s <= t`` of
  largest ``I[t,s]`` are attended.

Serving (docs/SERVING.md "Model polymorphism"): a token's cache row is
one vector and no heads, ``[c_kv ; k_rope]`` padded to whole 128-lane
tiles, plus the indexer's key (``dI``) in a pool of its own where there
is an indexer. A chunk of more than one token (prefill) gathers the rows
it may attend and EXPANDS them to per-head keys and values, in query
blocks (with a window a block scores only the band of rows its window
can keep; without one, on the chip, the blocks yield their masks and ONE
call of ``kernels.mla_prefill.mla_flash_prefill`` attends them all with
the scores kept in VMEM); a one-token step (decode) ABSORBS ``W_kvb``
into the query and the output and runs
``kernels.paged_attention.paged_mla_decode`` on the latent rows
themselves: under the indexer's mask, over the window's pages, or over
every row of the context where there is neither.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ... import monitor
from ...core import place
from ...core.dispatch import unwrap, wrap
from ...framework.param_attr import ParamAttr
from ...kernels import mla_prefill
from ...kernels import paged_attention as paged
from ...nn.initializer import Normal
from ...nn.layer.common import Linear
from ...nn.layer.layers import Layer
from ...nn.layer.norm import LayerNorm
from .llama import LlamaRMSNorm

LANES = 128


class Indexer(NamedTuple):
    """A sparse indexer's sizes: `heads` x `dim`, `topk` keys kept."""
    heads: int
    dim: int
    topk: int


def init_linear(n_in, n_out, std):
    return Linear(n_in, n_out, bias_attr=False,
                  weight_attr=ParamAttr(initializer=Normal(0.0, std)))


def rope(x, pos, theta=None, n=None, inv_freq=None, interleaved=False):
    """RoPE on the first `n` (default all) entries of the last axis. x
    [b, s, ..., d], pos [b, s]; computed in float32. The frequencies are
    ``theta ** (-2i / d)``, or `inv_freq` [d / 2] where given. Pairs are
    (i, i + d/2) (rotate-half), or with `interleaved` the published
    DeepSeek pairs (2i, 2i + 1), rotated as its code does: the even
    entries moved to the first half and the odd ones to the second, then
    rotate-half (so the result is in that half-split order)."""
    d = x.shape[-1] if n is None else n
    inv = jnp.float32(theta) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d) if inv_freq is None \
        else jnp.asarray(inv_freq, jnp.float32)
    ang = pos.astype(jnp.float32)[..., None] * inv          # [b, s, d/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x[..., :d].astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), x[..., d:]], -1)


def yarn(rope_scaling: dict, dim: int, base: float):
    """YaRN (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``) for `dim`
    rope dims at `base`: (inv_freq [dim / 2] float32, the factor on the
    softmax scale). The frequencies interpolate between ``freq / factor``
    and ``freq`` along a ramp over dims [low, high], the ``floor`` /
    ``ceil`` of ``dim ln(L / (beta 2 pi)) / (2 ln base)`` for ``beta_fast``
    and ``beta_slow``, L the original context. cos and sin are multiplied
    by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, which
    this returns no room for: it must be 1 (DeepSeek-V2's 0.707 / 0.707).
    The softmax scale is multiplied by ``mscale(factor, mscale_all_dim)``
    squared."""
    factor = float(rope_scaling["factor"])
    orig = float(rope_scaling["original_max_position_embeddings"])
    m = float(rope_scaling.get("mscale", 1.0))
    m_all = float(rope_scaling.get("mscale_all_dim", 0.0))

    def mscale(scale):          # YaRN's attention factor at scale
        return 1.0 if factor <= 1 else 0.1 * scale * math.log(factor) + 1.0

    if mscale(m) != mscale(m_all):
        raise ValueError(
            f"rope_scaling mscale {m} != mscale_all_dim {m_all}: a scale on "
            f"cos and sin is not implemented")

    def corr(beta):
        return dim * math.log(orig / (beta * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(float(rope_scaling["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rope_scaling["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp               # 1: the dim keeps its own frequency
    freq = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inv = freq / factor * (1.0 - keep) + freq * keep
    return inv.astype(np.float32), (mscale(m_all) if m_all else 1.0) ** 2


def yarn_rope(rope_scaling: dict, dim: int, base: float):
    """(the block's `rope` in DeepSeek-V2's interleaved pairing at YaRN's
    frequencies, the factor on the softmax scale). Each application
    bumps the trace-time counter `kernels.rope.yarn`."""
    inv, factor = yarn(rope_scaling, dim, base)
    inv = tuple(float(v) for v in inv)

    def fn(x, pos, n=None):
        monitor.counter("kernels.rope.yarn").increase()
        return rope(x, pos, n=n, inv_freq=inv, interleaved=True)
    return fn, factor


def block_steps(step, n):
    """step, 2 * step, ... below n, then n (static page counts)."""
    return list(range(step, n, step)) + [n]


def topk_mask(scores, k):
    """[n, L] bool: the k largest entries of each row of `scores` (all
    of them where L <= k), ties to the lower index: `lax.top_k`'s set,
    found without a sort. The k-th largest value is located by a binary
    search over the float32 bit pattern (32 compare-and-count passes),
    which on the TPU costs a fraction of sorting 5,120 keys a row."""
    n, L = scores.shape
    if L <= k:
        return jnp.ones((n, L), bool)
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.uint32)
    # order-preserving map of float32 onto uint32
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def step(i, lo):
        cand = lo | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        enough = jnp.sum(keys >= cand[:, None], axis=1) >= k
        return jnp.where(enough, cand, lo)

    kth = jax.lax.fori_loop(0, 32, step, jnp.zeros((n,), jnp.uint32))
    above = keys > kth[:, None]
    tied = keys == kth[:, None]
    need = k - jnp.sum(above, axis=1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, axis=1) <= need))


class LatentAttention(Layer):
    """One latent-attention block (see the module docstring for the
    equations, the arguments and the cache row). Linear weights are
    N(0, `std`); `eps` is the RMSNorms'. `q_block` queries make a block
    of prefill scores; a prefill chunk on a layer with no window reads
    its cache in whole `key_block`s."""

    def __init__(self, hidden, heads, q_rank, kv_rank, d_nope, d_rope, d_v,
                 *, rope, scale, eps, std, q_rescale=None, kv_rescale=None,
                 gate=False, window=None,
                 indexer: Optional[Indexer] = None, q_block=256,
                 key_block=1024):
        super().__init__()
        self.hidden, self.heads = hidden, heads
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.d_nope, self.d_rope, self.d_v = d_nope, d_rope, d_v
        self.rope = rope
        self.scale = float(scale)
        self.q_rescale, self.kv_rescale = q_rescale, kv_rescale
        self.window = None if window is None else int(window)
        self.indexer = indexer
        self.topk = None if indexer is None else int(indexer.topk)
        self.q_block = int(q_block)
        self.key_block = int(key_block)
        # cache row: [c_kv ; k_rope ; zeros] up to whole lane tiles, so
        # that the value part c_kv is a lane-aligned prefix of the row
        self.row = -(-(kv_rank + d_rope) // LANES) * LANES
        H = heads
        _linear = functools.partial(init_linear, std=std)
        self.q_a_proj = _linear(hidden, q_rank)
        self.q_a_layernorm = LlamaRMSNorm(q_rank, eps)
        self.q_b_proj = _linear(q_rank, H * (d_nope + d_rope))
        self.kv_a_proj_with_mqa = _linear(hidden, kv_rank + d_rope)
        self.kv_a_layernorm = LlamaRMSNorm(kv_rank, eps)
        self.kv_b_proj = _linear(kv_rank, H * (d_nope + d_v))
        self.gate_proj = _linear(hidden, H) if gate else None
        self.o_proj = _linear(H * d_v, hidden)
        if indexer is not None:
            self.idx_heads, self.idx_dim = indexer.heads, indexer.dim
            self.idx_q_proj = _linear(q_rank, self.idx_heads * self.idx_dim)
            self.idx_k_proj = _linear(hidden, self.idx_dim)
            self.idx_k_norm = LayerNorm(self.idx_dim, epsilon=1e-6)
            self.idx_w_proj = _linear(hidden, self.idx_heads)

    # -- what a layer keeps of a token --------------------------------------

    def cache_rows(self):
        """Widths of this layer's pools: one vector a token each."""
        return (self.row,) if self.indexer is None \
            else (self.row, self.idx_dim)

    def _project(self, u, pos):
        """The per-token quantities. u [b, s, hidden] Tensor, pos [b, s]
        array. Returns arrays: q_nope [b,s,H,dn], q_rope (roped)
        [b,s,H,dr], row [b,s,row] (normed, rescaled latent ; roped key ;
        zeros), gate [b,s,H] | None, and (qI [b,s,J,dI], wI [b,s,J],
        kI [b,s,dI]) where there is an indexer, else None."""
        b, s = u.shape[0], u.shape[1]
        H = self.heads
        c_q = self.q_a_layernorm(self.q_a_proj(u))
        if self.q_rescale is not None:
            c_q = c_q * self.q_rescale
        q = unwrap(self.q_b_proj(c_q)).reshape(
            b, s, H, self.d_nope + self.d_rope)
        q_nope = q[..., :self.d_nope]
        q_rope = self.rope(q[..., self.d_nope:], pos)
        kv = unwrap(self.kv_a_proj_with_mqa(u))
        c_kv = self.kv_a_layernorm(wrap(kv[:, :, :self.kv_rank]))
        if self.kv_rescale is not None:
            c_kv = c_kv * self.kv_rescale
        c_kv = unwrap(c_kv)
        k_rope = self.rope(kv[:, :, self.kv_rank:], pos)
        pad = self.row - self.kv_rank - self.d_rope
        row = jnp.concatenate(
            [c_kv, k_rope.astype(c_kv.dtype),
             jnp.zeros((b, s, pad), c_kv.dtype)], -1)
        gate = None if self.gate_proj is None else jax.nn.sigmoid(
            unwrap(self.gate_proj(u)).astype(jnp.float32))
        idx = None
        if self.indexer is not None:
            J, dI = self.idx_heads, self.idx_dim
            qI = self.rope(unwrap(self.idx_q_proj(c_q)).reshape(b, s, J, dI),
                           pos, n=self.d_rope)
            kI = self.rope(unwrap(self.idx_k_norm(self.idx_k_proj(u))),
                           pos, n=self.d_rope)
            wI = unwrap(self.idx_w_proj(u)).astype(jnp.float32) \
                * jnp.float32(1.0 / math.sqrt(J * dI))
            idx = (qI, wI, kI)
        return q_nope, q_rope, row, gate, idx

    def _w_kvb(self):
        w = unwrap(self.kv_b_proj.weight).reshape(
            self.kv_rank, self.heads, self.d_nope + self.d_v)
        return w[..., :self.d_nope], w[..., self.d_nope:]

    @staticmethod
    def _gated(out, gate):
        """out [..., H, d] times the head-wise gate [..., H], if any."""
        return out if gate is None else out * gate[..., None]

    # -- attention over gathered rows (prefill, and the cache-less pass) ----

    def _attend_expanded(self, q_nope, q_rope, gate, idx, rows, kI_rows,
                         q_pos, k_pos, align):
        """Expanded attention of s queries over L gathered latent rows:
        K and V are computed from the rows for every head, the mask of
        `q_block` queries at a time. rows [b, L, row], kI_rows
        [b, L, dI] | None, q_pos [b, s], k_pos [b, L] (absolute and
        consecutive along L). Returns [b, s, H * d_v] gated.

        With no window, on the chip and where the shapes tile
        (`mla_prefill_requirements`), the blocks yield only their MASK
        (causal compare, indexer, `topk_mask`) and one call of
        `kernels.mla_prefill.mla_flash_prefill` attends all of them, the
        float32 scores never leaving VMEM; elsewhere each block scores
        its keys in XLA (`mla_block_xla`).

        With a window a block of queries scores only the `band` rows its
        window can keep: a slice of static length that starts at a
        multiple of `align` (the page size, where there are pages) at or
        below the first key its first query keeps."""
        b, s, H = q_nope.shape[:3]
        L = rows.shape[1]
        cdt = rows.dtype
        qb = self.q_block if s % self.q_block == 0 else s
        nblk = s // qb
        band = L
        flash = False
        if self.window is not None:
            # qb + window - 1 rows from the first kept key, up to
            # align - 1 rows between it and the slice's start
            band = min(L, -(-(qb + self.window - 1) // align) * align + align)
            monitor.counter("kernels.prefill.swa_band" if band < L else
                            "kernels.prefill.swa_whole").increase()
        else:
            flash = place.accelerator_available() \
                and mla_prefill.mla_prefill_requirements(
                    s, L, self.d_nope + self.d_rope, self.d_v, cdt) is None
            monitor.counter("kernels.prefill.mla_flash" if flash else
                            "kernels.prefill.mla_xla").increase()
        w_k, w_v = self._w_kvb()
        c_kv = rows[..., :self.kv_rank]
        k_rope = rows[..., self.kv_rank:self.kv_rank + self.d_rope]
        # one key a head: [k_nope ; k_rope], so that a block's scores are
        # ONE matmul; heads-major for the kernel, whose tile is [rows, d]
        to = "bhLd" if flash else "bLhd"
        k_nope = jnp.einsum(f"bLc,chd->{to}", c_kv, w_k.astype(cdt))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(
                jnp.expand_dims(k_rope, 1 if flash else 2),
                k_nope.shape[:3] + (self.d_rope,))], -1)
        v = jnp.einsum(f"bLc,chd->{to}", c_kv, w_v.astype(cdt))
        q = jnp.concatenate([q_nope.astype(cdt), q_rope.astype(cdt)], -1)

        def split(a):                 # [b, s, ...] -> [nblk, b, qb, ...]
            return jnp.moveaxis(
                a.reshape((b, nblk, qb) + a.shape[2:]), 1, 0)

        def block(args):
            q_blk, qp, sel = args
            k_blk, v_blk, k_pos_blk = k, v, k_pos
            if band < L:
                first = qp[:, 0] - (self.window - 1) - k_pos[:, 0]
                start = jnp.clip(first // align * align, 0, L - band)
                k_blk, v_blk, k_pos_blk = (
                    jax.vmap(lambda a, i: jax.lax.dynamic_slice_in_dim(
                        a, i, band))(a, start) for a in (k, v, k_pos))
            keep = k_pos_blk[:, None, :] <= qp[:, :, None]    # [b, qb, band]
            if self.window is not None:
                keep &= qp[:, :, None] - k_pos_blk[:, None, :] < self.window
            if sel is not None:
                qI, wI = sel
                I = jnp.einsum(
                    "bqj,bqjL->bqL", wI, jax.nn.relu(jnp.einsum(
                        "bqjd,bLd->bqjL", qI, kI_rows,
                        preferred_element_type=jnp.float32)))
                I = jnp.where(keep, I, -jnp.inf)
                keep &= topk_mask(I.reshape(b * qb, L),
                                  self.topk).reshape(b, qb, L)
            if flash:
                return keep.astype(jnp.int8)
            return mla_prefill.mla_block_xla(q_blk, k_blk, v_blk, keep,
                                             self.scale)

        sel = None if idx is None else (split(idx[0]), split(idx[1]))
        xs = (None if flash else split(q), split(q_pos), sel)
        out = jax.lax.map(block, xs) if nblk > 1 else \
            block(jax.tree_util.tree_map(lambda a: a[0], xs))[None]
        out = jnp.moveaxis(out, 0, 1)
        if flash:
            out = mla_prefill.mla_flash_prefill(
                jnp.moveaxis(q, 2, 1), k, v, out.reshape(b, s, L),
                self.scale)
        out = out.reshape(b, s, H, self.d_v)
        return self._gated(out, gate).astype(cdt).reshape(
            b, s, H * self.d_v)

    # -- one-token step on the latent rows (decode) -------------------------

    def _attend_absorbed(self, q_nope, q_rope, gate, pool, bt, ctx, mask,
                         window, pages_per_chunk=None):
        """q_nope/q_rope [b, H, .]; the latent pool; bt/ctx the (maybe
        windowed) block table and context lengths. Returns
        [b, H * d_v] gated."""
        w_k, w_v = self._w_kvb()
        cdt = pool.dtype
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope, w_k.astype(q_nope.dtype),
                           preferred_element_type=jnp.float32)
        b, H = q_lat.shape[:2]
        pad = self.row - self.kv_rank - self.d_rope
        q = jnp.concatenate(
            [q_lat.astype(cdt), q_rope.astype(cdt),
             jnp.zeros((b, H, pad), cdt)], -1)
        on_chip = place.accelerator_available()
        why = paged.paged_mla_requirements(self.row, self.kv_rank,
                                           pool.shape[1], cdt)
        if on_chip and why:
            raise ValueError(
                f"the latent pool of a {self.row}-wide row cannot take "
                f"paged_mla_decode: {why}")
        if on_chip:
            monitor.counter("kernels.decode.paged_mla_pallas").increase()
            o_lat = paged.paged_mla_decode(
                q, pool, bt, ctx, self.kv_rank, self.scale, window=window,
                mask=mask, pages_per_chunk=pages_per_chunk)
        else:
            monitor.counter("kernels.decode.paged_mla_fallback").increase()
            o_lat = paged.paged_mla_arrays(
                q, pool, bt, ctx, self.kv_rank, self.scale, window=window,
                mask=mask)
        o = jnp.einsum("bhc,chd->bhd", o_lat, w_v.astype(o_lat.dtype),
                       preferred_element_type=jnp.float32)
        return self._gated(o, gate).astype(cdt).reshape(b, H * self.d_v)

    def forward(self, u, kv_cache=None, cache_index=None):
        b, s = u.shape[0], u.shape[1]
        if kv_cache is None:
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                   (b, s))
            q_nope, q_rope, row, gate, idx = self._project(u, pos)
            out = self._attend_expanded(
                q_nope, q_rope, gate, idx, row,
                None if idx is None else idx[2], pos, pos, self.q_block)
            return self.o_proj(wrap(out))
        pools, bt = kv_cache[:-1], kv_cache[-1]
        pool = pools[0]
        bs_ = pool.shape[1]
        pos0 = jnp.broadcast_to(jnp.atleast_1d(
            jnp.asarray(unwrap(cache_index), jnp.int32)), (b,))
        pos = pos0[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        q_nope, q_rope, row, gate, idx = self._project(u, pos)
        chunks = [row] if idx is None else [row, idx[2]]
        # the in-place paged write of docs/DECODE.md, on one-vector rows
        pools = paged.paged_write_rows(chunks, pools, bt, pos0)
        pool = pools[0]
        nblocks = bt.shape[1]
        if self.window is not None:
            # read only the pages that hold positions a query of this
            # call may attend: [first query - (window - 1), last query]
            first = jnp.maximum(pos0 - (self.window - 1), 0) // bs_
            n_win = min(nblocks, (self.window + s - 3) // bs_ + 2)
            bt_r = paged.window_pages(bt, first, n_win)
            base = first * bs_
        else:
            bt_r, n_win, base = bt, nblocks, jnp.zeros((b,), jnp.int32)
        if s == 1:
            mask = None
            if idx is not None:
                qI, wI, _ = idx
                kI_rows = paged.gather_rows(pools[1], bt)
                I = jnp.einsum(
                    "bj,bjL->bL", wI[:, 0], jax.nn.relu(jnp.einsum(
                        "bjd,bLd->bjL", qI[:, 0].astype(kI_rows.dtype),
                        kI_rows, preferred_element_type=jnp.float32)))
                L = I.shape[-1]
                causal = jnp.arange(L, dtype=jnp.int32)[None] <= pos0[:, None]
                mask = topk_mask(jnp.where(causal, I, -jnp.inf), self.topk)
            # a windowed layer's few window pages are one chunk of the walk
            out = self._attend_absorbed(
                q_nope[:, 0], q_rope[:, 0],
                None if gate is None else gate[:, 0], pool, bt_r,
                pos0 + 1 - base, mask, self.window,
                None if self.window is None else n_win)[:, None]
        else:
            monitor.counter("kernels.decode.paged_mla_gather").increase()

            def attend(n_pages, q_nope, q_rope, gate, qI, wI, pool, ki_pool):
                """The chunk against the first n_pages columns of its
                (maybe windowed) block table."""
                cols = bt_r[:, :n_pages]
                k_pos = base[:, None] + jnp.arange(
                    n_pages * bs_, dtype=jnp.int32)[None]
                sel, kI_rows = None, None
                if qI is not None:
                    kI_rows = paged.gather_rows(ki_pool, cols)
                    sel = (qI.astype(kI_rows.dtype), wI, None)
                return self._attend_expanded(
                    q_nope, q_rope, gate, sel,
                    paged.gather_rows(pool, cols), kI_rows, pos, k_pos,
                    bs_)

            qI, wI = (None, None) if idx is None else idx[:2]
            ops = (q_nope, q_rope, gate, qI, wI, pool,
                   pools[1] if idx is not None else None)
            # a layer with no window attends as many key blocks as its
            # chunk's last query needs, not the block table's whole width:
            # one branch a static length, chosen by the traced positions
            step = max(1, self.key_block // bs_)
            sizes = block_steps(step, n_win)
            if self.window is not None or len(sizes) == 1:
                out = attend(n_win, *ops)
            else:
                need = (jnp.max(pos0) + s + bs_ - 1) // bs_
                which = sum((need > n).astype(jnp.int32)
                            for n in sizes[:-1])
                out = jax.lax.switch(
                    which, [functools.partial(attend, n) for n in sizes],
                    *ops)
        return self.o_proj(wrap(out)), tuple(pools) + (bt,)
