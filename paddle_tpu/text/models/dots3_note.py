"""dots3-note: a latent-attention (MLA) decoder with two kinds of layer.

Published configuration: dots-studio/dots3-note-prev ``config.json``
(model_type ``dots3_note``). The field names of ``Dots3NoteConfig`` are
its keys. What the block computes, with ``u`` the RMS-normed layer
input, ``t`` a query and ``s`` a key position:

* full-attention layer (``layer_types[i] == "full_attention"``):
  DeepSeek-V3's MLA. ``c_q = RMSNorm(u W_qa)``, ``q = c_q W_qb`` ->
  H x (nope + rope); ``[c_kv ; k_r] = u W_kva``, ``c_kv = RMSNorm(c_kv)``,
  ``k_rope = RoPE(k_r)`` shared by all heads; ``[k_nope ; v] = c_kv W_kvb``;
  scores ``(q_nope.k_nope + RoPE(q_rope).k_rope) / sqrt(nope + rope)``.
  Keys are restricted to the learned sparse indexer's selection
  (DeepSeek-V3.2): ``qI = c_q W_Iq`` -> J x dI, ``kI = LayerNorm(u W_Ik)``,
  RoPE on the first rope dims of both, ``w = u W_Iw / sqrt(J dI)``,
  ``I[t,s] = sum_j w[t,j] relu(qI[t,j].kI[s])``; the ``index_topk``
  positions ``s <= t`` of largest ``I[t,s]`` are attended.
* sliding layer: the same MLA with the ``swa_*`` sizes and its own
  ``swa_rope_theta``, keys ``0 <= t - s < sliding_window_size``, no
  indexer.
* both: ``apply_mla_qkv_lora_rescale`` multiplies ``c_q`` by
  ``sqrt(hidden / q_lora_rank)`` and ``c_kv`` by
  ``sqrt(hidden / kv_lora_rank)`` after their norms; a head-wise gate
  ``sigmoid(u W_g)`` (one scalar a head) scales each head's output
  before ``W_o``.
* FFN: layer ``i < first_k_dense_replace`` SwiGLU of
  ``intermediate_size``; the others ``MoELayer`` with a sigmoid top-k
  gate over ``n_routed_experts`` gated experts of
  ``moe_intermediate_size`` plus ``n_shared_experts`` shared ones.
  ``expert_share=(index, of)`` holds one chip's share of the experts.

Serving (docs/SERVING.md "Model polymorphism"): ``serving_spec()`` gives
the paged cache PER LAYER. A token's cache row is one vector and no
heads: ``[c_kv ; k_rope]`` padded to whole 128-lane tiles (576 -> 640 on
a full layer, 1088 -> 1152 on a sliding one), plus the indexer's key
(128) in a pool of its own on full layers. A chunk of more than one
token (prefill) gathers the rows it may attend and EXPANDS them to
per-head keys and values, in query blocks (on a sliding layer a block
scores only the band of rows its window can keep; on a full layer, on
the chip, the blocks yield their masks and ONE call of
``kernels.mla_prefill.mla_flash_prefill`` attends them all with the
scores kept in VMEM); a one-token step
(decode) ABSORBS ``W_kvb`` into the query and the output and runs
``kernels.paged_attention.paged_mla_decode`` on the latent rows
themselves: full layers under the indexer's mask, sliding layers over
the pages that hold the last ``sliding_window_size`` positions and no
others.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ... import monitor
from ...core import place
from ...core.dispatch import unwrap, wrap
from ...framework.param_attr import ParamAttr
from ...incubate.distributed.models.moe import MoELayer, SigmoidTopKGate
from ...kernels import mla_prefill
from ...kernels import paged_attention as paged
from ...nn import functional as F
from ...nn.initializer import Normal
from ...nn.layer.common import Embedding, Linear
from ...nn.layer.container import LayerList
from ...nn.layer.layers import Layer, param_dtype
from ...nn.layer.norm import LayerNorm
from .llama import LlamaRMSNorm

FULL, SLIDING = "full_attention", "sliding_attention"
LANES = 128


def _layer_pattern(n):
    """The published pattern: full, then periods of (full, sliding x 3);
    at the published depth, 46 = 1 + 11 * 4 + 1, the last layer is the
    full one that opens a twelfth period."""
    return tuple([FULL] + [FULL if i % 4 == 0 else SLIDING
                           for i in range(n - 1)])


@dataclass
class Dots3NoteConfig:
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 46
    layer_types: Optional[Tuple[str, ...]] = None
    max_position_embeddings: int = 524288
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    attention_bias: bool = False
    apply_mla_qkv_lora_rescale: bool = True
    # full-attention layers
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    rope_scaling: Optional[dict] = None
    attention_gate_type: str = "headwise"
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # sliding layers
    sliding_window_size: int = 513
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    swa_attention_gate_type: str = "headwise"
    # experts
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    # not in the published file
    expert_share: Tuple[int, int] = (0, 1)    # (index, of): experts held
    dtype: str = "float32"                    # honoured at construction
    initializer_range: float = 0.02           # std of every matrix's init
    prefill_query_block: int = 256            # queries a block of scores
    prefill_key_block: int = 1024             # a full layer's prefill reads
    #                                           its cache in whole such blocks

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = _layer_pattern(self.num_hidden_layers)
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types has {len(self.layer_types)} entries for "
                f"{self.num_hidden_layers} layers")
        for name, want in (("scoring_func", "sigmoid"),
                           ("topk_method", "noaux_tc"),
                           ("attention_gate_type", "headwise"),
                           ("swa_attention_gate_type", "headwise"),
                           ("hidden_act", "silu"), ("rope_scaling", None),
                           ("attention_bias", False),
                           ("moe_layer_freq", 1)):
            if getattr(self, name) != want:
                raise ValueError(
                    f"{name}={getattr(self, name)!r}: only {want!r} is "
                    f"implemented")

    @property
    def n_routed_experts_held(self) -> int:
        return self.n_routed_experts // self.expert_share[1]

    @staticmethod
    def tiny(**over):
        """The CPU tests' size: both layer kinds and the leading dense
        layer, selection and window small enough to bite at 24 tokens."""
        kw = dict(
            vocab_size=96, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3,
            layer_types=(FULL, FULL, SLIDING),
            max_position_embeddings=256, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            index_n_heads=2, index_head_dim=16, index_topk=8,
            sliding_window_size=5, swa_num_attention_heads=2,
            swa_num_key_value_heads=2, swa_q_lora_rank=32,
            swa_kv_lora_rank=24, swa_qk_nope_head_dim=24,
            swa_qk_rope_head_dim=8, swa_v_head_dim=16,
            n_routed_experts=8, num_experts_per_tok=2,
            prefill_query_block=8, prefill_key_block=16)
        kw.update(over)
        return Dots3NoteConfig(**kw)


def _init_linear(n_in, n_out, std):
    return Linear(n_in, n_out, bias_attr=False,
                  weight_attr=ParamAttr(initializer=Normal(0.0, std)))


def _rope(x, pos, theta, n=None):
    """Rotate-half RoPE on the first `n` (default all) entries of the
    last axis. x [b, s, ..., d], pos [b, s]; computed in float32."""
    d = x.shape[-1] if n is None else n
    inv = jnp.float32(theta) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv          # [b, s, d/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x[..., :d].astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), x[..., d:]], -1)


def _block_steps(step, n):
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


class Dots3LatentAttention(Layer):
    """One latent-attention block of either kind (see the module
    docstring for the equations and the cache row)."""

    def __init__(self, config: Dots3NoteConfig, kind: str):
        super().__init__()
        c, full = config, kind == FULL
        self.kind = kind
        self.hidden = c.hidden_size
        self.heads = c.num_attention_heads if full \
            else c.swa_num_attention_heads
        self.q_rank = c.q_lora_rank if full else c.swa_q_lora_rank
        self.kv_rank = c.kv_lora_rank if full else c.swa_kv_lora_rank
        self.d_nope = c.qk_nope_head_dim if full else c.swa_qk_nope_head_dim
        self.d_rope = c.qk_rope_head_dim if full else c.swa_qk_rope_head_dim
        self.d_v = c.v_head_dim if full else c.swa_v_head_dim
        self.theta = c.rope_theta if full else c.swa_rope_theta
        self.window = None if full else int(c.sliding_window_size)
        self.topk = int(c.index_topk) if full else None
        self.scale = 1.0 / math.sqrt(self.d_nope + self.d_rope)
        self.q_block = int(c.prefill_query_block)
        self.key_block = int(c.prefill_key_block)
        rescale = c.apply_mla_qkv_lora_rescale
        self.q_rescale = math.sqrt(self.hidden / self.q_rank) \
            if rescale else 1.0
        self.kv_rescale = math.sqrt(self.hidden / self.kv_rank) \
            if rescale else 1.0
        # cache row: [c_kv ; k_rope ; zeros] up to whole lane tiles, so
        # that the value part c_kv is a lane-aligned prefix of the row
        self.row = -(-(self.kv_rank + self.d_rope) // LANES) * LANES
        H = self.heads
        _linear = functools.partial(_init_linear, std=c.initializer_range)
        self.q_a_proj = _linear(self.hidden, self.q_rank)
        self.q_a_layernorm = LlamaRMSNorm(self.q_rank, c.rms_norm_eps)
        self.q_b_proj = _linear(self.q_rank, H * (self.d_nope + self.d_rope))
        self.kv_a_proj_with_mqa = _linear(self.hidden,
                                          self.kv_rank + self.d_rope)
        self.kv_a_layernorm = LlamaRMSNorm(self.kv_rank, c.rms_norm_eps)
        self.kv_b_proj = _linear(self.kv_rank, H * (self.d_nope + self.d_v))
        self.gate_proj = _linear(self.hidden, H)
        self.o_proj = _linear(H * self.d_v, self.hidden)
        if full:
            self.idx_heads, self.idx_dim = c.index_n_heads, c.index_head_dim
            self.idx_q_proj = _linear(self.q_rank,
                                      self.idx_heads * self.idx_dim)
            self.idx_k_proj = _linear(self.hidden, self.idx_dim)
            self.idx_k_norm = LayerNorm(self.idx_dim, epsilon=1e-6)
            self.idx_w_proj = _linear(self.hidden, self.idx_heads)

    # -- what a layer keeps of a token --------------------------------------

    def cache_rows(self):
        """Widths of this layer's pools: one vector a token each."""
        return (self.row, self.idx_dim) if self.kind == FULL \
            else (self.row,)

    def _project(self, u, pos):
        """The per-token quantities. u [b, s, hidden] Tensor, pos [b, s]
        array. Returns arrays: q_nope [b,s,H,dn], q_rope (roped)
        [b,s,H,dr], row [b,s,row] (normed, rescaled latent ; roped key ;
        zeros), gate [b,s,H], and (qI [b,s,J,dI], wI [b,s,J],
        kI [b,s,dI]) on a full layer, else None."""
        b, s = u.shape[0], u.shape[1]
        H = self.heads
        c_q = self.q_a_layernorm(self.q_a_proj(u)) * self.q_rescale
        q = unwrap(self.q_b_proj(c_q)).reshape(
            b, s, H, self.d_nope + self.d_rope)
        q_nope = q[..., :self.d_nope]
        q_rope = _rope(q[..., self.d_nope:], pos, self.theta)
        kv = unwrap(self.kv_a_proj_with_mqa(u))
        c_kv = unwrap(self.kv_a_layernorm(wrap(kv[:, :, :self.kv_rank]))
                      * self.kv_rescale)
        k_rope = _rope(kv[:, :, self.kv_rank:], pos, self.theta)
        pad = self.row - self.kv_rank - self.d_rope
        row = jnp.concatenate(
            [c_kv, k_rope.astype(c_kv.dtype),
             jnp.zeros((b, s, pad), c_kv.dtype)], -1)
        gate = jax.nn.sigmoid(unwrap(self.gate_proj(u)).astype(jnp.float32))
        idx = None
        if self.kind == FULL:
            J, dI = self.idx_heads, self.idx_dim
            qI = _rope(unwrap(self.idx_q_proj(c_q)).reshape(b, s, J, dI),
                       pos, self.theta, n=self.d_rope)
            kI = _rope(unwrap(self.idx_k_norm(self.idx_k_proj(u))),
                       pos, self.theta, n=self.d_rope)
            wI = unwrap(self.idx_w_proj(u)).astype(jnp.float32) \
                * jnp.float32(1.0 / math.sqrt(J * dI))
            idx = (qI, wI, kI)
        return q_nope, q_rope, row, gate, idx

    def _w_kvb(self):
        w = unwrap(self.kv_b_proj.weight).reshape(
            self.kv_rank, self.heads, self.d_nope + self.d_v)
        return w[..., :self.d_nope], w[..., self.d_nope:]

    # -- attention over gathered rows (prefill, and the cache-less pass) ----

    def _attend_expanded(self, q_nope, q_rope, gate, idx, rows, kI_rows,
                         q_pos, k_pos, align):
        """Expanded attention of s queries over L gathered latent rows:
        K and V are computed from the rows for every head, the mask of
        `q_block` queries at a time. rows [b, L, row], kI_rows
        [b, L, dI] | None, q_pos [b, s], k_pos [b, L] (absolute and
        consecutive along L). Returns [b, s, H * d_v] gated.

        On a full layer, on the chip and where the shapes tile
        (`mla_prefill_requirements`), the blocks yield only their MASK
        (causal compare, indexer, `topk_mask`) and one call of
        `kernels.mla_prefill.mla_flash_prefill` attends all of them, the
        float32 scores never leaving VMEM; elsewhere each block scores
        its keys in XLA (`mla_block_xla`).

        On a sliding layer a block of queries scores only the `band`
        rows its window can keep: a slice of static length that starts
        at a multiple of `align` (the page size, where there are pages)
        at or below the first key its first query keeps."""
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
        return (out * gate[..., None]).astype(cdt).reshape(
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
                f"the latent pool of a {self.kind} layer cannot take "
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
        return (o * gate[..., None]).astype(cdt).reshape(b, H * self.d_v)

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
        cdt = pool.dtype
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
            # a sliding layer's few window pages are one chunk of the walk
            out = self._attend_absorbed(
                q_nope[:, 0], q_rope[:, 0], gate[:, 0], pool, bt_r,
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
            # a full layer's chunk attends as many key blocks as its last
            # query needs, not the block table's whole width: one branch
            # a static length, chosen by the traced positions
            step = max(1, self.key_block // bs_)
            sizes = _block_steps(step, n_win)
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


class Dots3MLP(Layer):
    """SwiGLU FFN: the leading dense layers and the shared expert."""

    def __init__(self, hidden, width, std=0.02):
        super().__init__()
        self.gate_proj = _init_linear(hidden, width, std)
        self.up_proj = _init_linear(hidden, width, std)
        self.down_proj = _init_linear(width, hidden, std)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class Dots3DecoderLayer(Layer):
    def __init__(self, config: Dots3NoteConfig, index: int):
        super().__init__()
        c = config
        self.kind = c.layer_types[index]
        self.input_layernorm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = Dots3LatentAttention(c, self.kind)
        self.post_attention_layernorm = LlamaRMSNorm(c.hidden_size,
                                                     c.rms_norm_eps)
        self.is_moe = index >= c.first_k_dense_replace
        if self.is_moe:
            shared = Dots3MLP(
                c.hidden_size, c.moe_intermediate_size * c.n_shared_experts,
                c.initializer_range) if c.n_shared_experts else None
            self.mlp = MoELayer(
                d_model=c.hidden_size, d_hidden=c.moe_intermediate_size,
                num_experts=c.n_routed_experts,
                gate=SigmoidTopKGate(c.n_routed_experts,
                                     c.num_experts_per_tok,
                                     c.norm_topk_prob,
                                     c.routed_scaling_factor),
                activation="swiglu", expert_share=c.expert_share,
                shared_experts=shared)
        else:
            self.mlp = Dots3MLP(c.hidden_size, c.intermediate_size,
                                c.initializer_range)

    def forward(self, x, kv_cache=None, cache_index=None, token_mask=None):
        new_cache = None
        if kv_cache is not None:
            attn, new_cache = self.self_attn(
                self.input_layernorm(x), kv_cache=kv_cache,
                cache_index=cache_index)
        else:
            attn = self.self_attn(self.input_layernorm(x))
        x = x + attn
        h = self.post_attention_layernorm(x)
        if self.is_moe:
            x = x + self.mlp(h, token_mask=token_mask,
                             decode_mode=kv_cache is not None)
        else:
            x = x + self.mlp(h)
        return x if kv_cache is None else (x, new_cache)


class Dots3NoteForCausalLM(Layer):
    """The decoder, with the call signature the serving engine uses for
    LlamaForCausalLM (``kv_caches`` / ``cache_index``)."""

    def __init__(self, config: Dots3NoteConfig):
        super().__init__()
        self.config = config
        c = config
        # every parameter is created in config.dtype: at the published
        # widths the model does not fit the chip in float32 first
        with param_dtype(c.dtype):
            self.embed_tokens = Embedding(
                c.vocab_size, c.hidden_size, weight_attr=ParamAttr(
                    initializer=Normal(0.0, c.initializer_range)))
            self.layers = LayerList([Dots3DecoderLayer(c, i)
                                     for i in range(c.num_hidden_layers)])
            self.norm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
            self.lm_head = _init_linear(c.hidden_size, c.vocab_size,
                                        c.initializer_range)

    def forward(self, input_ids, kv_caches=None, cache_index=None):
        x = self.embed_tokens(input_ids)
        if kv_caches is None:
            for lyr in self.layers:
                x = lyr(x)
            return self.lm_head(self.norm(x))
        b, s = input_ids.shape
        idx = jnp.asarray(unwrap(cache_index), jnp.int32)
        # the engine's idle decode lanes ride at cache_index -1: their
        # token claims no expert (MoELayer token_mask)
        mask = jnp.broadcast_to(
            jnp.reshape(jnp.atleast_1d(idx), (-1, 1)) >= 0, (b, s))
        new_caches = []
        for lyr, cache in zip(self.layers, kv_caches):
            x, nc = lyr(x, kv_cache=cache, cache_index=cache_index,
                        token_mask=mask)
            new_caches.append(nc)
        return self.lm_head(self.norm(x)), new_caches

    def num_params(self):
        return sum(math.prod(p.shape) for _, p in self.named_parameters())

    def serving_spec(self):
        """The engine's probe. ``cache_layers`` gives each layer's pools
        as the widths of its per-token rows (one vector a token, no
        heads); ``tick_stats`` names what ``serving_tick_stats()``
        returns after a forward; ``window`` and ``index_topk`` are what
        the engine's span arguments and the window gauge are computed
        from."""
        c = self.config
        return {
            "kind": "decoder",
            "num_layers": c.num_hidden_layers,
            "max_context": c.max_position_embeddings,
            "vocab_size": c.vocab_size,
            "cache_layers": [
                {"kind": "latent", "rows": lyr.self_attn.cache_rows(),
                 "window": lyr.self_attn.window}
                for lyr in self.layers],
            "index_topk": c.index_topk,
            "window": c.sliding_window_size,
            "tick_stats": ("serving.moe.picks_held",
                           "serving.moe.picks_total",
                           "serving.moe.experts_touched",
                           "serving.moe.layer_ticks",
                           "serving.moe.slabs"),
            "moe": {"num_experts": c.n_routed_experts,
                    "held": c.n_routed_experts_held,
                    "top_k": c.num_experts_per_tok,
                    "d_model": c.hidden_size,
                    "d_hidden": c.moe_intermediate_size,
                    "dispatch_mode": "ragged"},
        }

    def serving_tick_stats(self):
        """[5] int32, in ``tick_stats``' order: the expert layers' picks
        held here, picks made and held experts touched, summed over the
        expert layers of the last forward (traced inside a compiled
        step), how many layers that was, and the slabs they ran."""
        stats = [unwrap(lyr.mlp.last_stats) for lyr in self.layers
                 if lyr.is_moe and lyr.mlp.last_stats is not None]
        total = sum(stats[1:], stats[0])
        return jnp.concatenate([total[:3],
                                jnp.asarray([len(stats)], jnp.int32),
                                total[3:]])
