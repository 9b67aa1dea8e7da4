"""Solar-Open2: a decoder of linear-attention (KDA) layers on a
constant-size state, with a softmax GQA layer every fourth, every layer
an expert layer.

Published configuration: upstage/Solar-Open2-250B ``config.json``
(model_type ``solar_open2``). The field names of ``SolarOpen2Config`` are
its keys. Pre-norm residual blocks, ``x += Mix(RMSNorm(x)); x +=
MoE(RMSNorm(x))``, final RMSNorm, untied head. With ``u`` the normed
layer input, H heads of d:

* KDA layer (gated delta rule with channel-wise decay;
  ``linear_attn_config``): ``q~, k~, v~ = u W_q, u W_k, u W_v``; each
  channel passes a causal convolution over its own last
  ``short_conv_kernel_size`` tokens, then SiLU. A head:
  ``q = l2norm(q') / sqrt(d)``, ``k = l2norm(k')``, ``v = v'``;
  ``a = -exp(A_log[h]) softplus((u W_f1) W_f2 + dt_bias)`` (a key
  channel's log-decay), ``beta = 2 sigmoid(u W_b)`` (the 2 is
  ``kda_allow_neg_eigval``), and the recurrence of ``kernels/kda.py`` on
  a float32 state ``S`` [d, d] a head. Output
  ``(RMSNorm_head(o) * sigmoid((u W_g1) W_g2)) W_o``.
* GQA layer (indices ``gqa_layers``; ``use_rope`` false, ``use_gqa_gate``
  true): ``q = u W_q`` (H x d), ``k, v = u W_k, u W_v`` (``num_key_value
  _heads`` x d), NO position signal, causal softmax, output
  ``(attn * sigmoid(u W_gate)) W_o``.
* every layer: ``MoELayer`` with a sigmoid top-k gate over
  ``n_routed_experts`` SwiGLU experts of ``moe_intermediate_size`` plus
  ``n_shared_experts`` shared; ``expert_share=(index, of)`` holds one
  chip's share.

Serving (docs/SERVING.md "Model polymorphism"): ``serving_spec()`` gives
the cache PER LAYER. A GQA layer is ``kind: "kv"``: the paged (k, v)
pools with heads that ``LlamaAttention`` uses, the Pallas paged decode
kernel on a one-token step, gathered pages in query blocks on a chunk. A
KDA layer is ``kind: "state"``: arrays with one row a SLOT and no pages,
``S`` [H, d, d] float32 and the convolution's tail, its last taps - 1
inputs ``conv0..`` [3 H d] each. The engine hands such a layer ``(S,
conv0, .., slots, n_valid)``: ``slots`` the state rows of the batch's
sequences (None: row i is slot i, the decode program), ``n_valid`` how
many of each sequence's tokens are real (0: a lane that is not decoding;
a chunk's real length before its padding). A sequence at position 0 starts from
zeros, whatever its rows hold; every other sequence from its rows; rows
of a sequence with ``n_valid`` 0 come back bit-identical.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ... import monitor
from ...core import place
from ...core import random as random_mod
from ...core.dispatch import unwrap, wrap
from ...framework.param_attr import ParamAttr
from ...incubate.distributed.models.moe import MoELayer, SigmoidTopKGate
from ...kernels import kda
from ...nn.initializer import Constant, Initializer, Normal
from ...nn.layer.common import Embedding
from ...nn.layer.container import LayerList
from ...nn.layer.layers import Layer, param_dtype
from .dots3_note import Dots3MLP, _init_linear
from .gqa import gqa_attend, paged_gqa
from .llama import LlamaRMSNorm

L2_EPS = 1e-6


def _linear_attn_config():
    return {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
            "num_kv_heads": None}


@dataclass
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    intermediate_size: int = 10240      # read by no layer: no dense FFN
    moe_intermediate_size: int = 1280
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    tie_word_embeddings: bool = False
    use_rope: bool = False
    gqa_interval: int = 3
    gqa_layers: Optional[Tuple[int, ...]] = None
    use_gqa_gate: bool = True
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    linear_attn_config: dict = field(default_factory=_linear_attn_config)
    first_k_dense_replace: int = 0
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # not in the published file
    expert_share: Tuple[int, int] = (0, 1)    # (index, of): experts held
    dtype: str = "float32"                    # honoured at construction
    initializer_range: float = 0.02           # std of every matrix's init
    prefill_query_block: int = 256            # queries a block of scores

    def __post_init__(self):
        if self.gqa_layers is None:
            self.gqa_layers = range(0, self.num_hidden_layers,
                                    self.gqa_interval + 1)
        self.gqa_layers = tuple(int(i) for i in self.gqa_layers)
        if any(not 0 <= i < self.num_hidden_layers
               for i in self.gqa_layers):
            raise ValueError(
                f"gqa_layers {self.gqa_layers} name a layer beyond the "
                f"{self.num_hidden_layers} there are")
        for name, want in (("use_rope", False), ("use_gqa_gate", True),
                           ("kda_use_full_proj", False),
                           ("first_k_dense_replace", 0),
                           ("tie_word_embeddings", False)):
            if getattr(self, name) != want:
                raise ValueError(
                    f"{name}={getattr(self, name)!r}: only {want!r} is "
                    f"implemented")

    @property
    def n_routed_experts_held(self) -> int:
        return self.n_routed_experts // self.expert_share[1]

    @staticmethod
    def tiny(**over):
        """The CPU tests' size: one GQA layer and two KDA layers, 8
        experts top-2 with one shared, 4 heads of 16 (2 KV heads)."""
        kw = dict(
            vocab_size=96, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3, gqa_layers=(0,),
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            max_position_embeddings=256,
            linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                                    num_heads=4, num_kv_heads=None),
            n_routed_experts=8, num_experts_per_tok=2,
            prefill_query_block=8)
        kw.update(over)
        return SolarOpen2Config(**kw)


class _Drawn(Initializer):
    """fn(u), u ~ U(0, 1) elementwise from the global generator."""

    def __init__(self, fn):
        self.fn = fn

    def __call__(self, param, block=None):
        u = jax.random.uniform(random_mod.next_key(), param._data.shape,
                               jnp.float32)
        return self._set(param, self.fn(u))


def _log_uniform(u, lo, hi):
    return jnp.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))


def _inverse_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


def l2norm(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


class SolarKDAttention(Layer):
    """One gated delta-rule linear-attention block (module docstring)."""

    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        c, lin = config, config.linear_attn_config
        self.hidden = c.hidden_size
        self.heads = H = int(lin["num_heads"])
        self.d = d = int(lin["head_dim"])
        self.taps = int(lin["short_conv_kernel_size"])
        self.eps = c.rms_norm_eps
        self.beta_scale = 2.0 if c.kda_allow_neg_eigval else 1.0
        std = c.initializer_range
        self.q_proj = _init_linear(self.hidden, H * d, std)
        self.k_proj = _init_linear(self.hidden, H * d, std)
        self.v_proj = _init_linear(self.hidden, H * d, std)
        # a tap of each channel's own convolution: [taps, q ; k ; v]
        bound = 1.0 / math.sqrt(self.taps)
        self.conv_weight = self.create_parameter(
            [self.taps, 3 * H * d], default_initializer=_Drawn(
                lambda u: (2.0 * u - 1.0) * bound))
        # the two low-rank pairs (kda_use_full_proj false), rank = d
        self.f_a_proj = _init_linear(self.hidden, d, std)
        self.f_b_proj = _init_linear(d, H * d, std)
        self.g_a_proj = _init_linear(self.hidden, d, std)
        self.g_b_proj = _init_linear(d, H * d, std)
        self.b_proj = _init_linear(self.hidden, H, std)
        self.A_log = self.create_parameter(
            [H], default_initializer=_Drawn(
                lambda u: jnp.log(1.0 + 15.0 * u)))
        self.dt_bias = self.create_parameter(
            [H * d], default_initializer=_Drawn(
                lambda u: _inverse_softplus(_log_uniform(u, 1e-3, 1e-1))))
        self.o_norm_weight = self.create_parameter(
            [d], default_initializer=Constant(1.0))
        self.o_proj = _init_linear(H * d, self.hidden, std)

    def state_arrays(self, dtype):
        """What a slot keeps of this layer: name -> (shape, dtype). The
        convolution's tail is one array a token (`conv0` the oldest), so
        that a tick shifts it by renaming rows and no array is sliced
        across its tiles."""
        width = 3 * self.heads * self.d
        return {"S": ([self.heads, self.d, self.d], "float32"),
                **{f"conv{j}": ([width], str(dtype))
                   for j in range(self.taps - 1)}}

    def _mix(self, u, hist, n_valid):
        """Projections, convolution, gates. u [b, s, hidden] Tensor; hist
        the taps - 1 inputs before the chunk, [b, 3 H d] each, oldest
        first; n_valid [b]. Returns q, k, v [b, s, H, d], a [b, s, H, d],
        beta [b, s, H] (float32; a and beta zero on tokens that are not
        real), the output gate [b, s, H, d], and the convolution's input
        with the history in front [b, taps - 1 + s, 3 H d]."""
        b, s = u.shape[0], u.shape[1]
        H, d, K = self.heads, self.d, self.taps
        x = jnp.concatenate([unwrap(self.q_proj(u)), unwrap(self.k_proj(u)),
                             unwrap(self.v_proj(u))], -1)
        xx = jnp.concatenate([h[:, None].astype(x.dtype) for h in hist]
                             + [x], 1)
        w = unwrap(self.conv_weight).astype(jnp.float32)
        y = sum(xx[:, j:j + s].astype(jnp.float32) * w[j] for j in range(K))
        y = jax.nn.silu(y).reshape(b, s, 3, H, d)
        q = l2norm(y[:, :, 0]) * jnp.float32(1.0 / math.sqrt(d))
        k, v = l2norm(y[:, :, 1]), y[:, :, 2]
        raw = unwrap(self.f_b_proj(self.f_a_proj(u))).astype(jnp.float32) \
            + unwrap(self.dt_bias).astype(jnp.float32)
        a = -jnp.exp(unwrap(self.A_log).astype(jnp.float32))[:, None] \
            * jax.nn.softplus(raw.reshape(b, s, H, d))
        beta = self.beta_scale * jax.nn.sigmoid(
            unwrap(self.b_proj(u)).astype(jnp.float32))
        real = jnp.arange(s)[None] < n_valid[:, None]
        a = jnp.where(real[..., None, None], a, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
        gate = jax.nn.sigmoid(unwrap(self.g_b_proj(self.g_a_proj(u)))
                              .astype(jnp.float32)).reshape(b, s, H, d)
        return q, k, v, a, beta, gate, xx

    def _out(self, o, gate, dtype):
        """(RMSNorm_head(o) * gate) W_o; o, gate [b, s, H, d] float32."""
        b, s = o.shape[:2]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + jnp.float32(self.eps)) \
            * unwrap(self.o_norm_weight).astype(jnp.float32)
        return self.o_proj(wrap((o * gate).astype(dtype).reshape(
            b, s, self.heads * self.d)))

    def _decode_step(self, S, q, k, v, a, beta, alive):
        """One token a slot, every slot's rows in place."""
        if not place.accelerator_available():
            monitor.counter("kernels.decode.kda_fallback").increase()
            return kda.kda_step_arrays(S, q, k, v, a, beta, alive)
        why = kda.kda_decode_requirements(self.heads, self.d, self.d)
        if why:
            raise ValueError(f"the state of a KDA layer cannot take "
                             f"kda_decode: {why}")
        monitor.counter("kernels.decode.kda_pallas").increase()
        return kda.kda_decode(S, q, k, v, a, beta, alive)

    def forward(self, u, kv_cache=None, cache_index=None):
        b, s = u.shape[0], u.shape[1]
        dtype = unwrap(u).dtype
        width = 3 * self.heads * self.d
        if kv_cache is None:
            hist = [jnp.zeros((b, width), dtype)] * (self.taps - 1)
            q, k, v, a, beta, gate, _ = self._mix(
                u, hist, jnp.full((b,), s, jnp.int32))
            monitor.counter("kernels.prefill.kda_chunked").increase()
            o, _ = kda.kda_chunked(q, k, v, a, beta, jnp.zeros(
                (b, self.heads, self.d, self.d), jnp.float32))
            return self._out(o, gate, dtype)
        S, *conv, slots, n_valid = kv_cache
        n_valid = n_valid.astype(jnp.int32)
        if slots is None:
            # the decode program: one token a slot, row i is slot i
            q, k, v, a, beta, gate, xx = self._mix(u, conv, n_valid)
            alive = n_valid > 0
            o, S = self._decode_step(S, q[:, 0], k[:, 0], v[:, 0], a[:, 0],
                                     beta[:, 0], alive)
            conv = [jnp.where(alive[:, None], xx[:, j + 1].astype(h.dtype), h)
                    for j, h in enumerate(conv)]
            return self._out(o[:, None], gate, dtype), (S, *conv)
        # a chunk of each sequence: from zeros at position 0, else from
        # the slot's rows; the rows are written back where they lie
        fresh = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(
            unwrap(cache_index), jnp.int32)), (b,)) == 0

        def rows(x):
            """x [slots, ...] -> the batch's rows [b, ...], zeros where
            the sequence starts."""
            got = jnp.concatenate([jax.lax.dynamic_slice_in_dim(
                x, slots[r], 1, 0) for r in range(b)], 0)
            return jnp.where(fresh.reshape((b,) + (1,) * (x.ndim - 1)),
                             jnp.zeros((), x.dtype), got)

        q, k, v, a, beta, gate, xx = self._mix(
            u, [rows(h) for h in conv], n_valid)
        monitor.counter("kernels.prefill.kda_chunked").increase()
        o, S_end = kda.kda_chunked(q, k, v, a, beta, rows(S))
        for r in range(b):
            S = jax.lax.dynamic_update_slice_in_dim(
                S, S_end[r:r + 1], slots[r], 0)
            # the tail after the last REAL token: rows n_valid .. of xx
            tail = jax.lax.dynamic_slice_in_dim(xx[r], n_valid[r],
                                                self.taps - 1, 0)
            conv = [jax.lax.dynamic_update_slice_in_dim(
                h, tail[j][None].astype(h.dtype), slots[r], 0)
                for j, h in enumerate(conv)]
        return self._out(o, gate, dtype), (S, *conv)


class SolarGQAttention(Layer):
    """Softmax GQA with no position encoding and an element-wise output
    gate, on the paged (k, v) pools with heads."""

    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        c = config
        self.heads, self.kv_heads = c.num_attention_heads, \
            c.num_key_value_heads
        self.d = c.head_dim
        self.q_block = int(c.prefill_query_block)
        std = c.initializer_range
        self.q_proj = _init_linear(c.hidden_size, self.heads * self.d, std)
        self.k_proj = _init_linear(c.hidden_size, self.kv_heads * self.d,
                                   std)
        self.v_proj = _init_linear(c.hidden_size, self.kv_heads * self.d,
                                   std)
        self.gate_proj = _init_linear(c.hidden_size, self.heads * self.d,
                                      std)
        self.o_proj = _init_linear(self.heads * self.d, c.hidden_size, std)

    def forward(self, u, kv_cache=None, cache_index=None):
        b, s = u.shape[0], u.shape[1]
        H, G, d = self.heads, self.kv_heads, self.d
        q = unwrap(self.q_proj(u)).reshape(b, s, H, d)
        k = unwrap(self.k_proj(u)).reshape(b, s, G, d)
        v = unwrap(self.v_proj(u)).reshape(b, s, G, d)
        gate = jax.nn.sigmoid(unwrap(self.gate_proj(u)).astype(jnp.float32))
        new_cache = None
        if kv_cache is None:
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                   (b, s))
            out = gqa_attend(q, k, v, pos, pos, self.q_block)
        else:
            out, new_cache = paged_gqa(q, k, v, kv_cache, cache_index,
                                       self.q_block)
        out = (out.reshape(b, s, H * d) * gate).astype(q.dtype)
        out = self.o_proj(wrap(out))
        return out if kv_cache is None else (out, new_cache)


class SolarDecoderLayer(Layer):
    def __init__(self, config: SolarOpen2Config, index: int):
        super().__init__()
        c = config
        self.is_gqa = index in c.gqa_layers
        self.input_layernorm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = SolarGQAttention(c) if self.is_gqa \
            else SolarKDAttention(c)
        self.post_attention_layernorm = LlamaRMSNorm(c.hidden_size,
                                                     c.rms_norm_eps)
        shared = Dots3MLP(
            c.hidden_size, c.moe_intermediate_size * c.n_shared_experts,
            c.initializer_range) if c.n_shared_experts else None
        self.mlp = MoELayer(
            d_model=c.hidden_size, d_hidden=c.moe_intermediate_size,
            num_experts=c.n_routed_experts,
            gate=SigmoidTopKGate(c.n_routed_experts, c.num_experts_per_tok,
                                 c.norm_topk_prob, c.routed_scaling_factor),
            activation="swiglu", expert_share=c.expert_share,
            shared_experts=shared)

    def forward(self, x, kv_cache=None, cache_index=None, token_mask=None):
        new_cache = None
        if kv_cache is not None:
            mix, new_cache = self.self_attn(
                self.input_layernorm(x), kv_cache=kv_cache,
                cache_index=cache_index)
        else:
            mix = self.self_attn(self.input_layernorm(x))
        x = x + mix
        x = x + self.mlp(self.post_attention_layernorm(x),
                         token_mask=token_mask,
                         decode_mode=kv_cache is not None)
        return x if kv_cache is None else (x, new_cache)


class SolarOpen2ForCausalLM(Layer):
    """The decoder, with the call signature the serving engine uses for
    LlamaForCausalLM (``kv_caches`` / ``cache_index``)."""

    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        self.config = config
        c = config
        # every parameter is created in config.dtype: at the published
        # widths the model does not fit the chip in float32 first; and
        # one at a time, or the float32 draws of an expert layer's three
        # matrices lie beside the weights (11-14 GB at the peak for a
        # model of 6.6)
        with param_dtype(c.dtype, wait=True):
            self.embed_tokens = Embedding(
                c.vocab_size, c.hidden_size, weight_attr=ParamAttr(
                    initializer=Normal(0.0, c.initializer_range)))
            self.layers = LayerList([SolarDecoderLayer(c, i)
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
        """The engine's probe. ``cache_layers``: a GQA layer's paged
        pools with heads (``kv``), a KDA layer's per-slot arrays
        (``state``: name -> (shape a slot, dtype), in the order the
        layer takes them); ``tick_stats`` as Dots3NoteForCausalLM's."""
        c = self.config
        dtype = unwrap(self.lm_head.weight).dtype
        return {
            "kind": "decoder",
            "num_layers": c.num_hidden_layers,
            "max_context": c.max_position_embeddings,
            "vocab_size": c.vocab_size,
            "cache_layers": [
                {"kind": "kv", "kv_heads": c.num_key_value_heads,
                 "head_dim": c.head_dim} if lyr.is_gqa else
                {"kind": "state",
                 "arrays": lyr.self_attn.state_arrays(dtype)}
                for lyr in self.layers],
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
        """[5] int32, in ``tick_stats``' order (see Dots3NoteForCausalLM)."""
        stats = [unwrap(lyr.mlp.last_stats) for lyr in self.layers
                 if lyr.mlp.last_stats is not None]
        total = sum(stats[1:], stats[0])
        return jnp.concatenate([total[:3],
                                jnp.asarray([len(stats)], jnp.int32),
                                total[3:]])
