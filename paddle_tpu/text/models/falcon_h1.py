"""Falcon-H1: a decoder whose every block runs a Mamba-2 state-space
mixer and a softmax GQA attention IN PARALLEL on one normed input, then
a SwiGLU FFN, with muP multipliers on every path.

Published configuration: tiiuae/Falcon-H1-34B-Instruct ``config.json``
(model_type ``falcon_h1``); the published implementation is
``transformers``' ``models/falcon_h1/modeling_falcon_h1.py``. The field
names of ``FalconH1Config`` are its keys. With ``u`` a block's input:

    h   = RMSNorm_in(u)
    u1  = u + Mixer(h * ssm_in_multiplier) * ssm_out_multiplier
            + Attn(h * attention_in_multiplier) * attention_out_multiplier
    u2  = u1 + FFN(RMSNorm_ff(u1))
    x0  = embed[ids] * embedding_multiplier
    logits = (RMSNorm_final(u_last) W_head) * lm_head_multiplier

* Attn: ``q = h W_q``, ``k = (h W_k) * key_multiplier``, ``v = h W_v``
  (``num_attention_heads`` / ``num_key_value_heads`` heads of
  ``head_dim``), rotate-half RoPE at ``rope_theta`` over all of a head's
  dims on q and k, causal softmax at 1/sqrt(head_dim), ``W_o``.
* Mixer: ``[z | xBC | dt] = (h W_in) * mup_vector``, the vector holding
  ``ssm_multipliers[0..4]`` over the z, x, B, C and dt columns; each xBC
  channel passes a causal convolution over its own last ``mamba_d_conv``
  tokens (with bias), then SiLU, and splits into x (``mamba_n_heads`` x
  ``mamba_d_head``), B and C (``mamba_n_groups`` x ``mamba_d_state``
  each). ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, one a
  head, and the recurrence of ``kernels/ssd.py`` on a float32 state
  ``S`` [d_head, d_state] a head; ``y = S C + D x``. Output
  ``GroupRMSNorm(y * silu(z)) W_out`` (``mamba_rms_norm`` true,
  ``mamba_norm_before_gate`` false: the gate first, then an RMSNorm over
  each of ``mamba_n_groups`` slices of the d_ssm values).
* FFN: ``(W_up m * silu((W_gate m) * mlp_multipliers[0])) W_down *
  mlp_multipliers[1]``.

The seeded draw is muP's: a matrix is N(0, 1 / fan_in) over the
multipliers its output meets, so that every path carries unit scale
into the residual whatever its multiplier (N(0, 0.02) under multipliers
of 0.0375 and 0.0078 would leave branches that vanish, and a comparison
that cannot tell them ON from OFF); q and k are drawn ``QK_GAIN`` wider,
since a softmax over thousands of random keys is flat at unit scale and
the attention branch would again be an average of noise.

Serving (docs/SERVING.md "Model polymorphism"): ``serving_spec()``'s
``cache_layers`` is the flat list of what the model keeps, in the order
``forward`` takes ``kv_caches``: TWO entries a block, the attention's
paged (k, v) pools with heads (``kind: "kv"``) and then the mixer's
arrays by slot (``kind: "state"``: ``S`` [H, d_head, d_state] float32
and the convolution's tail, its last taps - 1 inputs ``conv0..``
[conv_dim] each). The engine hands the second ``(S, conv0, .., slots,
n_valid)`` with the meanings ``solar_open2.py`` gives them.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import monitor
from ...core.dispatch import unwrap, wrap
from ...framework.param_attr import ParamAttr
from ...kernels import ssd
from ...nn.initializer import Constant, Normal
from ...nn.layer.common import Embedding
from ...nn.layer.container import LayerList
from ...nn.layer.layers import Layer, param_dtype
from .dots3_note import _init_linear, _rope
from .gqa import gqa_attend, paged_gqa
from .llama import LlamaRMSNorm
from .solar_open2 import _Drawn, _inverse_softplus, _log_uniform

QK_GAIN = 1.6      # q and k drawn this much wider: scores of std ~2.5


@dataclass
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    rope_scaling: Optional[dict] = None
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    projectors_bias: bool = False
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_expand: int = 2               # read by no layer: d_ssm is given
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    mlp_multipliers: Tuple[float, ...] = (0.1767766952966369,
                                          0.011160714285714284)
    # not in the published file
    dtype: str = "float32"                    # honoured at construction
    prefill_query_block: int = 256            # queries a block of scores

    def __post_init__(self):
        self.ssm_multipliers = tuple(float(m) for m in self.ssm_multipliers)
        self.mlp_multipliers = tuple(float(m) for m in self.mlp_multipliers)
        for name, want in (("attention_bias", False), ("mlp_bias", False),
                           ("projectors_bias", False),
                           ("mamba_proj_bias", False),
                           ("mamba_conv_bias", True),
                           ("mamba_rms_norm", True),
                           ("mamba_norm_before_gate", False),
                           ("rope_scaling", None), ("hidden_act", "silu"),
                           ("tie_word_embeddings", False)):
            if getattr(self, name) != want:
                raise ValueError(
                    f"{name}={getattr(self, name)!r}: only {want!r} is "
                    f"implemented")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError(
                f"mamba_n_heads {self.mamba_n_heads} x mamba_d_head "
                f"{self.mamba_d_head} is not mamba_d_ssm {self.mamba_d_ssm}")

    @staticmethod
    def tiny(**over):
        """The CPU tests' size: two blocks, 4 query / 2 KV heads of 16, 4
        mixer heads of 8 in 2 groups on a state of 16, the published
        multipliers."""
        kw = dict(
            vocab_size=96, hidden_size=64, intermediate_size=96,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, max_position_embeddings=256,
            mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
            mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=8,
            prefill_query_block=8)
        kw.update(over)
        return FalconH1Config(**kw)


def _scaled(x, multiplier):
    """x times a muP multiplier: the product in float32, the result in
    x's dtype (the multiplier itself is never rounded)."""
    return (x.astype(jnp.float32) * jnp.float32(multiplier)).astype(x.dtype)


def _mup_linear(n_in, n_out, multiplier, gain=1.0):
    """A bias-free Linear drawn N(0, 1 / n_in) over `multiplier` (a
    scalar, or one value an output column): unit scale out of unit scale
    in once the multipliers have acted."""
    return _init_linear(n_in, n_out, gain / (
        math.sqrt(n_in) * np.asarray(multiplier, np.float32)))


class FalconH1Attention(Layer):
    """Softmax GQA with RoPE and a key multiplier, on the paged (k, v)
    pools with heads."""

    def __init__(self, config: FalconH1Config):
        super().__init__()
        c = config
        self.heads, self.kv_heads = c.num_attention_heads, \
            c.num_key_value_heads
        self.d = c.head_dim
        self.theta = float(c.rope_theta)
        self.key_multiplier = float(c.key_multiplier)
        self.q_block = int(c.prefill_query_block)
        m_in = c.attention_in_multiplier
        self.q_proj = _mup_linear(c.hidden_size, self.heads * self.d, m_in,
                                  QK_GAIN)
        self.k_proj = _mup_linear(c.hidden_size, self.kv_heads * self.d,
                                  m_in * c.key_multiplier, QK_GAIN)
        self.v_proj = _mup_linear(c.hidden_size, self.kv_heads * self.d,
                                  m_in)
        self.o_proj = _mup_linear(self.heads * self.d, c.hidden_size,
                                  c.attention_out_multiplier)

    def forward(self, u, kv_cache=None, cache_index=None):
        b, s = u.shape[0], u.shape[1]
        H, G, d = self.heads, self.kv_heads, self.d
        q = unwrap(self.q_proj(u)).reshape(b, s, H, d)
        k = _scaled(unwrap(self.k_proj(u)), self.key_multiplier).reshape(
            b, s, G, d)
        v = unwrap(self.v_proj(u)).reshape(b, s, G, d)
        pos0 = jnp.zeros((b,), jnp.int32) if kv_cache is None else \
            jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(
                unwrap(cache_index), jnp.int32)), (b,))
        pos = pos0[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        q, k = _rope(q, pos, self.theta), _rope(k, pos, self.theta)
        new_cache = None
        if kv_cache is None:
            out = gqa_attend(q, k, v, pos, pos, self.q_block)
        else:
            out, new_cache = paged_gqa(q, k, v, kv_cache, pos0,
                                       self.q_block)
        out = self.o_proj(wrap(out.reshape(b, s, H * d).astype(q.dtype)))
        return out if kv_cache is None else (out, new_cache)


class FalconH1Mixer(Layer):
    """One Mamba-2 state-space mixer (module docstring)."""

    def __init__(self, config: FalconH1Config):
        super().__init__()
        c = config
        self.heads, self.p = c.mamba_n_heads, c.mamba_d_head
        self.groups, self.n = c.mamba_n_groups, c.mamba_d_state
        self.taps = c.mamba_d_conv
        self.chunk = c.mamba_chunk_size
        self.eps = c.rms_norm_eps
        self.d_ssm = c.mamba_d_ssm
        gn = self.groups * self.n
        self.conv_dim = self.d_ssm + 2 * gn
        # ssm_multipliers over the z, x, B, C, dt columns of in_proj
        self.mup = np.repeat(np.asarray(c.ssm_multipliers, np.float32),
                             [self.d_ssm, self.d_ssm, gn, gn, self.heads])
        self.in_proj = _mup_linear(c.hidden_size, self.mup.size,
                                   c.ssm_in_multiplier * self.mup)
        # a tap of each channel's own convolution, and its bias
        bound = 1.0 / math.sqrt(self.taps)
        within_bound = _Drawn(lambda u: (2.0 * u - 1.0) * bound)
        self.conv_weight = self.create_parameter(
            [self.taps, self.conv_dim], default_initializer=within_bound)
        self.conv_bias = self.create_parameter(
            [self.conv_dim], default_initializer=within_bound)
        # -A log-uniform over 1/16 .. 16 and dt over the published code's
        # time_step_min .. time_step_max: a head forgets in anything from
        # a token to ten thousand, and a head's share of y grows as its A
        # shrinks, so the state a chunk inherits is a visible part of y
        self.A_log = self.create_parameter(
            [self.heads], default_initializer=_Drawn(
                lambda u: (2.0 * u - 1.0) * math.log(16.0)))
        self.dt_bias = self.create_parameter(
            [self.heads], default_initializer=_Drawn(
                lambda u: _inverse_softplus(_log_uniform(u, 1e-3, 1e-1))))
        self.D = self.create_parameter(
            [self.heads], default_initializer=_Drawn(lambda u: 0.5 + u))
        self.norm_weight = self.create_parameter(
            [self.d_ssm], default_initializer=Constant(1.0))
        self.out_proj = _mup_linear(self.d_ssm, c.hidden_size,
                                    c.ssm_out_multiplier)

    def state_arrays(self, dtype):
        """What a slot keeps of this mixer: name -> (shape, dtype). The
        convolution's tail is one array a token (`conv0` the oldest), as
        `SolarKDAttention.state_arrays` lays it out."""
        return {"S": ([self.heads, self.p, self.n], "float32"),
                **{f"conv{j}": ([self.conv_dim], str(dtype))
                   for j in range(self.taps - 1)}}

    def _mix(self, u, hist, n_valid):
        """Projection, multipliers, convolution. u [b, s, hidden] Tensor;
        hist the taps - 1 convolution inputs before the chunk, [b,
        conv_dim] each, oldest first; n_valid [b]. Returns x [b, s, H,
        P], B and C [b, s, G, N], dt and a [b, s, H] (float32; dt and a
        zero on tokens that are not real), the gate z [b, s, d_ssm], and
        the convolution's input with the history in front [b, taps - 1 +
        s, conv_dim]."""
        b, s = u.shape[0], u.shape[1]
        H, G, K = self.heads, self.groups, self.taps
        proj = unwrap(self.in_proj(u)).astype(jnp.float32) * self.mup
        z, xbc, dt = jnp.split(proj, [self.d_ssm,
                                      self.d_ssm + self.conv_dim], -1)
        xx = jnp.concatenate([h[:, None] for h in hist]
                             + [xbc.astype(hist[0].dtype)], 1)
        w = unwrap(self.conv_weight).astype(jnp.float32)
        y = sum(xx[:, j:j + s].astype(jnp.float32) * w[j] for j in range(K))
        y = jax.nn.silu(y + unwrap(self.conv_bias).astype(jnp.float32))
        x, B, C = jnp.split(y, [self.d_ssm, self.d_ssm + G * self.n], -1)
        real = jnp.arange(s)[None] < n_valid[:, None]
        dt = jnp.where(real[..., None], jax.nn.softplus(
            dt + unwrap(self.dt_bias).astype(jnp.float32)), 0.0)
        a = -jnp.exp(unwrap(self.A_log).astype(jnp.float32)) * dt
        return (x.reshape(b, s, H, self.p), B.reshape(b, s, G, self.n),
                C.reshape(b, s, G, self.n), dt, a, z, xx)

    def _out(self, y, x, z, dtype):
        """GroupRMSNorm((y + D x) * silu(z)) W_out; y, x [b, s, H, P],
        z [b, s, d_ssm], float32."""
        b, s = y.shape[:2]
        y = y + unwrap(self.D).astype(jnp.float32)[:, None] * x
        y = (y.reshape(b, s, self.d_ssm) * jax.nn.silu(z)).reshape(
            b, s, self.groups, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                              + jnp.float32(self.eps))
        y = y.reshape(b, s, self.d_ssm) \
            * unwrap(self.norm_weight).astype(jnp.float32)
        return self.out_proj(wrap(y.astype(dtype)))

    def _chunked(self, x, dt, a, B, C, S0):
        monitor.counter("kernels.prefill.ssd_chunked").increase()
        return ssd.ssd_chunked(x, dt, a, B, C, S0, self.chunk)

    def forward(self, u, kv_cache=None, cache_index=None):
        b, s = u.shape[0], u.shape[1]
        dtype = unwrap(u).dtype
        if kv_cache is None:
            hist = [jnp.zeros((b, self.conv_dim), dtype)] * (self.taps - 1)
            x, B, C, dt, a, z, _ = self._mix(
                u, hist, jnp.full((b,), s, jnp.int32))
            y, _ = self._chunked(x, dt, a, B, C, jnp.zeros(
                (b, self.heads, self.p, self.n), jnp.float32))
            return self._out(y, x, z, dtype)
        S, *conv, slots, n_valid = kv_cache
        n_valid = n_valid.astype(jnp.int32)
        if slots is None:
            # the decode program: one token a slot, row i is slot i
            x, B, C, dt, a, z, xx = self._mix(u, conv, n_valid)
            alive = n_valid > 0
            y, S = ssd.ssd_step_arrays(S, x[:, 0], dt[:, 0], a[:, 0],
                                       B[:, 0], C[:, 0], alive)
            conv = [jnp.where(alive[:, None], xx[:, j + 1], h)
                    for j, h in enumerate(conv)]
            return self._out(y[:, None], x, z, dtype), (S, *conv)
        # a chunk of each sequence: from zeros at position 0, else from
        # the slot's rows; the rows are written back where they lie
        fresh = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(
            unwrap(cache_index), jnp.int32)), (b,)) == 0

        def rows(t):
            """t [slots, ...] -> the batch's rows [b, ...], zeros where
            the sequence starts."""
            got = jnp.concatenate([jax.lax.dynamic_slice_in_dim(
                t, slots[r], 1, 0) for r in range(b)], 0)
            return jnp.where(fresh.reshape((b,) + (1,) * (t.ndim - 1)),
                             jnp.zeros((), t.dtype), got)

        x, B, C, dt, a, z, xx = self._mix(u, [rows(h) for h in conv],
                                          n_valid)
        y, S_end = self._chunked(x, dt, a, B, C, rows(S))
        for r in range(b):
            S = jax.lax.dynamic_update_slice_in_dim(
                S, S_end[r:r + 1], slots[r], 0)
            # the tail after the last REAL token: rows n_valid .. of xx
            tail = jax.lax.dynamic_slice_in_dim(xx[r], n_valid[r],
                                                self.taps - 1, 0)
            conv = [jax.lax.dynamic_update_slice_in_dim(
                h, tail[j][None], slots[r], 0) for j, h in enumerate(conv)]
        return self._out(y, x, z, dtype), (S, *conv)


class FalconH1MLP(Layer):
    """SwiGLU with a multiplier on the gate's input to SiLU and one on
    the output."""

    def __init__(self, config: FalconH1Config):
        super().__init__()
        c = config
        self.gate_multiplier, self.down_multiplier = c.mlp_multipliers
        self.gate_proj = _mup_linear(c.hidden_size, c.intermediate_size,
                                     self.gate_multiplier)
        self.up_proj = _mup_linear(c.hidden_size, c.intermediate_size, 1.0)
        self.down_proj = _mup_linear(c.intermediate_size, c.hidden_size,
                                     self.down_multiplier)

    def forward(self, x):
        act = jax.nn.silu(_scaled(unwrap(self.gate_proj(x)),
                                  self.gate_multiplier))
        y = self.down_proj(wrap(unwrap(self.up_proj(x)) * act))
        return wrap(_scaled(unwrap(y), self.down_multiplier))


class FalconH1DecoderLayer(Layer):
    def __init__(self, config: FalconH1Config):
        super().__init__()
        c = config
        self.attention_in_multiplier = float(c.attention_in_multiplier)
        self.attention_out_multiplier = float(c.attention_out_multiplier)
        self.ssm_in_multiplier = float(c.ssm_in_multiplier)
        self.ssm_out_multiplier = float(c.ssm_out_multiplier)
        self.input_layernorm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = FalconH1Attention(c)
        self.mamba = FalconH1Mixer(c)
        self.pre_ff_layernorm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.feed_forward = FalconH1MLP(c)

    def forward(self, x, kv_cache=None, state_cache=None, cache_index=None):
        """Both mixers read the one normed input; `kv_cache` is the
        attention's, `state_cache` the state-space mixer's."""
        h = unwrap(self.input_layernorm(x))
        h_attn = wrap(_scaled(h, self.attention_in_multiplier))
        h_ssm = wrap(_scaled(h, self.ssm_in_multiplier))
        new_kv = new_state = None
        if kv_cache is None:
            attn, mix = self.self_attn(h_attn), self.mamba(h_ssm)
        else:
            attn, new_kv = self.self_attn(h_attn, kv_cache=kv_cache,
                                          cache_index=cache_index)
            mix, new_state = self.mamba(h_ssm, kv_cache=state_cache,
                                        cache_index=cache_index)
        x = x + wrap(_scaled(unwrap(mix), self.ssm_out_multiplier)
                     + _scaled(unwrap(attn), self.attention_out_multiplier))
        x = x + self.feed_forward(self.pre_ff_layernorm(x))
        return x if kv_cache is None else (x, new_kv, new_state)


class FalconH1ForCausalLM(Layer):
    """The decoder, with the call signature the serving engine uses for
    LlamaForCausalLM (``kv_caches`` / ``cache_index``)."""

    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.config = config
        c = config
        # every parameter is created in config.dtype, one at a time (see
        # SolarOpen2ForCausalLM)
        with param_dtype(c.dtype, wait=True):
            self.embed_tokens = Embedding(
                c.vocab_size, c.hidden_size, weight_attr=ParamAttr(
                    initializer=Normal(0.0, 1.0 / c.embedding_multiplier)))
            self.layers = LayerList([FalconH1DecoderLayer(c)
                                     for _ in range(c.num_hidden_layers)])
            self.final_layernorm = LlamaRMSNorm(c.hidden_size,
                                                c.rms_norm_eps)
            self.lm_head = _mup_linear(c.hidden_size, c.vocab_size,
                                       c.lm_head_multiplier)

    def _head(self, x):
        return wrap(_scaled(unwrap(self.lm_head(self.final_layernorm(x))),
                            self.config.lm_head_multiplier))

    def forward(self, input_ids, kv_caches=None, cache_index=None):
        x = wrap(_scaled(unwrap(self.embed_tokens(input_ids)),
                         self.config.embedding_multiplier))
        if kv_caches is None:
            for lyr in self.layers:
                x = lyr(x)
            return self._head(x)
        new_caches = []
        for i, lyr in enumerate(self.layers):
            x, kv, state = lyr(x, kv_cache=kv_caches[2 * i],
                               state_cache=kv_caches[2 * i + 1],
                               cache_index=cache_index)
            new_caches += [kv, state]
        return self._head(x), new_caches

    def num_params(self):
        return sum(math.prod(p.shape) for _, p in self.named_parameters())

    def serving_spec(self):
        """The engine's probe. ``cache_layers``, in the order ``forward``
        takes them: a block's attention keeps paged pools with heads
        (``kv``), its mixer arrays by slot (``state``: name -> (shape a
        slot, dtype))."""
        c = self.config
        dtype = unwrap(self.lm_head.weight).dtype
        return {
            "kind": "decoder",
            "num_layers": c.num_hidden_layers,
            "max_context": c.max_position_embeddings,
            "vocab_size": c.vocab_size,
            "cache_layers": [entry for lyr in self.layers for entry in (
                {"kind": "kv", "kv_heads": c.num_key_value_heads,
                 "head_dim": c.head_dim},
                {"kind": "state", "arrays": lyr.mamba.state_arrays(dtype)})],
        }
