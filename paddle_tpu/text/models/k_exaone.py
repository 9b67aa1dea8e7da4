"""K-EXAONE: a decoder of softmax GQA layers of two kinds, three with a
128-token sliding window to each full one, a leading dense FFN and then
sigmoid-routed experts with a shared one.

Published configuration: LGAI-EXAONE/K-EXAONE-236B-A23B ``config.json``
(model_type ``exaone_moe``). The field names of ``KExaoneConfig`` are
its keys. Pre-norm residual blocks, ``x += Attn(RMSNorm(x)); x +=
FFN(RMSNorm(x))``, final RMSNorm, untied head. With ``u`` the normed
layer input, H query heads and G key/value heads of d:

* both kinds: ``q = u W_q`` (H x d), ``k, v = u W_k, u W_v`` (G x d), no
  biases; ``q, k <- RMSNorm_d(q), RMSNorm_d(k)`` a head, with a learned
  weight of d each; scores ``q k^T / sqrt(d)``, causal softmax, ``W_o``.
* sliding layer (``layer_types[i] == "sliding_attention"``): RoPE
  (rotate-half, ``rope_parameters.rope_theta``, all d dims) on q and k
  after the norm; a query keeps the keys at ``0 <= t - s <
  sliding_window``.
* full layer: NO position encoding, every key ``s <= t``.
* FFN: ``mlp_layer_types[i]`` "dense" a SwiGLU of ``intermediate_size``,
  "sparse" a ``MoELayer`` with a sigmoid top-k gate over ``num_experts``
  SwiGLU experts of ``moe_intermediate_size`` (picked weights normalised,
  times ``routed_scaling_factor``) plus ``num_shared_experts`` shared;
  ``expert_share=(index, of)`` holds one chip's share.
* multi-token prediction (built when ``num_nextn_predict_layers`` is 1;
  DeepSeek-V3's shape): ``h' = W_p [RMSNorm(h_t) ; RMSNorm(E(x_{t+1}))]``
  with ``h_t`` the last layer's output before the final norm, one full
  attention expert layer, an RMSNorm, the shared head: logits for
  ``x_{t+2}`` (``mtp_logits``). The serving path does not run it.

Serving (docs/SERVING.md "Model polymorphism"): ``serving_spec()`` gives
the cache PER LAYER. A full layer is ``kind: "kv"``: the paged (k, v)
pools with heads, as ``SolarGQAttention``'s. A sliding layer is ``kind:
"state"``: a RING a slot, ``k`` and ``v`` [G, sliding_window, d] in the
cache's dtype, whatever the context's length: position p's key (after
norm and RoPE) and value lie on row ``p % sliding_window``, which is all
a later query can read of them. The ring has the layout of one page of
the paged pools, so a one-token step is ``paged_decode`` itself on the
rings as a pool of one page a slot (block table ``arange(slots)``,
context ``min(p + 1, sliding_window)``; the order of keys inside a
softmax does not matter, so no window mask). A chunk scores each block
of queries against the band of keys its window can reach, the chunk's
own behind the rows the ring holds from before it, then leaves its last
``sliding_window`` keys and values in the ring. The engine hands such a
layer ``(k, v, slots, n_valid)`` as it does a linear-attention layer's
state (``SolarKDAttention``): rows of a lane with ``n_valid`` 0 come
back bit-identical. A sequence at position 0 needs no reset: the
context length says which rows are its own.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.dispatch import unwrap, wrap
from ...framework.param_attr import ParamAttr
from ...incubate.distributed.models.moe import MoELayer, SigmoidTopKGate
from ...kernels import paged_attention as paged
from ...nn.initializer import Normal
from ...nn.layer.common import Embedding
from ...nn.layer.container import LayerList
from ...nn.layer.layers import Layer, param_dtype
from .dots3_note import (Dots3MLP, Dots3NoteForCausalLM, _init_linear,
                         _rope)
from .gqa import gqa_attend, paged_decode_or_gather, paged_gqa
from .llama import LlamaRMSNorm

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


def _rope_parameters():
    return {"rope_theta": 1000000, "rope_type": "default"}


@dataclass
class KExaoneConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    hidden_act: str = "silu"
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_parameters: dict = field(default_factory=_rope_parameters)
    tie_word_embeddings: bool = False
    sliding_window: int = 128
    sliding_window_pattern: str = "LLLG"
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_windows: Optional[Tuple[int, ...]] = None
    first_k_dense_replace: int = 1
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    num_nextn_predict_layers: int = 1
    mtp_layer_types: Tuple[str, ...] = (FULL,)
    mtp_sliding_windows: Tuple[int, ...] = (0,)
    # not in the published file
    expert_share: Tuple[int, int] = (0, 1)    # (index, of): experts held
    dtype: str = "float32"                    # honoured at construction
    initializer_range: float = 0.02           # std of every matrix's init
    prefill_query_block: int = 256            # queries a block of scores

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            pat = self.sliding_window_pattern
            self.layer_types = [SLIDING if pat[i % len(pat)] == "L" else FULL
                                for i in range(n)]
        if self.mlp_layer_types is None:
            self.mlp_layer_types = [
                DENSE if i < self.first_k_dense_replace else SPARSE
                for i in range(n)]
        self.layer_types = tuple(self.layer_types)
        self.mlp_layer_types = tuple(self.mlp_layer_types)
        self.mtp_layer_types = tuple(self.mtp_layer_types)
        self.mtp_sliding_windows = tuple(self.mtp_sliding_windows)
        windows = tuple(self.sliding_window if k == SLIDING else 0
                        for k in self.layer_types)
        if self.sliding_windows is None:
            self.sliding_windows = windows
        self.sliding_windows = tuple(int(w) for w in self.sliding_windows)
        for name, known in (("layer_types", (SLIDING, FULL)),
                            ("mlp_layer_types", (DENSE, SPARSE))):
            got = getattr(self, name)
            if len(got) != n or any(k not in known for k in got):
                raise ValueError(
                    f"{name} {got}: one of {known} for each of the {n} "
                    f"layers")
        if self.sliding_windows != windows:
            raise ValueError(
                f"sliding_windows {self.sliding_windows} is not "
                f"sliding_window {self.sliding_window} on the sliding "
                f"layers of {self.layer_types} and 0 on the full ones")
        for name, want in (("scoring_func", "sigmoid"), ("n_group", 1),
                           ("topk_group", 1), ("hidden_act", "silu"),
                           ("tie_word_embeddings", False),
                           ("mtp_layer_types", (FULL,))):
            if getattr(self, name) != want:
                raise ValueError(
                    f"{name}={getattr(self, name)!r}: only {want!r} is "
                    f"implemented")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(
                f"num_nextn_predict_layers={self.num_nextn_predict_layers}: "
                f"0 or 1 prediction module")

    @property
    def num_experts_held(self) -> int:
        return self.num_experts // self.expert_share[1]

    @staticmethod
    def tiny(**over):
        """The CPU tests' size: (sliding, sliding, sliding, full, sliding)
        with a window of 8, the first layer dense, 8 experts top-2 with
        one shared, 4 heads of 16 (2 KV heads), the prediction module."""
        kw = dict(
            vocab_size=96, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=5,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            max_position_embeddings=256, sliding_window=8,
            num_experts=8, num_experts_per_tok=2, prefill_query_block=8)
        kw.update(over)
        return KExaoneConfig(**kw)


def ring_gqa(q, k, v, ring, pos0, q_block, window):
    """Sliding-window GQA of a step or a chunk on the per-slot rings:
    write, then attend (module docstring). q [b, s, H, d]; k, v
    [b, s, G, d]; ring (k ring, v ring [slots, G, window, d], slots,
    n_valid); pos0 [b] each sequence's first position. Returns
    ([b, s, H, d] float32, the new rings)."""
    kr, vr, slots, n_valid = ring
    b, s = q.shape[:2]
    W = int(window)
    n_valid = n_valid.astype(jnp.int32)
    if slots is None:
        # the decode program: one token a slot, row i is slot i; a lane
        # that is not decoding writes past the rings (dropped) and reads
        # a context of 0
        alive = n_valid > 0
        lane = jnp.arange(b, dtype=jnp.int32)
        kr, vr = paged.paged_write_arrays(
            k, v, kr, vr, jnp.where(alive, lane, b)[:, None], pos0 % W)
        out = paged_decode_or_gather(
            q[:, 0], kr, vr, lane[:, None],
            jnp.where(alive, jnp.minimum(pos0 + 1, W), 0))
        return out[:, None].astype(jnp.float32), (kr, vr)
    # a chunk of each sequence: the W positions before it as the slot's
    # ring holds them (none that count at position 0), then its own
    order = (pos0[:, None] + jnp.arange(W, dtype=jnp.int32)[None]) % W
    kk, vv = (jnp.concatenate([
        jnp.stack([jnp.take(jax.lax.dynamic_index_in_dim(
            x, slots[r], 0, keepdims=False), order[r], axis=1)
            for r in range(b)]).swapaxes(1, 2),
        new.astype(x.dtype)], 1) for x, new in ((kr, k), (vr, v)))
    q_pos = pos0[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    k_pos = pos0[:, None] - W + jnp.arange(W + s, dtype=jnp.int32)[None]
    out = gqa_attend(q, kk, vv, q_pos, k_pos, q_block, window=W)
    # the W positions up to the chunk's last REAL token, each back on
    # the row its position names
    end = pos0 + n_valid
    back = (jnp.arange(W, dtype=jnp.int32)[None] - end[:, None]) % W

    def put(x, xx):
        for r in range(b):
            tail = jax.lax.dynamic_slice_in_dim(xx[r], n_valid[r], W, 0)
            rows = jnp.take(tail, back[r], axis=0).swapaxes(0, 1)
            x = jax.lax.dynamic_update_slice_in_dim(x, rows[None],
                                                    slots[r], 0)
        return x

    return out, (put(kr, kk), put(vr, vv))


class KExaoneAttention(Layer):
    """One GQA block of either kind (module docstring)."""

    def __init__(self, config: KExaoneConfig, kind: str):
        super().__init__()
        c = config
        self.window = int(c.sliding_window) if kind == SLIDING else None
        self.heads, self.kv_heads = c.num_attention_heads, \
            c.num_key_value_heads
        self.d = c.head_dim
        self.theta = float(c.rope_parameters["rope_theta"])
        self.q_block = int(c.prefill_query_block)
        std = c.initializer_range
        self.q_proj = _init_linear(c.hidden_size, self.heads * self.d, std)
        self.k_proj = _init_linear(c.hidden_size, self.kv_heads * self.d,
                                   std)
        self.v_proj = _init_linear(c.hidden_size, self.kv_heads * self.d,
                                   std)
        self.q_norm = LlamaRMSNorm(self.d, c.rms_norm_eps)
        self.k_norm = LlamaRMSNorm(self.d, c.rms_norm_eps)
        self.o_proj = _init_linear(self.heads * self.d, c.hidden_size, std)

    def ring_arrays(self):
        """What a slot keeps of a sliding layer: name -> (shape, dtype),
        the dtype left to the engine's cache."""
        shape = [self.kv_heads, self.window, self.d]
        return {"k": (shape, None), "v": (shape, None)}

    def forward(self, u, kv_cache=None, cache_index=None):
        b, s = u.shape[0], u.shape[1]
        H, G, d = self.heads, self.kv_heads, self.d
        q = unwrap(self.q_norm(wrap(
            unwrap(self.q_proj(u)).reshape(b, s, H, d))))
        k = unwrap(self.k_norm(wrap(
            unwrap(self.k_proj(u)).reshape(b, s, G, d))))
        v = unwrap(self.v_proj(u)).reshape(b, s, G, d)
        pos0 = jnp.zeros((b,), jnp.int32) if kv_cache is None else \
            jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(
                unwrap(cache_index), jnp.int32)), (b,))
        pos = pos0[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        new_cache = None
        if self.window is not None:
            q, k = _rope(q, pos, self.theta), _rope(k, pos, self.theta)
        if kv_cache is not None and self.window is not None:
            out, new_cache = ring_gqa(q, k, v, kv_cache, pos0,
                                      self.q_block, self.window)
        elif kv_cache is not None:
            out, new_cache = paged_gqa(q, k, v, kv_cache, pos0,
                                       self.q_block)
        elif self.window is not None:
            # no cache: nothing lies before the sequence
            front = jnp.zeros((b, self.window, G, d), k.dtype)
            out = gqa_attend(
                q, jnp.concatenate([front, k], 1),
                jnp.concatenate([front, v], 1), pos,
                pos0[:, None] + jnp.arange(-self.window, s,
                                           dtype=jnp.int32)[None],
                self.q_block, window=self.window)
        else:
            out = gqa_attend(q, k, v, pos, pos, self.q_block)
        out = self.o_proj(wrap(out.reshape(b, s, H * d).astype(q.dtype)))
        return out if kv_cache is None else (out, new_cache)


class KExaoneDecoderLayer(Layer):
    def __init__(self, config: KExaoneConfig, kind: str, mlp_kind: str):
        super().__init__()
        c = config
        self.kind = kind
        self.is_moe = mlp_kind == SPARSE
        self.input_layernorm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = KExaoneAttention(c, kind)
        self.post_attention_layernorm = LlamaRMSNorm(c.hidden_size,
                                                     c.rms_norm_eps)
        if self.is_moe:
            shared = Dots3MLP(
                c.hidden_size,
                c.moe_intermediate_size * c.num_shared_experts,
                c.initializer_range) if c.num_shared_experts else None
            self.mlp = MoELayer(
                d_model=c.hidden_size, d_hidden=c.moe_intermediate_size,
                num_experts=c.num_experts,
                gate=SigmoidTopKGate(c.num_experts, c.num_experts_per_tok,
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


class KExaoneMTP(Layer):
    """The multi-token prediction module (module docstring)."""

    def __init__(self, config: KExaoneConfig):
        super().__init__()
        c = config
        self.hnorm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.enorm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.eh_proj = _init_linear(2 * c.hidden_size, c.hidden_size,
                                    c.initializer_range)
        self.layer = KExaoneDecoderLayer(c, c.mtp_layer_types[0], SPARSE)
        self.norm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)

    def forward(self, h, e):
        """h [b, n, hidden] the trunk's outputs at t, e the embeddings
        of the tokens at t + 1: the hidden states that predict t + 2."""
        x = self.eh_proj(wrap(jnp.concatenate(
            [unwrap(self.hnorm(h)), unwrap(self.enorm(e))], -1)))
        return self.norm(self.layer(x))


class KExaoneForCausalLM(Layer):
    """The decoder, with the call signature the serving engine uses for
    LlamaForCausalLM (``kv_caches`` / ``cache_index``)."""

    def __init__(self, config: KExaoneConfig):
        super().__init__()
        self.config = config
        c = config
        # every parameter is created in config.dtype, one at a time
        # (SolarOpen2ForCausalLM says why)
        with param_dtype(c.dtype, wait=True):
            self.embed_tokens = Embedding(
                c.vocab_size, c.hidden_size, weight_attr=ParamAttr(
                    initializer=Normal(0.0, c.initializer_range)))
            self.layers = LayerList([
                KExaoneDecoderLayer(c, c.layer_types[i], c.mlp_layer_types[i])
                for i in range(c.num_hidden_layers)])
            self.norm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
            self.lm_head = _init_linear(c.hidden_size, c.vocab_size,
                                        c.initializer_range)
            if c.num_nextn_predict_layers:
                self.mtp = KExaoneMTP(c)

    def _trunk(self, input_ids):
        x = self.embed_tokens(input_ids)
        for lyr in self.layers:
            x = lyr(x)
        return x

    def forward(self, input_ids, kv_caches=None, cache_index=None):
        if kv_caches is None:
            return self.lm_head(self.norm(self._trunk(input_ids)))
        x = self.embed_tokens(input_ids)
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

    def mtp_logits(self, input_ids):
        """[b, s - 1, vocab]: row t the prediction module's logits for
        token t + 2, from the trunk's state at t and token t + 1."""
        h = unwrap(self._trunk(input_ids))[:, :-1]
        e = unwrap(self.embed_tokens(input_ids))[:, 1:]
        return self.lm_head(self.mtp(wrap(h), wrap(e)))

    num_params = Dots3NoteForCausalLM.num_params

    def serving_spec(self):
        """The engine's probe. ``cache_layers``: a full layer's paged
        pools with heads (``kv``), a sliding layer's rings by slot
        (``state``; ``window`` says that they slide); ``window`` is what
        the engine's ``win_tokens`` span argument is computed from;
        ``tick_stats`` as Dots3NoteForCausalLM's."""
        c = self.config
        return {
            "kind": "decoder",
            "num_layers": c.num_hidden_layers,
            "max_context": c.max_position_embeddings,
            "vocab_size": c.vocab_size,
            "cache_layers": [
                {"kind": "kv", "kv_heads": c.num_key_value_heads,
                 "head_dim": c.head_dim} if lyr.kind == FULL else
                {"kind": "state", "arrays": lyr.self_attn.ring_arrays(),
                 "window": c.sliding_window}
                for lyr in self.layers],
            "window": c.sliding_window,
            "tick_stats": ("serving.moe.picks_held",
                           "serving.moe.picks_total",
                           "serving.moe.experts_touched",
                           "serving.moe.layer_ticks",
                           "serving.moe.slabs"),
            "moe": {"num_experts": c.num_experts,
                    "held": c.num_experts_held,
                    "top_k": c.num_experts_per_tok,
                    "d_model": c.hidden_size,
                    "d_hidden": c.moe_intermediate_size,
                    "dispatch_mode": "ragged"},
        }

    # [5] int32 in ``tick_stats``' order, summed over the expert layers
    serving_tick_stats = Dots3NoteForCausalLM.serving_tick_stats
