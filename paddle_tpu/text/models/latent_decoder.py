"""The decoder the latent-attention models share: pre-norm blocks of an
attention and a dense or expert FFN, an embedding, a final norm and an
untied head, with the call signature the serving engine uses for
LlamaForCausalLM (``kv_caches`` / ``cache_index``).

A model gives what differs as two settings of its block class,
`LatentDecoderLayer.attention(config, kind)` (the latent block of
``mla.py`` with the model's arguments) and `LatentDecoderLayer.gate(config)`
(the expert layer's router, a ``CapacityFreeGate``), and its
configuration's keys: ``hidden_size``, ``intermediate_size``,
``moe_intermediate_size``, ``n_routed_experts`` (the router's width),
``n_routed_experts_held``, ``n_shared_experts``, ``num_experts_per_tok``,
``first_k_dense_replace``, ``expert_share``, ``layer_types``,
``num_hidden_layers``, ``vocab_size``, ``max_position_embeddings``,
``rms_norm_eps``, ``initializer_range`` and ``dtype``.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from ...core.dispatch import unwrap
from ...framework.param_attr import ParamAttr
from ...incubate.distributed.models.moe import MoELayer, moe_tick_stats
from ...nn import functional as F
from ...nn.initializer import Normal
from ...nn.layer.common import Embedding
from ...nn.layer.container import LayerList
from ...nn.layer.layers import Layer, param_dtype
from .llama import LlamaRMSNorm
from .mla import init_linear


class SwiGLU(Layer):
    """SwiGLU FFN: the leading dense layers and the shared expert."""

    def __init__(self, hidden, width, std=0.02):
        super().__init__()
        self.gate_proj = init_linear(hidden, width, std)
        self.up_proj = init_linear(hidden, width, std)
        self.down_proj = init_linear(width, hidden, std)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LatentDecoderLayer(Layer):
    """A pre-norm block: `attention`, then a dense FFN (layers before
    ``first_k_dense_replace``) or the expert layer under `gate`. A model
    subclasses it and gives those two."""

    def __init__(self, config, index: int):
        super().__init__()
        c = config
        self.kind = c.layer_types[index]
        self.input_layernorm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = self.attention(c, self.kind)
        self.post_attention_layernorm = LlamaRMSNorm(c.hidden_size,
                                                     c.rms_norm_eps)
        self.is_moe = index >= c.first_k_dense_replace
        if self.is_moe:
            shared = SwiGLU(
                c.hidden_size, c.moe_intermediate_size * c.n_shared_experts,
                c.initializer_range) if c.n_shared_experts else None
            self.mlp = MoELayer(
                d_model=c.hidden_size, d_hidden=c.moe_intermediate_size,
                num_experts=c.n_routed_experts, gate=self.gate(c),
                activation="swiglu", expert_share=c.expert_share,
                shared_experts=shared)
        else:
            self.mlp = SwiGLU(c.hidden_size, c.intermediate_size,
                              c.initializer_range)

    @staticmethod
    def attention(config, kind):
        """The latent attention of a layer of `kind`."""
        raise NotImplementedError

    @staticmethod
    def gate(config):
        """The expert layers' router."""
        raise NotImplementedError

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


class LatentDecoderForCausalLM(Layer):
    """The decoder; a layer is a `block` (a `LatentDecoderLayer`
    subclass, set by the model)."""

    block = None

    def __init__(self, config):
        super().__init__()
        self.config = config
        c = config
        # every parameter is created in config.dtype: at the published
        # widths the model does not fit the chip in float32 first
        with param_dtype(c.dtype):
            self.embed_tokens = Embedding(
                c.vocab_size, c.hidden_size, weight_attr=ParamAttr(
                    initializer=Normal(0.0, c.initializer_range)))
            self.layers = LayerList([self.block(c, i)
                                     for i in range(c.num_hidden_layers)])
            self.norm = LlamaRMSNorm(c.hidden_size, c.rms_norm_eps)
            self.lm_head = init_linear(c.hidden_size, c.vocab_size,
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
        heads) and its window; ``tick_stats`` names what the engine reads
        of what ``serving_tick_stats()`` returns after a forward (the
        groups the live tokens' picks reached, and the tokens, where the
        router has groups)."""
        c = self.config
        grouped = any(lyr.mlp.gate.n_group for lyr in self.layers
                      if lyr.is_moe)
        return {
            "kind": "decoder",
            "num_layers": c.num_hidden_layers,
            "max_context": c.max_position_embeddings,
            "vocab_size": c.vocab_size,
            "cache_layers": [
                {"kind": "latent", "rows": lyr.self_attn.cache_rows(),
                 "window": lyr.self_attn.window}
                for lyr in self.layers],
            "tick_stats": ("serving.moe.picks_held",
                           "serving.moe.picks_total",
                           "serving.moe.experts_touched",
                           "serving.moe.layer_ticks",
                           "serving.moe.slabs") + ((
                               "serving.moe.groups_reached",
                               "serving.moe.tokens_routed") if grouped
                               else ()),
            "moe": {"num_experts": c.n_routed_experts,
                    "held": c.n_routed_experts_held,
                    "top_k": c.num_experts_per_tok,
                    "d_model": c.hidden_size,
                    "d_hidden": c.moe_intermediate_size,
                    "dispatch_mode": "ragged"},
        }

    def serving_tick_stats(self):
        """The expert layers' counts of the last forward by name, every
        name ``tick_stats`` lists (`moe_tick_stats`: traced inside a
        compiled step)."""
        return moe_tick_stats([lyr.mlp for lyr in self.layers
                               if lyr.is_moe])
