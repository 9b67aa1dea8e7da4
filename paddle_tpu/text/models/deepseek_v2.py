"""DeepSeek-V2: latent attention on every layer, softmax experts routed
within the best groups.

Published configuration: deepseek-ai/DeepSeek-V2 ``config.json``
(model_type ``deepseek_v2``). The field names of ``DeepseekV2Config`` are
its keys. What a layer computes, with ``u`` the RMS-normed layer input
(eps ``rms_norm_eps``):

* attention: the latent block of ``mla.py`` with no indexer, no gate, no
  window and no rescale: ``c_q = RMSNorm(u W_qa)`` (``q_lora_rank``),
  ``q = c_q W_qb`` -> H x (128 nope + 64 rope); ``[c_kv ; k_r] = u W_kva``,
  ``c_kv = RMSNorm(c_kv)``, ``k_rope = RoPE(k_r)``, ``[k_nope ; v] = c_kv
  W_kvb``; causal over every earlier position. The rope dims are
  published interleaved: each is rotated in pairs (2i, 2i + 1) (the
  published code moves the even entries to the first half and the odd
  ones to the second, then rotates halves; `mla.rope(interleaved=True)`
  does the same), at YaRN's frequencies over ``rope_theta``
  (``rope_scaling``, `mla.yarn`), and ``softmax_scale = (nope +
  rope)^-0.5 * mscale(factor, mscale_all_dim)^2``.
* FFN: layer ``i < first_k_dense_replace`` a SwiGLU of
  ``intermediate_size``; the others ``MoELayer`` with
  ``GroupLimitedGate``: ``p = softmax(h W_r)`` over ``n_routed_experts``
  in float32, the ``topk_group`` best of ``n_group`` groups by their
  highest p, the top ``num_experts_per_tok`` of p inside them, weights
  the chosen p times ``routed_scaling_factor`` (not normalised while
  ``norm_topk_prob`` is false), plus ``n_shared_experts`` shared experts
  as one SwiGLU of that many times ``moe_intermediate_size``.
  ``expert_share=(index, of)`` holds one chip's share of the experts,
  whole groups. ``seq_aux`` is a training loss; serving leaves it out.

Serving: ``serving_spec()`` gives every layer one latent pool of one
``[c_kv ; k_rope]`` row a token, padded to 640; the engine runs the
block's chunked prefill (`mla_flash_prefill` under a causal mask on the
chip) and its absorbed decode (`paged_mla_decode` with no mask and no
window), as for dots3-note's full layers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Tuple

from ...incubate.distributed.models.moe import GroupLimitedGate
from .latent_decoder import LatentDecoderForCausalLM, LatentDecoderLayer
from .mla import LatentAttention, yarn_rope


def _yarn():
    return {"beta_fast": 32, "beta_slow": 1, "factor": 40,
            "mscale": 0.707, "mscale_all_dim": 0.707,
            "original_max_position_embeddings": 4096, "type": "yarn"}


@dataclass
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 60
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    attention_bias: bool = False
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=_yarn)
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_routed_experts: int = 160
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    topk_method: str = "group_limited_greedy"
    scoring_func: str = "softmax"
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 16.0
    seq_aux: bool = True
    # not in the published file
    expert_share: Tuple[int, int] = (0, 1)    # (index, of): experts held
    dtype: str = "float32"                    # honoured at construction
    initializer_range: float = 0.02           # std of every matrix's init
    prefill_query_block: int = 256            # queries a block of scores
    prefill_key_block: int = 1024             # a prefill chunk reads its
    #                                           cache in whole such blocks

    def __post_init__(self):
        for name, want in (("scoring_func", "softmax"),
                           ("topk_method", "group_limited_greedy"),
                           ("hidden_act", "silu"), ("attention_bias", False),
                           ("moe_layer_freq", 1)):
            if getattr(self, name) != want:
                raise ValueError(
                    f"{name}={getattr(self, name)!r}: only {want!r} is "
                    f"implemented")
        if self.q_lora_rank is None:
            raise ValueError("q_lora_rank=None: a query without its low-rank "
                             "projection is not implemented")
        kind = (self.rope_scaling or {}).get("type")
        if kind != "yarn":
            raise ValueError(f"rope_scaling type {kind!r}: only 'yarn' is "
                             f"implemented")

    @property
    def layer_types(self):
        return ("full_attention",) * self.num_hidden_layers

    @property
    def n_routed_experts_held(self) -> int:
        return self.n_routed_experts // self.expert_share[1]

    @staticmethod
    def tiny(**over):
        """The CPU tests' size: the leading dense layer and two expert
        layers, 16 experts in 8 groups of 2, top-4 inside the best 3
        groups, YaRN at a factor that bites at 40 positions."""
        kw = dict(
            vocab_size=96, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3,
            max_position_embeddings=256, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_scaling=dict(_yarn(), original_max_position_embeddings=16,
                              factor=4),
            n_routed_experts=16, num_experts_per_tok=4, n_group=8,
            topk_group=3, prefill_query_block=8, prefill_key_block=16)
        kw.update(over)
        return DeepseekV2Config(**kw)


def latent_attention(config: DeepseekV2Config) -> LatentAttention:
    """The shared latent block with DeepSeek-V2's settings."""
    c = config
    d_q = c.qk_nope_head_dim + c.qk_rope_head_dim
    rotary, factor = yarn_rope(c.rope_scaling, c.qk_rope_head_dim,
                               float(c.rope_theta))
    return LatentAttention(
        c.hidden_size, c.num_attention_heads, c.q_lora_rank, c.kv_lora_rank,
        c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, rope=rotary,
        scale=factor / math.sqrt(d_q), eps=c.rms_norm_eps,
        std=c.initializer_range, q_block=c.prefill_query_block,
        key_block=c.prefill_key_block)


class DeepseekV2DecoderLayer(LatentDecoderLayer):
    """The latent decoder's pre-norm block with DeepSeek-V2's attention
    and router."""

    @staticmethod
    def attention(config, kind):
        return latent_attention(config)

    @staticmethod
    def gate(config):
        c = config
        return GroupLimitedGate(c.n_routed_experts, c.num_experts_per_tok,
                                c.n_group, c.topk_group, c.norm_topk_prob,
                                c.routed_scaling_factor)


class DeepseekV2ForCausalLM(LatentDecoderForCausalLM):
    """The decoder (`latent_decoder.py`) with DeepSeek-V2's blocks: one
    latent pool a layer, one row a token, no selection and no window;
    ``tick_stats`` adds the groups the live tokens' picks reached, and
    the tokens."""

    block = DeepseekV2DecoderLayer
