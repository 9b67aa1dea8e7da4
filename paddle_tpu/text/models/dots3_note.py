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

The latent block is `mla.py`'s ``LatentAttention`` with these settings
(`Dots3LatentAttention`) and the decoder `latent_decoder.py`'s, both
shared with DeepSeek-V2. Serving
(docs/SERVING.md "Model polymorphism"): ``serving_spec()`` gives the
paged cache PER LAYER, one vector a token: ``[c_kv ; k_rope]`` padded to
whole 128-lane tiles (576 -> 640 on a full layer, 1088 -> 1152 on a
sliding one), plus the indexer's key (128) in a pool of its own on full
layers. Prefill expands the gathered rows (on the chip a full layer's
blocks yield their masks to ONE ``mla_flash_prefill`` call, a sliding
block scores its window's band); decode absorbs ``W_kvb`` and runs
``paged_mla_decode`` on the latent rows: full layers under the indexer's
mask, sliding layers over the pages that hold the last
``sliding_window_size`` positions and no others.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

from ...incubate.distributed.models.moe import SigmoidTopKGate
from .latent_decoder import LatentDecoderForCausalLM, LatentDecoderLayer
from .mla import Indexer, LatentAttention, rope
from .mla import topk_mask  # noqa: F401 -- the tests import it from here

FULL, SLIDING = "full_attention", "sliding_attention"


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


class Dots3LatentAttention(LatentAttention):
    """The shared latent block (`mla.py`) with dots3's settings for a
    layer of `kind`: rotate-half RoPE at the kind's theta, softmax scale
    ``1 / sqrt(nope + rope)``, the lora rescale, the head-wise gate; a
    full layer the sparse indexer, a sliding one the window."""

    def __init__(self, config: Dots3NoteConfig, kind: str):
        c, full = config, kind == FULL
        hidden = c.hidden_size
        q_rank = c.q_lora_rank if full else c.swa_q_lora_rank
        kv_rank = c.kv_lora_rank if full else c.swa_kv_lora_rank
        d_nope = c.qk_nope_head_dim if full else c.swa_qk_nope_head_dim
        d_rope = c.qk_rope_head_dim if full else c.swa_qk_rope_head_dim
        rescale = c.apply_mla_qkv_lora_rescale
        super().__init__(
            hidden, c.num_attention_heads if full
            else c.swa_num_attention_heads, q_rank, kv_rank, d_nope, d_rope,
            c.v_head_dim if full else c.swa_v_head_dim,
            rope=functools.partial(
                rope, theta=c.rope_theta if full else c.swa_rope_theta),
            scale=1.0 / math.sqrt(d_nope + d_rope), eps=c.rms_norm_eps,
            std=c.initializer_range,
            q_rescale=math.sqrt(hidden / q_rank) if rescale else None,
            kv_rescale=math.sqrt(hidden / kv_rank) if rescale else None,
            gate=True, window=None if full else int(c.sliding_window_size),
            indexer=Indexer(c.index_n_heads, c.index_head_dim,
                            int(c.index_topk)) if full else None,
            q_block=c.prefill_query_block, key_block=c.prefill_key_block)
        self.kind = kind


class Dots3DecoderLayer(LatentDecoderLayer):
    """The latent decoder's pre-norm block with dots3's attention and
    router."""

    @staticmethod
    def attention(config, kind):
        return Dots3LatentAttention(config, kind)

    @staticmethod
    def gate(config):
        c = config
        return SigmoidTopKGate(c.n_routed_experts, c.num_experts_per_tok,
                               c.norm_topk_prob, c.routed_scaling_factor)


class Dots3NoteForCausalLM(LatentDecoderForCausalLM):
    """The decoder (`latent_decoder.py`) with dots3's blocks."""

    block = Dots3DecoderLayer

    def serving_spec(self):
        """The latent decoder's probe; ``window`` and ``index_topk`` are
        what the engine's span arguments and the window gauge are
        computed from."""
        c = self.config
        return dict(super().serving_spec(), index_topk=c.index_topk,
                    window=c.sliding_window_size)
