"""The one way the runners build the decoder from a configuration file."""
from __future__ import annotations


def build_llama(config: dict, seed: int, **settings):
    """(LlamaConfig, LlamaForCausalLM) at the sizes at the top level of
    `config`, weights from `seed` as the program's initialisers make
    them; `settings` are the remaining LlamaConfig fields a runner sets."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    sizes = ("vocab_size", "hidden_size", "intermediate_size",
             "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "max_position_embeddings",
             "rms_norm_eps", "tie_word_embeddings", "sliding_window")
    cfg = LlamaConfig(**{k: config[k] for k in sizes}, **settings)
    paddle.seed(seed % (2 ** 31 - 1))
    return cfg, LlamaForCausalLM(cfg)
