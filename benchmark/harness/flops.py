"""Operations an algorithm needs, from shapes alone. Convention: one
multiply-add is 2 FLOPs; what a kernel recomputes for its own
convenience is not counted, so a share of peak built on these can only
read low."""
from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Weights that take part in a matrix multiplication for every
    token: the decoder layers' projections and the output head. The
    embedding is a lookup and the norms are elementwise: neither counts."""
    h = model["hidden_size"]
    head_dim = model.get("head_dim") or h // model["num_attention_heads"]
    q = model["num_attention_heads"] * head_dim
    kv = model["num_key_value_heads"] * head_dim
    layer = h * q + 2 * h * kv + q * h + 3 * h * model["intermediate_size"]
    return model["num_hidden_layers"] * layer + h * model["vocab_size"]


def mean_keys_per_query(seq: int, window=None) -> float:
    """Keys a query attends to under a causal mask cut to `window`
    (0 <= q_pos - k_pos < window), averaged over the `seq` queries."""
    w = seq if window is None else min(int(window), seq)
    # queries 0..w-1 see 1..w keys, the remaining seq-w see w each
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def _attn_matmul_flops(model: dict, tokens: int, seq: int) -> float:
    """FLOPs of ONE attention-core matmul (QK^T or PV alike) over all
    heads and layers for `tokens` query tokens in sequences of `seq`."""
    head_dim = model.get("head_dim") or (
        model["hidden_size"] // model["num_attention_heads"])
    keys = mean_keys_per_query(seq, model.get("sliding_window"))
    return (2.0 * keys * head_dim * model["num_attention_heads"]
            * model["num_hidden_layers"] * tokens)


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward of one token: 6 per matmul weight, plus the
    attention core (2 matmuls forward, 4 backward). Recomputation — the
    flash backward's second look at the scores, activation
    checkpointing — is not work the model needs and is not counted."""
    return 6.0 * matmul_params(model) + 6 * _attn_matmul_flops(model, 1, seq)


def flash_flops_per_step(model: dict, batch: int, seq: int) -> float:
    """What the flash forward and backward kernels must compute in one
    training step: QK^T and PV forward; dV, dP, dQ, dK backward; and one
    recomputation of the scores, without which no flash backward exists
    (7 matmuls, the FlashAttention-2 count). The repo's two backward
    kernels each rebuild scores and dP (9 matmuls run); the two extra
    are the implementation's, not the algorithm's."""
    return 7 * _attn_matmul_flops(model, batch * seq, seq)
