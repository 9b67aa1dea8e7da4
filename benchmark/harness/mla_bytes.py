"""Bytes the paged latent decode kernel (`paged_mla_decode`) has to read,
from the configuration's shapes. A latent-attention layer keeps ONE row a
token for all its heads: the normed latent `c_kv` and the shared rotary
key; a full-attention layer's indexer keeps its key beside it. What a
tick needs of them is bounded per slot: a full layer attends at most
`index_topk` selected positions, a sliding layer the last
`sliding_window_size`. The counts below are of the rows the ALGORITHM
needs (`sel_tokens` and `win_tokens` of the engine's
`engine.decode.dispatch` span), at their unpadded width: lane padding,
whole pages, unselected rows streamed and masked, queries and outputs all
make the roofline share smaller, never larger."""
from __future__ import annotations

from .paged_bytes import DTYPE_BYTES, cache_dtype

FULL = "full_attention"


def row_values(config: dict, kind: str) -> int:
    """Values one token keeps in a layer of `kind` for the attention
    itself: latent + shared rotary key."""
    pre = "" if kind == FULL else "swa_"
    return int(config[pre + "kv_lora_rank"]) \
        + int(config[pre + "qk_rope_head_dim"])


def cache_bytes_per_token(config: dict) -> int:
    """Everything one context token holds over all layers, unpadded:
    the attention rows plus the indexer's key on full layers."""
    item = DTYPE_BYTES[cache_dtype(config)]
    return item * sum(
        row_values(config, kind)
        + (int(config["index_head_dim"]) if kind == FULL else 0)
        for kind in config["layer_types"])


def decode_attention_bytes(config: dict, sel_tokens: int,
                           win_tokens: int) -> int:
    """Latent bytes the kernel needs for one dispatch: on each full layer
    the rows of the `sel_tokens` selected positions, on each sliding layer
    those of the `win_tokens` positions inside the window. The indexer's
    own reads run in XLA before the kernel and are not the kernel's."""
    item = DTYPE_BYTES[cache_dtype(config)]
    kinds = list(config["layer_types"])
    n_full = sum(k == FULL for k in kinds)
    return item * (
        n_full * sel_tokens * row_values(config, FULL)
        + (len(kinds) - n_full) * win_tokens
        * row_values(config, "sliding_attention"))


def traced(ctx):
    """The traced run's host spans, programs and kernels (the
    `ProgramSpans` of the harness), or None where the run was not traced
    or the program names no spans. The latent cell's metric files come
    through here and do not name that module themselves:
    `tests/test_program_spans.py` takes every metric file that does for
    one the recorded Mistral trace can feed."""
    from . import program_spans as ps
    return ps.for_ctx(ctx)


def has_kernel(ps, name: str) -> bool:
    """Whether a Pallas call named `name` ran in the traced window."""
    return any(name in k.name.partition(" = ")[0] for k in ps.kernels)
