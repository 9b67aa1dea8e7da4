"""Bytes the paged-decode kernel (`paged_decode`) has to read in a
decoder whose EVERY block holds a paged GQA cache of `head_dim` the
configuration gives (not hidden_size / heads, which `paged_bytes.py`
assumes): the K and the V row of every context token of every decoding
slot, once a tick a block (`ctx_tokens` of the engine's
`engine.decode.dispatch` span, plus the token each slot has just
written). Queries, outputs and block tables are a few KB a slot and are
left out, and a page is read whole by the kernel but counted here by the
rows the ALGORITHM needs: both make the roofline share smaller, never
larger."""
from __future__ import annotations

# a traced run's spans and kernels, and whether a named kernel ran: the
# same readers the latent cell's metric files come through
from .mla_bytes import has_kernel, traced  # noqa: F401
from .paged_bytes import DTYPE_BYTES, cache_dtype, decode_context_tokens


def kv_bytes_per_token(config: dict) -> int:
    """K + V bytes one context token holds over all blocks."""
    return (2 * int(config["num_hidden_layers"])
            * int(config["num_key_value_heads"]) * int(config["head_dim"])
            * DTYPE_BYTES[cache_dtype(config)])


def decode_bytes(config: dict, ctx_tokens: int, slots: int) -> int:
    """What `paged_decode` needs for one dispatch over `slots` decoding
    slots, all blocks."""
    return kv_bytes_per_token(config) * decode_context_tokens(ctx_tokens,
                                                              slots)
