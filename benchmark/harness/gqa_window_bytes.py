"""Bytes the paged-decode kernel (`paged_decode`) has to read in a
decoder whose GQA layers are of two kinds, from the configuration's
shapes. A full layer reads the K and the V row of every context token of
every decoding slot, once a tick (`ctx_tokens` of the engine's
`engine.decode.dispatch` span, plus the token each slot has just
written); a sliding layer reads the rows its window keeps,
min(context + 1, sliding_window) a slot (`win_tokens`), from the slot's
ring. Queries, outputs and block tables are a few KB a slot and are left
out, and a ring or a page is read whole by the kernel but counted here
by the rows the ALGORITHM needs: both make the roofline share smaller,
never larger."""
from __future__ import annotations

# a traced run's spans and kernels, and whether a named kernel ran: the
# same readers the latent cell's metric files come through
from .mla_bytes import has_kernel, traced  # noqa: F401
from .paged_bytes import DTYPE_BYTES, cache_dtype, decode_context_tokens

SLIDING = "sliding_attention"


def kv_bytes_per_token_layer(config: dict) -> int:
    """K + V bytes one token holds on one layer."""
    return (2 * int(config["num_key_value_heads"]) * int(config["head_dim"])
            * DTYPE_BYTES[cache_dtype(config)])


def decode_bytes(config: dict, ctx_tokens: int, win_tokens: int,
                 slots: int) -> int:
    """What `paged_decode` needs for one dispatch over `slots` decoding
    slots, all layers: the whole context on each full layer, the window
    on each sliding one."""
    kinds = list(config["layer_types"])
    n_sliding = sum(k == SLIDING for k in kinds)
    return kv_bytes_per_token_layer(config) * (
        (len(kinds) - n_sliding) * decode_context_tokens(ctx_tokens, slots)
        + n_sliding * int(win_tokens))
