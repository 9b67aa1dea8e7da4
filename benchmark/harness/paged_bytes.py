"""Bytes the paged-decode kernel has to read, from the configuration's
shapes: the K and the V row of every context token of every slot, in every
layer, once a tick. Queries, outputs and block tables are a few KB a slot
and are left out, and a page is read whole by the kernel but counted here
by the tokens the algorithm needs: both make the roofline share smaller,
never larger."""
from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def cache_dtype(config: dict) -> str:
    """The engine's KV dtype: "auto" follows the serving weights."""
    dtype = config["engine"].get("cache_dtype", "auto")
    return config["serving"]["weight_dtype"] if dtype == "auto" else dtype


def kv_bytes_per_token(config: dict) -> int:
    """K + V bytes one context token holds over all layers."""
    head = config["hidden_size"] // config["num_attention_heads"]
    return (2 * config["num_hidden_layers"] * config["num_key_value_heads"]
            * head * DTYPE_BYTES[cache_dtype(config)])


def decode_context_tokens(ctx_tokens: int, slots: int, ticks: int = 1) -> int:
    """Context tokens the kernel reads for one dispatch: `ctx_tokens` is
    the sum of the slots' cache positions at dispatch (the engine's
    `engine.decode.dispatch` span), and each slot also reads the token it
    writes. A fused dispatch of `ticks` steps reads a context that grows by
    one a step."""
    return ticks * (ctx_tokens + slots) + slots * ticks * (ticks - 1) // 2
