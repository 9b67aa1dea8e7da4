"""Work of the paged latent decode kernel (`paged_mla_decode`) on layers
that attend every row of their context: no sparse selection, no window
(DeepSeek-V2's latent attention on every layer). A layer keeps ONE row a
token for all its heads, `[c_kv ; k_rope]`; a decode step of a slot reads
each of its context rows once and, at H query heads absorbed into the
latent space, scores every row against H queries of `kv_lora_rank +
qk_rope_head_dim` and sums H values of `kv_lora_rank` from it:

    bytes = rows x (kv_lora_rank + qk_rope_head_dim) x dtype bytes
    FLOPs = rows x 2 x H x ((kv_lora_rank + qk_rope_head_dim) + kv_lora_rank)

At 128 heads, 576 + 512 wide: 278.5 kFLOP for 1,152 B a row, 242 FLOP/B,
at the v5e's ridge (197e12 / 819e9 = 240), so the least time is the
LARGER of the two bounds. `rows` = (`ctx_tokens` + `slots`) of the
engine's `engine.decode.dispatch` span (each slot also reads the row it
writes) x the layers. Lane padding (576 -> 640), whole pages, queries
and outputs are left out: the share can only read low."""
from __future__ import annotations

from .paged_bytes import DTYPE_BYTES, cache_dtype, decode_context_tokens


def decode_rows(config: dict, ctx_tokens: int, slots: int) -> int:
    """Latent rows one dispatch reads, over every layer."""
    return decode_context_tokens(ctx_tokens, slots) \
        * int(config["num_hidden_layers"])


def row_bytes(config: dict) -> int:
    return (int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])) \
        * DTYPE_BYTES[cache_dtype(config)]


def row_flops(config: dict) -> int:
    key = int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])
    return 2 * int(config["num_attention_heads"]) \
        * (key + int(config["kv_lora_rank"]))


def least_seconds(config: dict, peak: dict, rows: int, chips: int = 1):
    """The least time the chip could read and score `rows` latent rows:
    the larger of bytes over the HBM peak and FLOPs over the bf16 peak."""
    return rows * max(row_bytes(config) / peak["hbm_bytes_per_s"],
                      row_flops(config) / peak["bf16_flops_per_s"]) / chips
