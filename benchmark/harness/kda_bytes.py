"""Bytes the KDA decode kernel (`kda_decode`) has to move, from the
configuration's shapes. A linear-attention layer keeps one float32
matrix `S` [head_dim, head_dim] a head and slot; a decode tick reads it
and writes it, once each, for every slot whose state the dispatched
program updates (`state_slots` of the engine's `engine.decode.dispatch`
span), on every KDA layer. Beside it the kernel reads a head's q, k,
log-decay, v and beta rows and writes its output row, float32. The
convolution's tail is read and written by XLA ops around the kernel and
is not the kernel's. The counts are of what the ALGORITHM needs: a slot
that is not decoding is copied through by the kernel that ships and
counted here as nothing, which makes the roofline share smaller, never
larger."""
from __future__ import annotations

# a traced run's spans and kernels, and whether a named kernel ran: the
# same readers the latent cell's metric files come through
from .mla_bytes import has_kernel, traced  # noqa: F401

F32 = 4


def kda_layers(config: dict) -> int:
    """Layers of the configuration that keep a state."""
    gqa = set(int(i) for i in config["gqa_layers"])
    return sum(i not in gqa for i in range(int(config["num_hidden_layers"])))


def state_bytes_per_slot_layer(config: dict) -> int:
    """One slot's `S` on one layer."""
    lin = config["linear_attn_config"]
    return F32 * int(lin["num_heads"]) * int(lin["head_dim"]) ** 2


def decode_bytes(config: dict, state_slots: int) -> int:
    """What `kda_decode` moves for one dispatch over `state_slots`
    decoding slots, all KDA layers: S read + S written, and the six
    vectors of head_dim a head (q, k, decay, v, beta's row in, o out)."""
    lin = config["linear_attn_config"]
    rows = 6 * F32 * int(lin["num_heads"]) * int(lin["head_dim"])
    return kda_layers(config) * int(state_slots) * (
        2 * state_bytes_per_slot_layer(config) + rows)
