"""Expanded latent attention of a prefill chunk under a dense mask: the
Pallas kernel `mla_flash_prefill` and the XLA formulation of one query
block, `mla_block_xla`.

A full layer of `text/models/dots3_note.py` attends the keys its indexer
selected, one [s, L] mask for all its heads. The XLA formulation scores a
block of queries against every gathered key and leaves the float32
scores [H, qb, L] in HBM between its passes (write, max, exp + sum, the
bfloat16 `p`, the value matmul): 13% of the MXU's peak at 128 heads. The
kernel streams key tiles of one head through VMEM with an online softmax
(as `flash_attention._flash_fwd_kernel`, which it does not share a body
with: that one casts its tiles to float32, takes its mask as row bands
and saves the logsumexp for a backward pass), so the scores never reach
HBM. Forward only: serving prefill is never differentiated.

Precision is the XLA formulation's: operands go to the MXU as they come
(bfloat16 on the chip), scores and softmax in float32, `p` cast to the
operands' dtype before the value product, the division behind it.

The mask's contract: `keep` [b, s, L] int8, nonzero where query t may
attend key j; every query keeps at least one key. A masked key adds
exactly 0, so key tiles past the last one in which any query of a block
keeps a key are NOT VISITED (their rows may hold anything finite): the
trip count of each query block is computed from the mask itself and
handed to the kernel through scalar prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import CARRY_LANES, NEG_INF, _x32_trace

# queries and keys a tile. 256 x 512 float32 scores are the trainer's
# flash tiles (flash_attention.BLOCK_Q / BLOCK_K); halved until they
# divide the chunk and the gathered keys
BLOCK_Q = 256
BLOCK_K = 512
# of the v5e's 128 MiB of VMEM, what the double-buffered blocks of one
# call may take, and what the body's tiles get beside them
_VMEM_BLOCKS = 80 << 20
_VMEM_HEADROOM = 16 << 20


def _lanes(n):
    return -(-n // 128) * 128


def _halved(limit, n):
    b = limit
    while b > 128 and n % b:
        b //= 2
    return b


def mla_prefill_tiles(s, L, dk, dv, dtype):
    """(query tile, key tile, bytes of the blocks) from the static shapes
    alone. One head a grid step, its whole K [L, dk] and V [L, dv] in
    VMEM beside one query tile, its mask rows int8 [query tile, L] and
    the float32 output tile, each double-buffered by the pipeline:

        e.g. bf16, s 2048, L 5120, dk 192 (256 lanes), dv 128:
        K 2 x 2.5 MiB, V 2 x 1.25, mask 2 x 1.25, q + out 0.5 = 10.5 MiB
    """
    bq, bk = _halved(BLOCK_Q, s), _halved(BLOCK_K, L)
    item = jnp.dtype(dtype).itemsize
    need = 2 * (item * (bq * _lanes(dk) + L * (_lanes(dk) + _lanes(dv)))
                + bq * L + 4 * bq * _lanes(dv))
    return bq, bk, need


def mla_prefill_requirements(s, L, dk, dv, dtype):
    """Why `mla_flash_prefill` cannot take these shapes, or None: whole
    tiles of queries and keys, a lane-aligned value width, blocks that
    fit VMEM."""
    problems = []
    for name, n in (("queries", s), ("keys", L), ("value width", dv)):
        if n % 128:
            problems.append(f"{n} {name} are not a multiple of 128")
    need = mla_prefill_tiles(s, L, dk, dv, dtype)[2]
    if need > _VMEM_BLOCKS:
        problems.append(f"the blocks of {L} keys take {need >> 20} MiB of "
                        f"VMEM, more than {_VMEM_BLOCKS >> 20}")
    return "; ".join(problems) if problems else None


def mla_block_xla(q, k, v, keep, scale):
    """One block of queries in XLA: q [b, qb, H, dk], k [b, L, H, dk],
    v [b, L, H, dv], keep [b, qb, L] bool -> float32 [b, qb, H, dv].
    Softmax with the division moved behind the value matmul: every row
    keeps at least one key, so exp(NEG_INF - max) = 0."""
    sc = jnp.einsum("bqhd,bLhd->bhqL", q, k,
                    preferred_element_type=jnp.float32)
    sc = jnp.where(keep[:, None], sc * jnp.float32(scale), NEG_INF)
    p = jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True))
    out = jnp.einsum("bhqL,bLhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out / jnp.moveaxis(jnp.sum(p, axis=-1), 1, 2)[..., None]


def _mla_flash_kernel(trips_ref, q_ref, k_ref, v_ref, keep_ref, o_ref, *,
                      scale, block_k):
    """One (batch row, head, query tile) program. Refs: trips [b, s / bq]
    int32 in SMEM; q [bq, dk], k [L, dk], v [L, dv] of this head, keep
    int8 [bq, L], o float32 [bq, dv]."""
    from jax.experimental import pallas as pl

    q = q_ref[...]
    bq, dv = q.shape[0], v_ref.shape[-1]
    neg_inf = jnp.float32(NEG_INF)

    def body(i, carry):
        m_prev, l_prev, acc_prev = carry
        at = pl.ds(pl.multiple_of(i * block_k, block_k), block_k)
        s = jax.lax.dot_general(
            q, k_ref[at, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, block_k]
        s = jnp.where(keep_ref[:, at].astype(jnp.int32) != 0,
                      s * jnp.float32(scale), neg_inf)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_cur[:, :1])
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_cur = acc_prev * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[at, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_cur, l_cur, acc_cur

    # loop carries are full-lane-width (flash_attention.CARRY_LANES)
    _, l, acc = jax.lax.fori_loop(
        0, trips_ref[pl.program_id(0), pl.program_id(2)], body,
        (jnp.full((bq, CARRY_LANES), neg_inf, jnp.float32),
         jnp.zeros((bq, CARRY_LANES), jnp.float32),
         jnp.zeros((bq, dv), jnp.float32)))
    o_ref[...] = acc / l[:, :1]


def key_tile_trips(keep, bq, bk):
    """[b, s / bq] int32: for each tile of bq queries, the key tiles of
    bk up to and including the last in which any of them keeps a key."""
    b, s, L = keep.shape
    live = (keep.reshape(b, s // bq, bq, L // bk, bk) != 0).any(axis=(2, 4))
    last = jnp.max(jnp.where(live, jnp.arange(L // bk, dtype=jnp.int32), 0),
                   axis=-1)
    return last + 1


# jitted: the full layers of one program, and every branch of their key
# lengths, call it; a shape is traced and lowered to Mosaic once for all
# of them (kernels.moe._ffn_gated_pallas: set-up time, not device time)
@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "tiles"))
def mla_flash_prefill(q, k, v, keep, scale, interpret=False, tiles=None):
    """softmax(scale * q.k | keep).v for every head, heads-major operands:
    q [b, H, s, dk], k [b, H, L, dk], v [b, H, L, dv], keep [b, s, L] int8
    (one mask for all heads) -> float32 [b, s, H * dv], a token's heads
    side by side as the output projection reads them. Grid (b, H, s / bq),
    the head outside the query tiles so that its K and V are fetched
    once; see `mla_prefill_tiles` for the blocks (`tiles` = (query tile,
    key tile) overrides its two, for tools/mla_prefill_bench.py's sweep)
    and the module docstring for the mask's contract."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, H, s, dk = q.shape
    L, dv = v.shape[2], v.shape[3]
    bq, bk, need = mla_prefill_tiles(s, L, dk, dv, q.dtype)
    if tiles is not None:
        bq, bk = tiles
    visited = key_tile_trips(keep, bq, bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, H, s // bq),
        in_specs=[
            pl.BlockSpec((None, None, bq, dk), lambda i, h, j, t: (i, h, j, 0)),
            pl.BlockSpec((None, None, L, dk), lambda i, h, j, t: (i, h, 0, 0)),
            pl.BlockSpec((None, None, L, dv), lambda i, h, j, t: (i, h, 0, 0)),
            pl.BlockSpec((None, bq, L), lambda i, h, j, t: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, dv), lambda i, h, j, t: (i, j, h)),
    )
    item = jnp.dtype(q.dtype).itemsize
    with _x32_trace():
        return pl.pallas_call(
            functools.partial(_mla_flash_kernel, scale=scale, block_k=bk),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, s, H * dv), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=need + _VMEM_HEADROOM),
            cost_estimate=pl.CostEstimate(
                flops=2 * b * H * s * L * (dk + dv),
                bytes_accessed=b * H * (item * (s * dk + L * (dk + dv))
                                        + s * L + 4 * s * dv),
                transcendentals=b * H * s * L),
            interpret=interpret,
            name="mla_flash_prefill",
        )(visited, q, k, v, keep)
