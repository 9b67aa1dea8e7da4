"""Paged-KV decode attention — TPU-native block-table serving cache.

Reference capability: block_multihead_attention
(/root/reference/python/paddle/incubate/nn/functional/blha_get_max_len.py
family and paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu)
— the paged-attention decode kernel behind PaddleNLP serving, where each
sequence's KV cache lives in non-contiguous fixed-size blocks addressed
through a block table, so cache memory is allocated block-by-block as
sequences grow instead of max-length-per-sequence up front.

TPU-native design: the block gather is ONE XLA gather
(``cache[block_tables]``), attention over the gathered pages is a dense
masked softmax — XLA fuses gather + QK + softmax + PV into a handful of
kernels, with no CUDA-style hand scheduling. Shapes stay static
(max_blocks_per_seq bounds the gather); per-sequence validity comes from
``context_lens`` masking, the standard Pallas/serving pattern on TPU.

GQA/MQA: caches carry ``h_kv`` heads; query heads map to kv head
``h // rep`` exactly like kernels/flash_attention.py.
"""
from __future__ import annotations

import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import run_op

NEG_INF = -1e30

logger = logging.getLogger(__name__)


def paged_pallas_requirements(head_dim, block_size, cache_dtype):
    """Which Pallas-eligibility constraint a page-pool geometry misses,
    as a human-readable string — or None when the geometry is eligible.
    The [block_size, head_dim] page tile must meet the dtype's minimum
    (sublane, lane) tile: (8, 128) f32, (16, 128) bf16/f16. An int8
    pool also streams one f32 scale row of block_size lanes per
    (page, head), which must fill whole 128-lane tiles — the v5e
    compiler refuses to slice a narrower row out of the scale pool
    (tests/test_tpu_compile.py; docs/DECODE.md eligibility table)."""
    name = jnp.dtype(cache_dtype).name
    sublane = {"bfloat16": 16, "float16": 16}.get(name, 8)
    problems = []
    if head_dim % 128:
        problems.append(
            f"head_dim {head_dim} is not a multiple of the 128 lane width")
    if name == "int8":
        if block_size % 128:
            problems.append(
                f"page_size {block_size} is not a multiple of the 128 "
                f"lanes an int8 pool's per-token scale rows must fill")
    elif block_size % sublane:
        problems.append(
            f"page_size {block_size} is not a multiple of the {name} "
            f"sublane minimum {sublane}")
    return "; ".join(problems) if problems else None


def paged_pallas_eligible(head_dim, block_size, cache_dtype):
    """Static eligibility of the Pallas decode kernel for a page-pool
    geometry (see paged_pallas_requirements for the constraint names).
    The caller falls back to the XLA gather path (and bumps the
    `kernels.decode.paged_xla_*` counter) when this is False, so a
    bench line showing the gather path names the constraint that was
    missed."""
    return paged_pallas_requirements(head_dim, block_size,
                                     cache_dtype) is None


_ineligible_warned = set()


def log_paged_ineligible(head_dim, block_size, cache_dtype,
                         site="decode"):
    """Trace-time note for a paged decode step that cannot take the
    Pallas kernel: the `kernels.decode.paged_xla_gather_step` counter
    records THAT it fell back; this names WHY, once per geometry, so a
    slow serving run points straight at the violated constraint."""
    why = paged_pallas_requirements(head_dim, block_size, cache_dtype)
    if why and (site, why) not in _ineligible_warned:
        _ineligible_warned.add((site, why))
        logger.warning(
            "paged %s step falling back to the XLA gather path: %s "
            "(docs/DECODE.md eligibility table)", site, why)
    return why


def paged_attention_arrays(q, k_cache, v_cache, block_tables, context_lens,
                           scale: Optional[float] = None,
                           k_scale=None, v_scale=None):
    """One decode step of attention against a paged KV cache.

    q:            [b, h, d]           — this step's query (one token/seq).
    k_cache/v_cache: [num_blocks, h_kv, block_size, d] — the global page
                  pool; h_kv may divide h (GQA). Head-major layout so
                  the Pallas decode kernel's [block_size, d] page tiles
                  are the (tile-aligned) trailing dims.
    block_tables: [b, max_blocks] int — page ids per sequence, in order;
                  entries past the sequence's pages may be any valid id
                  (masked out by context_lens).
    context_lens: [b] int             — tokens (incl. this step's, if
                  already written) visible per sequence.
    k_scale/v_scale: [num_blocks, h_kv, block_size] f32 — per-slot
                  dequant scales for an int8 pool (kv_quantize_arrays
                  granularity); None for float pools.
    Returns [b, h, d].
    """
    b, h, d = q.shape
    nb, h_kv, bs, _ = k_cache.shape
    if h_kv < 1 or h % h_kv:
        raise ValueError(
            f"GQA requires query heads ({h}) to be a multiple of cache "
            f"kv heads ({h_kv})")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    rep = h // h_kv

    k = gather_pages(k_cache, block_tables)
    v = gather_pages(v_cache, block_tables)
    if k_scale is not None:
        ks = gather_page_scales(k_scale, block_tables)
        vs = gather_page_scales(v_scale, block_tables)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    L = block_tables.shape[1] * bs
    # GQA served by grouped einsum — no rep-times K/V copy over the
    # gathered pages (same idea as flash_attention's kv index map)
    qg = q.reshape(b, h_kv, rep, d).astype(jnp.float32)
    logits = jnp.einsum("bgrd,bLgd->bgrL", qg,
                        k.astype(jnp.float32)) * jnp.float32(scale)
    valid = jnp.arange(L)[None, :] < context_lens[:, None]      # [b, L]
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrL,bLgd->bgrd", p, v.astype(jnp.float32))
    # padded slots (context_len 0) emit zeros, not a uniform average of
    # whatever pages their block table points at
    out = jnp.where(context_lens[:, None, None, None] > 0, out, 0.0)
    return out.reshape(b, h, d).astype(q.dtype)


def gather_pages(cache, block_tables):
    """Materialize each sequence's pages as a contiguous [b, L, h_kv, d]
    view (L = max_blocks * block_size) from the head-major pool. ONE
    XLA gather — it copies the pages the block table names (never the
    pool), which is still the whole visible cache: the decode hot path
    uses paged_decode_pallas instead, prefill chunks come here."""
    nb, h_kv, bs, d = cache.shape
    b = block_tables.shape[0]
    L = block_tables.shape[1] * bs
    g = jnp.take(cache, block_tables, axis=0)   # [b, mb, h_kv, bs, d]
    return jnp.swapaxes(g, 2, 3).reshape(b, L, h_kv, d)


def gather_page_scales(scales, block_tables):
    """gather_pages for a per-slot scale pool [num_blocks, h_kv,
    block_size] → [b, L, h_kv] (the kv_quantize_arrays layout of the
    gathered token axis)."""
    nb, h_kv, bs = scales.shape
    b = block_tables.shape[0]
    L = block_tables.shape[1] * bs
    g = jnp.take(scales, block_tables, axis=0)  # [b, mb, h_kv, bs]
    return jnp.swapaxes(g, 2, 3).reshape(b, L, h_kv)


def paged_write_arrays(k, v, k_cache, v_cache, block_tables, positions):
    """Append token k/v per sequence into the paged cache.

    k/v:        [b, h_kv, d] (one token/seq) or [b, s, h_kv, d] (a
                prefill chunk of s consecutive tokens/seq). The pool is
                head-major [num_blocks, h_kv, block_size, d].
    positions:  [b] int — each sequence's (FIRST) token position; chunk
                token i lands at position + i. The page is
                block_tables[seq, pos // block_size], the slot
                pos % block_size.
    Returns the updated (k_cache, v_cache).
    """
    if k.ndim == 3:
        k, v = k[:, None], v[:, None]
    return _scatter_tokens(
        (k_cache, v_cache),
        (k.astype(k_cache.dtype), v.astype(v_cache.dtype)),
        block_tables, positions)


def _page_slots(block_tables, positions, s, bs):
    """(page, slot) [b, s] for a chunk of s consecutive tokens starting
    at per-sequence ``positions``, with the eager-only capacity check."""
    capacity = block_tables.shape[1] * bs
    # NOTE: the concrete capacity check below costs a host sync per
    # EAGER call (jnp.max fetch); jit-compiled serving loops trace past
    # it. Contract not validated here: block-table rows must not alias
    # the same page across sequences — aliased pages are silently
    # last-write-wins.
    if not isinstance(positions, jax.core.Tracer):
        pmax = int(jnp.max(positions)) + s - 1
        if pmax >= capacity:
            # take_along_axis would silently CLIP the page index and
            # overwrite the last page's slots — corrupting cached
            # tokens; fail loudly instead, naming the offending row
            # (traced positions skip this concrete check; the engine's
            # allocator raises the pool-exhaustion RuntimeError before
            # a write can ever get here)
            seq = int(jnp.argmax(positions))
            raise ValueError(
                f"position {pmax} (sequence {seq}) exceeds the "
                f"block-table capacity {capacity} "
                f"({block_tables.shape[1]} pages x block_size {bs}) — "
                f"grow the block table / allocate more pages first")
    pos = positions[:, None] + jnp.arange(s, dtype=positions.dtype)[None]
    page = jnp.take_along_axis(block_tables, pos // bs, axis=1)  # [b, s]
    return page, pos % bs


def _scatter_rows(pool, x, page, slot):
    """pool[page, :, slot] = x for x [b, s, h_kv, ...], one row per
    (token, kv head), indexed on the pool's [num_blocks,
    h_kv * block_size, ...] view: the two indexed dimensions are then
    the pool's two major ones and the reshape is a bitcast, so the TPU
    compiler scatters into the (donated) pool where it lies. Indexed as
    ``.at[page, :, slot]`` the scatter straddles the head dimension,
    gets an operand layout with page and slot major, and every call
    transposes the WHOLE pool there and back (docs/DECODE.md "the KV
    write"). The page index keeps ``.at``'s own semantics — a negative
    id wraps, an id past the pool is dropped — and the column is always
    in range."""
    nb, h_kv, bs = pool.shape[:3]
    col = jnp.arange(h_kv, dtype=slot.dtype) * bs + slot[..., None]
    flat = pool.reshape((nb, h_kv * bs) + pool.shape[3:])
    return flat.at[page[..., None], col].set(x).reshape(pool.shape)


def _scatter_pages(pool, x, page):
    """pool[page] = x for whole pages: x [b, n * block_size, h_kv, ...]
    whose token 0 sits on slot 0, page [b, n]. One index row per page
    where _scatter_rows has block_size * h_kv (a TPU scatter walks its
    indices: 0.29 ms against 15.6 for the 16 pools of a 1792-token
    chunk, PERF.md section 6, PR 28)."""
    _, h_kv, bs = pool.shape[:3]
    b, n = page.shape
    tiles = x.reshape((b, n, bs, h_kv) + x.shape[3:])
    return pool.at[page].set(jnp.swapaxes(tiles, 2, 3))


def _scatter_tokens(pools, chunks, block_tables, positions,
                    headless=False):
    """Write each [b, s, h_kv, ...] chunk into its head-major pool at
    the (page, slot) of ``positions``, in place in the pool's own
    layout. The granularity follows what the chunk is: rows
    (_scatter_rows) for a chunk shorter than a page; for a longer one,
    whole-page tiles (_scatter_pages) plus rows for the ragged end when
    every sequence starts on a page boundary — a property of the traced
    ``positions``, so the choice is a ``lax.cond`` — and rows
    otherwise. Both branches write the same elements.

    ``headless``: the pools are [num_blocks, block_size, w] and the
    chunks [b, s, w], one vector a token and no head dimension (a
    latent-attention layer's rows). The two indexed dimensions are then
    the pool's two major ones as it stands. (A head dimension of size 1
    would do in arithmetic, but gives the TPU compiler two names for one
    layout, and it copies the whole pool from one to the other at the
    ``cond``.)"""
    bs = pools[0].shape[1 if headless else 2]
    s = chunks[0].shape[1]
    page, slot = _page_slots(block_tables, positions, s, bs)
    if headless:
        def row_fn(p, x, pg, sl):
            return p.at[pg, sl].set(x)

        def page_fn(p, x, pg):
            b, n = pg.shape
            return p.at[pg].set(x.reshape((b, n, bs) + x.shape[2:]))
    else:
        row_fn, page_fn = _scatter_rows, _scatter_pages

    def rows(pools, lo=0):
        return tuple(row_fn(p, x[:, lo:], page[:, lo:], slot[:, lo:])
                     for p, x in zip(pools, chunks))

    whole = s - s % bs
    if not whole:
        return rows(pools)

    def pages(pools):
        pools = tuple(page_fn(p, x[:, :whole], page[:, :whole:bs])
                      for p, x in zip(pools, chunks))
        return rows(pools, whole) if whole < s else pools

    return jax.lax.cond(jnp.all(slot[:, 0] == 0), pages, rows, pools)


def paged_write_rows(rows, pools, block_tables, positions):
    """Append per-token vectors into head-less paged pools: rows[i]
    [b, s, w_i] into pools[i] [num_blocks, block_size, w_i], token j of
    sequence b at position positions[b] + j. The same in-place write as
    paged_write_arrays (docs/DECODE.md "The KV write"). Returns the
    updated pools."""
    return _scatter_tokens(
        tuple(pools), tuple(r.astype(p.dtype) for r, p in zip(rows, pools)),
        block_tables, positions, headless=True)


def gather_rows(pool, block_tables):
    """gather_pages for a head-less pool [num_blocks, block_size, w]:
    each sequence's pages as [b, L, w]."""
    b, mb = block_tables.shape
    g = jnp.take(pool, block_tables, axis=0)          # [b, mb, bs, w]
    return g.reshape(b, mb * pool.shape[1], pool.shape[2])


def paged_write_quant_arrays(k, v, k_cache, v_cache, k_scale, v_scale,
                             block_tables, positions):
    """paged_write_arrays for an int8 pool: quantizes the float chunk
    per (token, kv_head) (quantization.kv_quantize_arrays) and writes
    values AND scales. k/v: [b, h_kv, d] or [b, s, h_kv, d] float;
    k_cache/v_cache int8 pools; k_scale/v_scale f32
    [num_blocks, h_kv, block_size]. Returns the four updated pools."""
    from ..quantization.functional import kv_quantize_arrays

    if k.ndim == 3:
        k, v = k[:, None], v[:, None]
    qk, sk = kv_quantize_arrays(k)     # [b, s, h_kv, d] / [b, s, h_kv]
    qv, sv = kv_quantize_arrays(v)
    return _scatter_tokens((k_cache, v_cache, k_scale, v_scale),
                           (qk, qv, sk, sv), block_tables, positions)


# Multi-sequence-grid kernel tiling (paged_decode_pallas): target
# tokens per compute chunk, and the VMEM budget for ONE double-buffer
# slot of ONE of the K/V chunk buffers (two slots x k+v stay well
# under 1/4 of the 16 MB VMEM at the cap)
_CHUNK_TOKENS = 512
_PAGE_BUF_BYTES = 512 * 1024


def _chunk_geometry(nblocks, bs, h_kv, d, itemsize,
                    pages_per_chunk=None, kv_heads_per_block=None):
    """(pages_per_chunk, kv_heads_per_block) for the decode grid. Both
    must divide their dimension (the grid is exact, no ragged tail);
    the defaults pick the largest divisors that keep one chunk at
    ~_CHUNK_TOKENS tokens and one buffer slot under _PAGE_BUF_BYTES."""
    if pages_per_chunk is None:
        ppc = 1
        for c in range(1, nblocks + 1):
            if nblocks % c == 0 and c * bs <= max(bs, _CHUNK_TOKENS):
                ppc = c
    else:
        ppc = int(pages_per_chunk)
        if ppc < 1 or nblocks % ppc:
            raise ValueError(
                f"pages_per_chunk must divide the block-table width "
                f"{nblocks}; got {pages_per_chunk}")
    if kv_heads_per_block is None:
        hpb = 1
        per_head = ppc * bs * d * itemsize
        for c in range(1, h_kv + 1):
            if h_kv % c == 0 and c * per_head <= max(per_head,
                                                     _PAGE_BUF_BYTES):
                hpb = c
    else:
        hpb = int(kv_heads_per_block)
        if hpb < 1 or h_kv % hpb:
            raise ValueError(
                f"kv_heads_per_block must divide the cache's kv heads "
                f"{h_kv}; got {kv_heads_per_block}")
    return ppc, hpb


def _paged_decode_kernel(bt_ref, cl_ref, nxt_ref, buf_ref, primed_ref,
                         q_ref, *refs,
                         batch, h_kv, bs, ppc, hpb, nchunks,
                         scale, window, quant, latent_dv=None,
                         masked=False):
    """One (slot, kv-head-block) program of multi-sequence single-token
    paged decode: a loop over the slot's live chunks of ppc pages.

    The K/V pools stay in HBM (`ANY` memory space); each chunk of ppc
    pages x hpb kv heads is streamed HBM→VMEM by explicit
    `pltpu.make_async_copy` DMAs into a two-slot rotating buffer: while
    chunk c is being reduced, the DMA for the NEXT live chunk — chunk
    c + 1 of this slot, else the first chunk of the next head block or
    of the next live slot — is already in flight (the upstream jax
    paged_attention kernel's schedule). `buf_ref`/`primed_ref` are
    mutable scalar-prefetch cells that persist across grid steps: the
    buffer toggle and a "pipeline primed" flag.

    Liveness: chunk c of a slot is live iff c * ppc * bs < context_len,
    so the loop runs ceil(context_len / (ppc * bs)) times (clamped to
    the table's width) and dead chunks cost nothing, not even a grid
    step. `nxt_ref` [b] names the first live slot after each slot
    (batch when none: `_next_live_slots`), one scalar read where the
    walk leaves a slot. Dead slots (context_len 0, e.g. empty serving
    lanes) issue NO copy and do NO math; their output rows are zeroed
    (matching paged_attention_arrays).

    quant=True adds per-slot scale pools (int8 cache): pages stream at
    a QUARTER of the f32 bytes and dequantize VMEM-side, inside this
    kernel — the XLA path would materialize the dequantized cache.

    Refs: q [hpb, rep, d] (kv-head-major GQA rows), k/v pools
    [num_blocks, h_kv, bs, d] in ANY, [scale pools [num_blocks, h_kv,
    1, bs] when quant], o [hpb, rep, d]; scratch: k/v chunk buffers
    [2, hpb, ppc, bs, d] (+ scale buffers [2, hpb, ppc, 1, bs]), one DMA
    semaphore per buffer slot, online-softmax m/l [hpb, rep, 128] and
    acc [hpb, rep, d].

    latent_dv (paged_mla_decode): the pool holds ONE latent row a token
    and no heads (h_kv 1, every query head reads the same row): keys are
    the whole row, values its first latent_dv entries, so there is no V
    pool, no V copy and no V buffer, and o/acc are latent_dv wide. The
    dots then keep the pool's dtype as operands (f32 accumulation): at
    128 query heads a row the kernel is as near the MXU's bound as the
    HBM's. masked adds the slot's keep-rows [nchunks, 1, T] int32 before
    the pools, one [1, T] row a chunk: the selected set of a
    sparse-attention layer.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    refs = list(refs)
    latent = latent_dv is not None
    mask_ref = refs.pop(0) if masked else None
    k_hbm = refs.pop(0)
    v_hbm = None if latent else refs.pop(0)
    ks_hbm, vs_hbm = (refs.pop(0), refs.pop(0)) if quant else (None, None)
    o_ref, kbuf = refs.pop(0), refs.pop(0)
    vbuf = None if latent else refs.pop(0)
    ksbuf, vsbuf = (refs.pop(0), refs.pop(0)) if quant else (None, None)
    sems, m_ref, l_ref, acc_ref = refs

    i = pl.program_id(0)          # slot (sequence / decode lane)
    hb = pl.program_id(1)         # kv-head block
    nhb = h_kv // hpb
    T = ppc * bs                  # tokens per chunk
    d = q_ref.shape[-1]
    ctx = cl_ref[i]
    neg_inf = jnp.float32(NEG_INF)

    def copies(slot, hblk, chunk, buf):
        """The chunk's DMA descriptors — recreated identically for
        start and wait (pallas semantics). All of a buffer slot's
        copies share that slot's semaphore: waiting on every one of
        them before compute means the total byte count has arrived,
        whatever order the DMA engines finished in."""
        hs = hblk * hpb
        out = []
        for p in range(ppc):
            page = bt_ref[slot, chunk * ppc + p]
            out.append(pltpu.make_async_copy(
                k_hbm.at[page, pl.ds(hs, hpb)],
                kbuf.at[buf, :, p], sems.at[buf]))
            if not latent:
                out.append(pltpu.make_async_copy(
                    v_hbm.at[page, pl.ds(hs, hpb)],
                    vbuf.at[buf, :, p], sems.at[buf]))
            if quant:
                out.append(pltpu.make_async_copy(
                    ks_hbm.at[page, pl.ds(hs, hpb)],
                    ksbuf.at[buf, :, p], sems.at[buf]))
                out.append(pltpu.make_async_copy(
                    vs_hbm.at[page, pl.ds(hs, hpb)],
                    vsbuf.at[buf, :, p], sems.at[buf]))
        return out

    @pl.when(ctx == 0)
    def _zero_dead():
        # dead slots emit zeros, not a stale buffer (the reference
        # path's cl > 0 guard)
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(ctx > 0)
    def _walk():
        @pl.when(primed_ref[0] == 0)
        def _prime():
            # very first live chunk of the whole call: nobody
            # prefetched it, start its copies now (the one unavoidable
            # pipeline bubble)
            for c in copies(i, hb, 0, buf_ref[0]):
                c.start()

        m_ref[...] = jnp.full_like(m_ref, neg_inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        n_live = jnp.minimum((ctx + T - 1) // T, nchunks)
        # where the walk goes after this program's last chunk: the
        # next head block of this slot, else the next live slot's first
        last_head = hb + 1 >= nhb
        slot_after = jnp.where(last_head, nxt_ref[i], i)
        head_after = jnp.where(last_head, 0, hb + 1)
        pos = ctx - 1

        def chunk_step(c, buf):
            more = c + 1 < n_live
            ni = jnp.where(more, i, slot_after)

            @pl.when(ni < batch)
            def _prefetch():
                # issue the NEXT live chunk's HBM→VMEM copies into the
                # other buffer slot while this chunk computes
                for cp in copies(ni, jnp.where(more, hb, head_after),
                                 jnp.where(more, c + 1, 0), 1 - buf):
                    cp.start()

            for cp in copies(i, hb, c, buf):
                cp.wait()
            q = q_ref[...].astype(jnp.float32) * jnp.float32(scale)
            if latent:
                k = kbuf[buf].reshape(hpb, T, d)
                v = k[:, :, :latent_dv]
                q = q.astype(k.dtype)
            else:
                k = kbuf[buf].reshape(hpb, T, d).astype(jnp.float32)
                v = vbuf[buf].reshape(hpb, T, d).astype(jnp.float32)
            # batched-over-heads skinny dots, f32 accumulation
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)   # [hpb, rep, T]
            if quant:
                # dequantize on the SCORE side: q·(k∘ks) = (q·k)∘ks and
                # p·(v∘vs) = (p∘vs)·v, with the per-token scales as
                # lane-dense [hpb, 1, T] rows (tokens on lanes, like s)
                # — a [hpb, T, 1] column to scale k/v themselves is a
                # shape cast the v5e compiler refuses, and rep·T
                # multiplies are fewer than T·d anyway
                def lane_row(sbuf):
                    pages = sbuf[buf]              # [hpb, ppc, 1, bs]
                    return jnp.concatenate(
                        [pages[:, p] for p in range(ppc)], axis=-1)
                s = s * lane_row(ksbuf)
            k_pos = (c * T
                     + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2))
            keep = k_pos <= pos
            if window is not None:
                keep = jnp.logical_and(keep,
                                       pos - k_pos < jnp.int32(window))
            if masked:
                keep = jnp.logical_and(keep, (mask_ref[c] != 0)[None])
            s = jnp.where(keep, s, neg_inf)

            m_prev = m_ref[:, :, :1]
            l_prev = l_ref[:, :, :1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            p = jnp.exp(s - m_cur)
            p = jnp.where(s > neg_inf * 0.5, p, 0.0)
            alpha = jnp.exp(m_prev - m_cur)
            l_cur = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
            if latent:
                p = p.astype(v.dtype)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p * lane_row(vsbuf) if quant else p, v,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)   # [hpb, rep, d]
            m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)
            return 1 - buf

        buf_ref[0] = jax.lax.fori_loop(0, n_live, chunk_step, buf_ref[0])
        primed_ref[0] = 1
        l_safe = jnp.maximum(l_ref[:, :, :1], jnp.float32(1e-30))
        valid = m_ref[:, :, :1] > neg_inf * 0.5
        o_ref[...] = jnp.where(valid, acc_ref[...] / l_safe,
                               0.0).astype(o_ref.dtype)


def paged_decode_pallas(q, k_cache, v_cache, block_tables, context_lens,
                        scale=None, window=None, interpret=False,
                        k_scale=None, v_scale=None,
                        pages_per_chunk=None, kv_heads_per_block=None):
    """Pallas multi-sequence paged decode: q [b, h, d] (one token per
    sequence) against the page pool, masked to context_lens (and a
    sliding window). Returns [b, h, d]. One kernel instance covers ALL
    b slots — grid (slot, kv-head-block), each program a loop over the
    slot's live page chunks with double-buffered HBM→VMEM page prefetch
    over the block table; slots with context_len 0 (empty serving
    lanes) cost no bandwidth and emit zeros. Pass k_scale/v_scale
    [num_blocks, h_kv, block_size] f32 for an int8 pool (in-kernel
    dequant). Geometry must satisfy paged_pallas_eligible(d,
    block_size, k_cache.dtype); pages_per_chunk/kv_heads_per_block
    override the auto tiling (each must divide its dimension)."""
    return _paged_decode_call(
        "paged_decode", q, k_cache, v_cache, block_tables, context_lens,
        scale=scale, window=window, interpret=interpret, k_scale=k_scale,
        v_scale=v_scale, pages_per_chunk=pages_per_chunk,
        kv_heads_per_block=kv_heads_per_block)


def _next_live_slots(context_lens):
    """[b] int32: the first slot after each slot whose context_len is
    nonzero, b where none is: a reverse running minimum over the live
    slots' indices, shifted by one. Where the decode walk goes after a
    slot's last chunk, read with ONE scalar load."""
    b = context_lens.shape[0]
    live = jnp.where(context_lens > 0, jnp.arange(b, dtype=jnp.int32),
                     jnp.int32(b))
    at_or_after = jax.lax.cummin(live, reverse=True)
    return jnp.concatenate([at_or_after[1:], jnp.full((1,), b, jnp.int32)])


def _paged_decode_call(name, q, k_cache, v_cache, block_tables,
                       context_lens, scale=None, window=None,
                       interpret=False, k_scale=None, v_scale=None,
                       pages_per_chunk=None, kv_heads_per_block=None,
                       latent_dv=None, mask=None):
    """The one pallas_call behind paged_decode_pallas and
    paged_mla_decode: the same grid, page walk and online softmax;
    `v_cache` None with `latent_dv` set is the latent pool whose rows
    are keys and (their first latent_dv entries) values, `mask`
    [b, max_blocks * block_size] a keep-set on top of the causal one.
    Counted once a traced call (`kernels.decode.paged_live_walk`)."""
    from .. import monitor

    monitor.counter("kernels.decode.paged_live_walk").increase()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_decode_jit(
        q, k_cache, v_cache, block_tables, context_lens, k_scale, v_scale,
        mask, name=name, scale=float(scale),
        window=None if window is None else int(window),
        interpret=bool(interpret), pages_per_chunk=pages_per_chunk,
        kv_heads_per_block=kv_heads_per_block,
        latent_dv=None if latent_dv is None else int(latent_dv))


# jitted: the decode layers of one program call the kernel with the same
# shapes, and a nested jit is traced and lowered (Pallas to Mosaic) once
# for all of them; set-up time, not device time
@functools.partial(jax.jit, static_argnames=(
    "name", "scale", "window", "interpret", "pages_per_chunk",
    "kv_heads_per_block", "latent_dv"))
def _paged_decode_jit(q, k_cache, v_cache, block_tables, context_lens,
                      k_scale, v_scale, mask, *, name, scale, window,
                      interpret, pages_per_chunk, kv_heads_per_block,
                      latent_dv):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .flash_attention import _x32_trace

    b, h, d = q.shape
    nb, h_kv, bs, _ = k_cache.shape
    nblocks = block_tables.shape[1]
    rep = h // h_kv
    quant = k_scale is not None
    latent = latent_dv is not None
    dv = latent_dv if latent else d
    ppc, hpb = _chunk_geometry(nblocks, bs, h_kv, d,
                               jnp.dtype(k_cache.dtype).itemsize,
                               pages_per_chunk, kv_heads_per_block)
    nchunks = nblocks // ppc
    nhb = h_kv // hpb
    T = ppc * bs
    bt = jnp.asarray(block_tables, jnp.int32)
    cl = jnp.asarray(context_lens, jnp.int32)
    qr = q.reshape(b, h_kv, rep, d)
    if rep % 8:
        # upstream paged_attention kernel's layout hint: a sub-8-row q
        # tile lowers to a <1x128>-ish memref that Mosaic lays out
        # badly unless the operand is f32
        qr = qr.astype(jnp.float32)

    kernel = functools.partial(
        _paged_decode_kernel, batch=b, h_kv=h_kv, bs=bs, ppc=ppc,
        hpb=hpb, nchunks=nchunks, scale=scale, window=window, quant=quant,
        latent_dv=latent_dv, masked=mask is not None)
    any_space = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    in_specs = [pl.BlockSpec((None, hpb, rep, d),
                             lambda i, hb, *_: (i, hb, 0, 0))]   # q
    inputs = [qr]
    if mask is not None:
        # the slot's keep-rows, one [1, T] row a chunk (tokens on lanes
        # like s), picked by the walk on the leading dimension
        in_specs.append(pl.BlockSpec((None, nchunks, 1, T),
                                     lambda i, hb, *_: (i, 0, 0, 0)))
        inputs.append(jnp.asarray(mask, jnp.int32)
                      .reshape(b, nchunks, 1, T))
    in_specs.append(any_space)
    inputs.append(k_cache)
    if not latent:
        in_specs.append(any_space)
        inputs.append(v_cache)
    if quant:
        in_specs += [any_space, any_space]
        # one [1, bs] row per (page, head): the page index stays a
        # major dimension of the VMEM buffer the rows are copied into,
        # so picking a page there never cuts through a tile
        inputs += [k_scale.reshape(nb, h_kv, 1, bs),
                   v_scale.reshape(nb, h_kv, 1, bs)]
    scratch = [pltpu.VMEM((2, hpb, ppc, bs, d), k_cache.dtype)]
    if not latent:
        scratch.append(pltpu.VMEM((2, hpb, ppc, bs, d), v_cache.dtype))
    if quant:
        scratch += [pltpu.VMEM((2, hpb, ppc, 1, bs), jnp.float32),
                    pltpu.VMEM((2, hpb, ppc, 1, bs), jnp.float32)]
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM((hpb, rep, 128), jnp.float32),
        pltpu.VMEM((hpb, rep, 128), jnp.float32),
        pltpu.VMEM((hpb, rep, dv), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # bt, cl, the next-live-slot table, plus two MUTABLE scalar
        # cells the kernel uses as cross-step pipeline state: the DMA
        # buffer toggle and the "pipeline primed" flag
        num_scalar_prefetch=5,
        grid=(b, nhb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, hpb, rep, dv),
                               lambda i, hb, *_: (i, hb, 0, 0)),
        scratch_shapes=scratch,
    )
    with _x32_trace():
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h_kv, rep, dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name=name,
        )(bt, cl, _next_live_slots(cl), jnp.zeros((1,), jnp.int32),
          jnp.zeros((1,), jnp.int32), *inputs)
    return out.reshape(b, h, dv)


def paged_mla_requirements(row_width, latent_dv, block_size, cache_dtype):
    """Which constraint a latent page pool misses for paged_mla_decode,
    or None: rows and their value part must fill whole 128-lane tiles
    (the model pads its row to that, see Dots3NoteForCausalLM), pages
    whole sublane tiles of the pool's dtype."""
    name = jnp.dtype(cache_dtype).name
    sublane = {"bfloat16": 16, "float16": 16}.get(name, 8)
    problems = []
    if name == "int8":
        problems.append("an int8 latent pool is not supported")
    if row_width % 128 or latent_dv % 128:
        problems.append(
            f"row width {row_width} / value width {latent_dv} is not a "
            f"multiple of the 128 lane width")
    if block_size % sublane:
        problems.append(
            f"page_size {block_size} is not a multiple of the {name} "
            f"sublane minimum {sublane}")
    return "; ".join(problems) if problems else None


def window_pages(block_tables, first_page, n_pages):
    """The `n_pages` block-table entries from column `first_page` [b]
    on (clamped into the table): the pages a layer has to read whose
    keys start on that page."""
    cols = jnp.minimum(first_page[:, None] + jnp.arange(n_pages)[None],
                       block_tables.shape[1] - 1)
    return jnp.take_along_axis(block_tables, cols, axis=1)


def paged_mla_arrays(q, pool, block_tables, context_lens, latent_dv,
                     scale, window=None, mask=None):
    """XLA formulation of paged_mla_decode (the reference the kernel is
    tested against, and the path off the TPU): q [b, h, w] against each
    slot's gathered latent rows [b, L, w]; values are the rows' first
    latent_dv entries. Gathers (copies) every page the table names."""
    rows = gather_rows(pool, block_tables)                    # [b, L, w]
    L = rows.shape[1]
    cdt = rows.dtype if rows.dtype in (jnp.bfloat16, jnp.float16) \
        else jnp.float32
    qs = (q.astype(jnp.float32) * jnp.float32(scale)).astype(cdt)
    logits = jnp.einsum("bhw,bLw->bhL", qs, rows.astype(cdt),
                        preferred_element_type=jnp.float32)
    k_pos = jnp.arange(L, dtype=jnp.int32)[None]
    pos = context_lens[:, None].astype(jnp.int32) - 1
    keep = k_pos <= pos
    if window is not None:
        keep &= pos - k_pos < window
    if mask is not None:
        keep &= mask != 0
    logits = jnp.where(keep[:, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(keep[:, None], p, 0.0)
    out = jnp.einsum("bhL,bLc->bhc", p.astype(cdt),
                     rows[..., :latent_dv].astype(cdt),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def paged_mla_decode(q, pool, block_tables, context_lens, latent_dv,
                     scale, window=None, mask=None, interpret=False,
                     pages_per_chunk=None):
    """Pallas paged LATENT decode (multi-head latent attention with the
    up-projection absorbed into q and the output): q [b, h, w], one
    token a slot and all h heads, against the slot's pages of the latent
    pool [num_blocks, block_size, w]. Every page is read once; a
    row is the key of all heads and its first latent_dv entries their
    value. Keys are masked to context_lens, to `window` (the last
    `window` positions, the query's included) and to `mask`
    [b, max_blocks * block_size] (nonzero = in the selected set).
    Returns [b, h, latent_dv]. Shares paged_decode_pallas's kernel body
    and page walk; dead slots (context_len 0) cost nothing."""
    return _paged_decode_call(
        "paged_mla_decode", q, pool[:, None], None, block_tables,
        context_lens,
        scale=scale, window=window, interpret=interpret,
        pages_per_chunk=pages_per_chunk, latent_dv=latent_dv, mask=mask)


def paged_attention(query, k_cache, v_cache, block_tables, context_lens,
                    scale=None, k_scale=None, v_scale=None):
    """Tensor-level entry (see paged_attention_arrays); pass
    k_scale/v_scale pools for an int8 cache."""
    if k_scale is not None:
        def fnq(q, kc, vc, bt, cl, ks, vs):
            return paged_attention_arrays(q, kc, vc, bt, cl, scale=scale,
                                          k_scale=ks, v_scale=vs)
        return run_op("paged_attention", fnq,
                      [query, k_cache, v_cache, block_tables,
                       context_lens, k_scale, v_scale])

    def fn(q, kc, vc, bt, cl):
        return paged_attention_arrays(q, kc, vc, bt, cl, scale=scale)
    return run_op("paged_attention", fn,
                  [query, k_cache, v_cache, block_tables, context_lens])


def paged_write(key, value, k_cache, v_cache, block_tables, positions):
    """Tensor-level entry (see paged_write_arrays)."""
    def fn(k, v, kc, vc, bt, pos):
        return paged_write_arrays(k, v, kc, vc, bt, pos)
    return run_op("paged_write", fn,
                  [key, value, k_cache, v_cache, block_tables, positions])
