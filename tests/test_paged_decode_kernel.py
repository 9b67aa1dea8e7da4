"""Multi-sequence-grid Pallas paged-decode kernel (interpret mode).

The kernel contract under test (kernels/paged_attention.py,
docs/DECODE.md): ONE kernel instance covers every decode slot — grid
(slot, kv-head-block), each program a loop over the slot's live page
chunks, with double-buffered HBM→VMEM page prefetch driven by explicit
async copies that cross from one program to the next — and must agree
with the
reference ``paged_attention_arrays`` gather path across the serving
matrix: mixed live/dead slots (dead slots emit zeros and are skipped
by the prefetch schedule), ragged context lengths including exact
page boundaries, GQA head grouping, sliding windows, int8 pools with
per-slot scale pools, bf16 pools, and every legal chunk/head-block
partition of the same problem. Interpret mode simulates the DMA
semaphores, so the pipeline logic itself is tier-1-covered with no
TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401 — platform/flags init
from paddle_tpu.kernels.paged_attention import (_chunk_geometry,
                                                paged_attention_arrays,
                                                paged_decode_pallas,
                                                paged_pallas_requirements)
from paddle_tpu.quantization.functional import kv_quantize_arrays

TOL = dict(rtol=2e-4, atol=2e-4)


def _pool(rng, b, h, h_kv, d, bs, nblocks, dtype=np.float32):
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
    kc = jnp.asarray(rng.standard_normal(
        (b * nblocks, h_kv, bs, d)).astype(dtype))
    vc = jnp.asarray(rng.standard_normal(
        (b * nblocks, h_kv, bs, d)).astype(dtype))
    bt = jnp.asarray(rng.permutation(b * nblocks).astype(
        np.int32).reshape(b, nblocks))
    return q, kc, vc, bt


# context lengths a slot (page 8, 5 pages: capacity 40); 0 = a dead lane
LIVE_LAYOUTS = {
    "mixed": [0, 1, 13, 0, 40, 23],
    "live-between-dead-runs": [0, 0, 0, 9, 0, 0, 40, 0, 0, 0, 17, 0],
    "only-the-last-live": [0, 0, 0, 0, 0, 33],
}


@pytest.mark.parametrize("layout", LIVE_LAYOUTS)
def test_mixed_live_dead_slots(rng, layout):
    """Dead slots (context 0 — empty serving lanes) must emit exact
    zeros while live neighbours, including a 1-token context, stay
    bit-identical to the same call without the dead lanes: the
    prefetch lookahead has to skip dead slots (a run of them, or all
    but the last), not stall on them."""
    cl = np.array(LIVE_LAYOUTS[layout], np.int32)
    b, h, h_kv, d, bs, nblocks = len(cl), 8, 4, 128, 8, 5
    q, kc, vc, bt = _pool(rng, b, h, h_kv, d, bs, nblocks)
    cl = jnp.asarray(cl)
    ref = paged_attention_arrays(q, kc, vc, bt, cl)
    out = paged_decode_pallas(q, kc, vc, bt, cl, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    assert (np.asarray(out)[np.asarray(cl) == 0] == 0.0).all()
    # live rows must not depend on which OTHER lanes are dead: rows
    # (1, 2, 4, 5) bitwise-match the dead-lane-free call
    live = np.asarray(cl) > 0
    alone = paged_decode_pallas(q[live], kc, vc, bt[live], cl[live],
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(out)[live],
                                  np.asarray(alone))


def test_all_slots_dead(rng):
    """An all-idle decode tick (every lane empty) must return zeros,
    not hang the prefetch pipeline waiting for a first live chunk."""
    b, h, h_kv, d, bs, nblocks = 3, 4, 4, 128, 8, 4
    q, kc, vc, bt = _pool(rng, b, h, h_kv, d, bs, nblocks)
    cl = jnp.zeros((b,), jnp.int32)
    out = paged_decode_pallas(q, kc, vc, bt, cl, interpret=True)
    assert (np.asarray(out) == 0.0).all()


@pytest.mark.parametrize("ppc", [1, 2, 4])
def test_page_boundary_context_lengths(rng, ppc):
    """Contexts ending one short of a chunk boundary, exactly ON it,
    one past it, mid-page, and at full capacity — the walk's trip count
    ceil(ctx / T) and the output write after the last live chunk must
    agree with the reference masks, for chunks of 1, 2 and 4 pages."""
    b, h, h_kv, d, bs, nblocks = 6, 8, 4, 128, 8, 8
    q, kc, vc, bt = _pool(rng, b, h, h_kv, d, bs, nblocks)
    T, capacity = ppc * bs, nblocks * bs
    cl = jnp.asarray(np.array([T - 1, T, T + 1, 11, capacity, 1],
                              np.int32))
    ref = paged_attention_arrays(q, kc, vc, bt, cl)
    out = paged_decode_pallas(q, kc, vc, bt, cl, interpret=True,
                              pages_per_chunk=ppc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


# partition matrix leg: mixed_live_dead/page_boundary/int8_scale
# keep the paged kernel tier-1; the chunk x headblock sweep rides
# slow.
@pytest.mark.slow
def test_chunk_and_headblock_partitions_agree(rng):
    """Every legal (pages_per_chunk, kv_heads_per_block) partition of
    the same problem — different DMA schedules, different grid shapes
    — produces the same attention output."""
    b, h, h_kv, d, bs, nblocks = 3, 8, 4, 128, 8, 4
    q, kc, vc, bt = _pool(rng, b, h, h_kv, d, bs, nblocks)
    cl = jnp.asarray(np.array([5, 0, 27], np.int32))
    ref = paged_attention_arrays(q, kc, vc, bt, cl)
    for ppc in (1, 2, 4):
        for hpb in (1, 2, 4):
            out = paged_decode_pallas(
                q, kc, vc, bt, cl, interpret=True,
                pages_per_chunk=ppc, kv_heads_per_block=hpb)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref),
                err_msg=f"ppc={ppc} hpb={hpb}", **TOL)


@pytest.mark.parametrize("cl", [[0, 33, 128, 64], [33, 0, 0, 97]],
                         ids=["mixed", "live-between-dead-runs"])
def test_int8_scale_pools_mixed_slots_window(rng, cl):
    """int8 pools + per-slot scale pools through the multi-sequence
    grid: in-VMEM dequant must match the gather+dequant reference with
    dead lanes, ragged lengths and a sliding window in the mix."""
    b, h, h_kv, d, bs, nblocks = 4, 8, 2, 128, 32, 4
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
    kq, ks = kv_quantize_arrays(jnp.asarray(rng.standard_normal(
        (b * nblocks, h_kv, bs, d)).astype(np.float32)))
    vq, vs = kv_quantize_arrays(jnp.asarray(rng.standard_normal(
        (b * nblocks, h_kv, bs, d)).astype(np.float32)))
    bt = jnp.asarray(rng.permutation(b * nblocks).astype(
        np.int32).reshape(b, nblocks))
    cl = jnp.asarray(np.array(cl, np.int32))
    ref = paged_attention_arrays(q, kq, vq, bt, cl,
                                 k_scale=ks, v_scale=vs)
    out = paged_decode_pallas(q, kq, vq, bt, cl, interpret=True,
                              k_scale=ks, v_scale=vs,
                              pages_per_chunk=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    assert (np.asarray(out)[np.asarray(cl) == 0] == 0.0).all()
    # windowed: only the last `window` positions stay visible
    win = 17
    L = nblocks * bs
    kk = jnp.swapaxes(jnp.take(kq.astype(jnp.float32) * ks[..., None],
                               bt, axis=0), 2, 3).reshape(b, L, h_kv, d)
    vv = jnp.swapaxes(jnp.take(vq.astype(jnp.float32) * vs[..., None],
                               bt, axis=0), 2, 3).reshape(b, L, h_kv, d)
    rep = h // h_kv
    qg = q.reshape(b, h_kv, rep, d).astype(jnp.float32)
    logits = jnp.einsum("bgrd,bLgd->bgrL", qg, kk) * (d ** -0.5)
    kpos = jnp.arange(L)
    valid = (kpos[None] < cl[:, None]) & \
        ((cl[:, None] - 1 - kpos[None]) < win)
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    want = jnp.einsum("bgrL,bLgd->bgrd", jax.nn.softmax(logits, -1),
                      vv).reshape(b, h, d)
    want = jnp.where((cl > 0)[:, None, None], want, 0.0)
    got = paged_decode_pallas(q, kq, vq, bt, cl, window=win,
                              interpret=True, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_bf16_pool(rng):
    """bf16 pools stream at half the f32 bytes; the reference path
    shares the same bf16→f32 read, so outputs agree tightly."""
    b, h, h_kv, d, bs, nblocks = 3, 4, 2, 128, 16, 3
    q, kc, vc, bt = _pool(rng, b, h, h_kv, d, bs, nblocks)
    kc = kc.astype(jnp.bfloat16)
    vc = vc.astype(jnp.bfloat16)
    cl = jnp.asarray(np.array([7, 30, 48], np.int32))
    ref = paged_attention_arrays(q, kc, vc, bt, cl)
    out = paged_decode_pallas(q, kc, vc, bt, cl, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-3, atol=5e-3)


# a latent pool of 640-lane rows (512 values + 64 rope + padding, the
# DeepSeek-V2 / dots3 full layer's row), page 16, 8 pages a slot walked
# in chunks of 2 (T = 32): contexts at T - 1, T + 1, 2T and capacity,
# live slots between runs of dead ones
LATENT_CTX = [31, 0, 0, 33, 128, 0, 64]


@pytest.mark.parametrize("kw", [
    dict(), dict(mask=True), dict(window=40), dict(dtype=jnp.bfloat16)],
    ids=["causal", "selected-set", "window", "bf16-pool"])
def test_latent_walk_matches_the_xla_formulation(rng, kw):
    """`paged_mla_decode` on the 640-lane latent row, with and without a
    keep-mask (the mask row follows the walk chunk by chunk), under a
    window, on a bf16 pool: every live slot agrees with
    `paged_mla_arrays`, every dead one is zeros."""
    from paddle_tpu.kernels.paged_attention import (paged_mla_arrays,
                                                    paged_mla_decode)
    kw = dict(kw)
    dtype = kw.pop("dtype", jnp.float32)
    cl = np.array(LATENT_CTX, np.int32)
    b, h, w, dv, bs, mb = len(cl), 8, 640, 512, 16, 8
    pool = jnp.asarray(rng.normal(size=(1 + b * mb, bs, w)), dtype)
    bt = jnp.asarray(1 + rng.permutation(b * mb).reshape(b, mb), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, h, w)), jnp.float32).astype(dtype)
    if kw.pop("mask", False):
        kw["mask"] = jnp.asarray(rng.integers(0, 2, (b, mb * bs)), jnp.int32)
    want = paged_mla_arrays(q, pool, bt, jnp.asarray(cl), dv, 0.05, **kw)
    got = paged_mla_decode(q, pool, bt, jnp.asarray(cl), dv, 0.05,
                           interpret=True, pages_per_chunk=2, **kw)
    assert got.shape == (b, h, dv)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    assert not np.asarray(got)[cl == 0].any()


def test_layers_of_one_signature_trace_the_kernel_once(rng, monkeypatch):
    """Six decode layers of one program calling the kernel at one
    signature trace its body ONCE (a nested jit), and each call is
    counted in `kernels.decode.paged_live_walk`: a body traced per call
    costs set-up time in every program that holds six such layers."""
    from paddle_tpu import monitor
    from paddle_tpu.kernels import paged_attention as pa
    traced = []
    body = pa._paged_decode_kernel

    def counting(*refs, **kw):
        traced.append(1)
        return body(*refs, **kw)

    monkeypatch.setattr(pa, "_paged_decode_kernel", counting)
    # a geometry no other test uses, so no earlier trace is cached
    b, h, h_kv, d, bs, nblocks = 3, 10, 2, 128, 8, 3
    q, kc, vc, bt = _pool(rng, b, h, h_kv, d, bs, nblocks)
    cl = jnp.asarray(np.array([5, 0, 24], np.int32))

    def six_layers(q, kc, vc, bt, cl):
        out = q
        for _ in range(6):
            out = paged_decode_pallas(out, kc, vc, bt, cl, interpret=True)
        return out

    walks = monitor.counter("kernels.decode.paged_live_walk")
    before = walks.get()
    jax.jit(six_layers).lower(q, kc, vc, bt, cl)
    assert len(traced) == 1
    assert walks.get() == before + 6


def test_next_live_slots_table():
    """The walk's next-live-slot table: for each slot, the first LATER
    slot with a nonzero context, b where none is."""
    from paddle_tpu.kernels.paged_attention import _next_live_slots
    cl = jnp.asarray([0, 5, 0, 0, 7, 0, 1, 0], jnp.int32)
    np.testing.assert_array_equal(np.asarray(_next_live_slots(cl)),
                                  [1, 4, 4, 4, 6, 6, 8, 8])
    np.testing.assert_array_equal(
        np.asarray(_next_live_slots(jnp.zeros((3,), jnp.int32))), [3, 3, 3])


def test_chunk_geometry_and_requirements():
    """Partition validation fails loudly on non-divisors; the
    eligibility helper names the violated constraint (the string the
    engine surfaces at construction)."""
    with pytest.raises(ValueError, match="pages_per_chunk"):
        _chunk_geometry(5, 8, 4, 128, 4, pages_per_chunk=2)
    with pytest.raises(ValueError, match="kv_heads_per_block"):
        _chunk_geometry(4, 8, 4, 128, 4, kv_heads_per_block=3)
    # defaults: divisors under the chunk/buffer budgets
    ppc, hpb = _chunk_geometry(12, 32, 4, 128, 4)
    assert 12 % ppc == 0 and ppc * 32 <= 512
    assert 4 % hpb == 0
    assert paged_pallas_requirements(128, 128, jnp.int8) is None
    assert "128 lanes" in paged_pallas_requirements(128, 32, jnp.int8)
    why = paged_pallas_requirements(64, 8, jnp.bfloat16)
    assert "head_dim 64" in why and "sublane" in why
    assert paged_pallas_requirements(128, 8, jnp.bfloat16) is not None


# -- the KV write (paged_write_arrays / paged_write_quant_arrays) ----------

def _write_today(pool, x, page, slot):
    """The write as it was before it moved to the pool's flat view: the
    reference for "lands where it landed"."""
    return pool.at[page, :, slot].set(x.astype(pool.dtype))


def _write_loop(pool, x, bt, positions):
    """Plain loop over (sequence, token, head) with the index rules
    spelled out: a position past the block table is dropped, a page id
    past the pool is dropped, a negative one counts from the end."""
    nb, h_kv, bs = pool.shape[:3]
    mb = bt.shape[1]
    out = np.array(pool)
    for seq in range(x.shape[0]):
        for i in range(x.shape[1]):
            pos = int(positions[seq]) + i
            j = pos // bs
            if not -mb <= j < mb:
                continue
            page = int(bt[seq, j])
            if not -nb <= page < nb:
                continue
            for head in range(h_kv):
                out[page, head, pos % bs] = x[seq, i, head]
    return out


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16) if a.dtype.itemsize == 2 else a


def _pools_for(rng, pool, nb, h_kv, bs, d):
    if pool == "bf16":
        return [jnp.asarray(rng.standard_normal((nb, h_kv, bs, d)),
                            jnp.bfloat16) for _ in range(2)]
    return [jnp.asarray(rng.integers(-127, 128, (nb, h_kv, bs, d)),
                        jnp.int8) for _ in range(2)] \
        + [jnp.asarray(rng.random((nb, h_kv, bs)), jnp.float32)
           for _ in range(2)]


@jax.jit
def _write_and_reference(k, v, pools, bt, pos):
    """(the pools the write returns, the [b, s, h_kv, ...] chunks it was
    to store, the pools `.at[page, :, slot].set` gives). One program, so
    the int8 path's quantisation is the same arithmetic on both sides;
    traced positions skip the eager capacity check."""
    from paddle_tpu.kernels.paged_attention import (
        _page_slots, paged_write_arrays, paged_write_quant_arrays)
    b, h_kv, d = k.shape[0], k.shape[-2], k.shape[-1]
    k4, v4 = k.reshape(b, -1, h_kv, d), v.reshape(b, -1, h_kv, d)
    if len(pools) == 2:
        got = paged_write_arrays(k, v, *pools, bt, pos)
        chunks = [k4.astype(pools[0].dtype), v4.astype(pools[1].dtype)]
    else:
        got = paged_write_quant_arrays(k, v, *pools, bt, pos)
        (qk, sk), (qv, sv) = kv_quantize_arrays(k4), kv_quantize_arrays(v4)
        chunks = [qk, qv, sk, sv]
    page, slot = _page_slots(bt, pos, k4.shape[1], pools[0].shape[2])
    today = [_write_today(p, x, page, slot) for p, x in zip(pools, chunks)]
    return got, chunks, today


# (block table or None for 3 sequences x 3 distinct pages of 12, first
# position of each sequence, tokens a sequence or None for the
# one-token form); page size 4, page 0 = the engine's scratch page
WRITE_CASES = {
    "one-token": (None, [5, 0, 11], None),
    "short-chunk-crossing-a-page": (None, [2, 6, 0], 3),
    "long-chunk-off-the-boundary": (None, [3, 1, 2], 6),
    "whole-pages": (None, [4, 0, 4], 8),
    "whole-pages-and-ragged-end": (None, [0, 0, 0], 11),
    "one-sequence-off-the-boundary": (None, [4, 1, 0], 8),
    # cache_index -1: page = block_tables[row, -1], slot = page_size - 1
    "dead-lane": ([[3, 4, 0], [5, 1, 2]], [-1, 6], None),
    "dead-lane-chunk": ([[3, 4, 0], [5, 1, 2]], [-1, 4], 3),
    "page-id-past-the-pool": ([[3, 6, 2], [5, 1, 7]], [5, 2], None),
    "page-id-past-the-pool-chunk": ([[3, 6, 2], [5, 1, 600]], [4, 4], 8),
    "negative-page-id-wraps": ([[3, -1, 2], [5, 1, -6]], [6, 8], None),
    "negative-page-id-wraps-chunk": ([[-2, 4, 0], [5, -7, 2]], [0, 0], 8),
    # the padded tail of a chunk that runs past the block table must
    # land nowhere
    "past-the-block-table": ([[3, 4, 2], [5, 1, 0]], [8, 4], 6),
    "past-the-block-table-whole-pages": ([[3, 4, 2], [5, 1, 0]], [8, 4],
                                         8),
}


@pytest.mark.parametrize("h_kv", [8, 1], ids=["gqa8", "mqa1"])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("case", WRITE_CASES)
def test_paged_write_matches_loop(rng, case, pool, h_kv):
    """Every element the write stores is the one a plain loop stores and
    the one `.at[page, :, slot].set` stored, at every granularity the
    chunk's shape selects (rows; whole-page tiles + rows for the ragged
    end when every sequence starts on a page boundary): a dead lane
    (position -1), a page id past the pool (dropped), a negative one
    (wraps) and a chunk that runs past the block table land where they
    landed, and pages the block table does not name are bit-unchanged."""
    bt, pos, s = WRITE_CASES[case]
    bs, d = 4, 8
    if bt is None:
        nb = 12
        bt = rng.permutation(np.arange(1, nb))[:9].reshape(3, 3)
    else:
        nb = 6
    bt, pos = np.asarray(bt, np.int32), np.asarray(pos, np.int32)
    b = len(pos)
    shape = (b, h_kv, d) if s is None else (b, s, h_kv, d)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    pools = _pools_for(rng, pool, nb, h_kv, bs, d)
    got, chunks, today = _write_and_reference(k, v, pools, bt, pos)
    assert len(got) == len(pools)
    named = np.isin(np.arange(nb), bt % nb)
    for new, old, x, was in zip(got, pools, chunks, today):
        assert new.dtype == old.dtype and new.shape == old.shape
        np.testing.assert_array_equal(
            _bits(new), _write_loop(_bits(old), _bits(x), bt, pos))
        np.testing.assert_array_equal(_bits(new), _bits(was))
        np.testing.assert_array_equal(_bits(new)[~named],
                                      _bits(old)[~named])
        assert (_bits(new)[named] != _bits(old)[named]).any()
    if case.startswith("dead-lane"):
        # the dead lane's first token: scratch page 0, last slot
        np.testing.assert_array_equal(_bits(got[0])[0, :, bs - 1],
                                      _bits(chunks[0])[0, 0])
