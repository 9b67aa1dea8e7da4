"""Flash attention: Pallas TPU kernels (fwd + bwd) with an XLA reference path.

Reference capability: paddle/phi/kernels/gpu/flash_attn_kernel.cu and
flash_attn_grad_kernel.cu (dynloaded flash-attn v2 lib). TPU-native
design: blocked online-softmax kernels in Pallas that stream K/V tiles
through VMEM so the S×S score matrix never materializes in HBM. The
backward is recompute-style (FlashAttention-2): the forward additionally
saves the per-row logsumexp; backward recomputes P = exp(S - lse) per
tile and accumulates dQ (one kernel, gridded over q blocks) and dK/dV
(one kernel, gridded over k blocks). The whole thing is wrapped in
``jax.custom_vjp`` so training differentiates through the Pallas path.

Shapes that don't tile (and non-TPU backends) route to an XLA
einsum+softmax — a decision made from geometry and platform BEFORE the
call. A kernel that fails to trace, lower or compile raises: nothing
here catches it and reroutes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core import place
from ..core.dispatch import run_op

# block sizes tuned on v5e (seq-4096 fwd+bwd sweep, round 3): larger q/k
# tiles feed the MXU bigger dots — 256x512 ran 2x faster than 128x128
# and 3.3x faster than the XLA softmax path; last dim stays 128 lanes.
# _pick_block halves these until they divide the sequence, so lengths
# like 768 (divisible by 128 but not 256) keep the Pallas path.
BLOCK_Q = 256
BLOCK_K = 512


def _pick_block(limit, s):
    b = min(limit, s)
    while s % b:
        b //= 2
    return max(b, 1)


NEG_INF = -1e30
# lse/delta row-stat arrays are (B*H, S, 1) in HBM: narrow loads/stores
# legalize fine and a wider layout would
# multiply HBM bytes for data the kernels only read at [:, :1] anyway.
STAT_LANES = 1
# loop *carries*, by contrast, must be full-lane-width: (bq, 1) carries
# fail Mosaic's 'func.return' legalization on the loop region boundary.
CARRY_LANES = 128

def _x32_trace():
    """Trace-time x64 off around pallas_call.

    The package enables jax x64 globally (paddle's int64 default); under
    x64 Pallas lowers its grid loop with i64 scalars, which Mosaic
    cannot legalize ('func.return' on an (i32, i32, i64) loop boundary;
    a trivial gridded kernel already fails). All kernels here pin their
    own dtypes, so tracing them in x32 is semantics-preserving.
    """
    return jax.enable_x64(False)


# ---------------------------------------------------------------------------
# causal-band bounds shared by all three kernels
# ---------------------------------------------------------------------------

def _causal_k_hi(q_idx, bq, diag_off, block_k, nblocks):
    """Exclusive upper bound on k-block index for rows of q block q_idx:
    the last attended key is q_pos_max + diag_off (bottom-right-aligned
    band). int32 throughout — Mosaic cannot lower i64."""
    last_k = ((q_idx.astype(jnp.int32) + 1) * jnp.int32(bq)
              - jnp.int32(1) + jnp.int32(diag_off))
    return jnp.clip(last_k // jnp.int32(block_k) + jnp.int32(1),
                    jnp.int32(0), jnp.int32(nblocks))


def _causal_q_lo(k_idx, bk, diag_off, block_q, nblocks):
    """Inclusive lower bound on q-block index that can see k block k_idx:
    first row with q_pos >= k_block_start - diag_off."""
    first_q = k_idx.astype(jnp.int32) * jnp.int32(bk) - jnp.int32(diag_off)
    return jnp.clip(first_q // jnp.int32(block_q), jnp.int32(0),
                    jnp.int32(nblocks))


def _window_k_lo(q_idx, bq, diag_off, block_k, window, nblocks):
    """Inclusive lower bound on k-block index under a sliding window:
    the earliest attended key for rows of q block q_idx is
    q_pos_min + diag_off - window + 1."""
    first_k = (q_idx.astype(jnp.int32) * jnp.int32(bq)
               + jnp.int32(diag_off) - jnp.int32(window) + jnp.int32(1))
    return jnp.clip(first_k // jnp.int32(block_k), jnp.int32(0),
                    jnp.int32(nblocks))


def _window_q_hi(k_idx, bk, diag_off, block_q, window, nblocks):
    """Exclusive upper bound on q-block index under a sliding window:
    the last query that sees any key of k block k_idx is
    k_pos_max + window - 1 - diag_off."""
    last_q = (k_idx.astype(jnp.int32) * jnp.int32(bk) + jnp.int32(bk)
              - jnp.int32(1) + jnp.int32(window) - jnp.int32(1)
              - jnp.int32(diag_off))
    return jnp.clip(last_q // jnp.int32(block_q) + jnp.int32(1),
                    jnp.int32(0), jnp.int32(nblocks))


def _normalize_startend(se, sq, sk, causal):
    """Normalize flashmask startend_row_indices (reference
    nn/functional/flash_attention.py:1098 shapes [b, h_se, sk, {1,2,4}])
    to FOUR per-column row bands [b, h_se, 4, sk] int32:
    key column j is masked for query rows in [lts[j], lte[j]) or
    [uts[j], ute[j]).

    C=1: LT-start -> [start, sq) (reference defines this for causal=True;
    accepted for causal=False too as the plain column-band superset);
    causal C=2: [start, end) ; non-causal C=2: LT [start, sq) plus
    UT [0, end) ; non-causal C=4: LT [s0, s1) plus UT [s2, s3).
    """
    se = jnp.asarray(se, jnp.int32)
    if se.ndim != 4 or se.shape[2] != sk:
        raise ValueError(
            f"startend_row_indices must be [batch, kv_heads, seq_k, C], "
            f"got {se.shape} (seq_k={sk})")
    C = se.shape[3]
    set_ = jnp.swapaxes(se, 2, 3)                   # [b, h_se, C, sk]
    zeros = jnp.zeros_like(set_[:, :, :1])
    full = jnp.full_like(set_[:, :, :1], sq)
    if C == 1:
        bands = [set_[:, :, 0:1], full, zeros, zeros]
    elif causal and C == 2:
        bands = [set_[:, :, 0:1], set_[:, :, 1:2], zeros, zeros]
    elif not causal and C == 2:
        bands = [set_[:, :, 0:1], full, zeros, set_[:, :, 1:2]]
    elif not causal and C == 4:
        bands = [set_[:, :, i:i + 1] for i in range(4)]
    else:
        raise ValueError(
            f"startend_row_indices last dim must be "
            f"{'1 or 2' if causal else '1, 2 or 4'} for causal={causal}, "
            f"got {C}")
    return jnp.concatenate(bands, axis=2)


def _flashmask_tile(s, q_start, se_tile, neg_inf):
    """Apply the normalized flashmask bands to a [BQ, BK] score tile
    whose rows start at q_start; se_tile is [4, BK] (lts/lte/uts/ute per
    key column). Shared by fwd and both bwd kernels."""
    bq, bk = s.shape
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    masked = jnp.logical_or(
        jnp.logical_and(q_pos >= se_tile[0:1, :], q_pos < se_tile[1:2, :]),
        jnp.logical_and(q_pos >= se_tile[2:3, :], q_pos < se_tile[3:4, :]))
    return jnp.where(masked, neg_inf, s)


def _flashmask_tile_full(se_tile, q_lo, q_hi):
    """Scalar predicate: every (row, column) of the [q_lo, q_hi) x tile
    region is masked — one of the two bands covers all rows for every
    column — so the whole tile (two MXU dots) can be skipped. This is
    the flashmask sparsity win: e.g. causal document masking skips every
    cross-document block."""
    lt = jnp.logical_and(jnp.max(se_tile[0:1, :]) <= q_lo,
                         jnp.min(se_tile[1:2, :]) >= q_hi)
    ut = jnp.logical_and(jnp.max(se_tile[2:3, :]) <= q_lo,
                         jnp.min(se_tile[3:4, :]) >= q_hi)
    return jnp.logical_or(lt, ut)


def _band_mask(s, q_start, k_start, diag_off, neg_inf, window=None):
    """Apply the bottom-right-aligned causal band to a [BQ, BK] score
    tile whose rows start at q_start and columns at k_start: query i
    attends key j iff i + diag_off >= j — and, under a sliding window,
    iff i + diag_off - j < window (Mistral-style local attention).
    Shared by all three kernels so fwd and bwd can never mask different
    patterns."""
    bq, bk = s.shape
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = q_pos + jnp.int32(diag_off) >= k_pos
    if window is not None:
        keep = jnp.logical_and(
            keep, q_pos + jnp.int32(diag_off) - k_pos < jnp.int32(window))
    return jnp.where(keep, s, neg_inf)


# rows with every key masked (causal with seq_q > seq_k) have lse pinned
# at ~NEG_INF; this threshold identifies them so fwd emits 0 (flash-attn
# v2 convention) and bwd assigns them zero probability mass instead of
# exp(s - lse) = 1 garbage
ROW_INVALID_LSE = NEG_INF / 2


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, causal, scale,
                      block_k, seq_k, seq_q, diag_off, window=None,
                      has_mask=False):
    """One (batch*head, q_block) program: stream K/V tiles, online softmax.

    Refs are VMEM tiles: q [BQ, D], k/v [S_k, D] (full K/V rows for this
    head), [se [4, S_k] flashmask row bands when has_mask], o [BQ, D],
    and — only when the call is being differentiated — lse
    [BQ, STAT_LANES] (row logsumexp, consumed by the bwd kernels).

    Causal masking is bottom-right aligned like the XLA fallback and
    flash-attn v2 (KV-cache decode convention): query i attends keys
    j <= i + (seq_k - seq_q); ``diag_off`` carries that offset.
    Flashmask tiles whose rows are fully covered by a band are SKIPPED
    (no dots), which is where the column-sparse mask pays off.
    """
    from jax.experimental import pallas as pl

    if has_mask:
        se_ref, o_ref, *maybe_lse = rest
    else:
        se_ref, (o_ref, *maybe_lse) = None, rest

    # pin every python-float constant to f32: x64 is enabled globally, so
    # weak f64 constants otherwise reach Mosaic and fail to lower
    q = q_ref[...].astype(jnp.float32) * jnp.float32(scale)
    bq, d = q.shape
    q_idx = pl.program_id(1)
    neg_inf = jnp.float32(NEG_INF)

    # online-softmax stats kept (bq, CARRY_LANES) with the row value
    # broadcast across lanes: loop carries must be full-lane-width
    # vectors — (bq, 1) carries fail Mosaic's 'func.return' legalization
    # on the loop region boundary (narrow intermediates inside the body
    # are fine).
    m = jnp.full((bq, CARRY_LANES), neg_inf, jnp.float32)
    l = jnp.zeros((bq, CARRY_LANES), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    nblocks = seq_k // block_k

    def body(i, carry):
        def compute(carry, se_tile=None):
            m_prev, l_prev, acc_prev = carry
            k_tile = k_ref[pl.ds(i * block_k, block_k), :].astype(
                jnp.float32)
            v_tile = v_ref[pl.ds(i * block_k, block_k), :].astype(
                jnp.float32)
            s = jax.lax.dot_general(
                q, k_tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bq, block_k]
            if causal:
                s = _band_mask(s, q_idx.astype(jnp.int32) * bq,
                               i * block_k, diag_off, neg_inf,
                               window=window)
            if se_tile is not None:
                s = _flashmask_tile(s, q_idx.astype(jnp.int32)
                                    * jnp.int32(bq), se_tile, neg_inf)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_cur[:, :1])
            alpha = jnp.exp(m_prev - m_cur)
            l_cur = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_cur = acc_prev * alpha[:, :1] + jax.lax.dot_general(
                p, v_tile, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_cur, l_cur, acc_cur

        if not has_mask:
            return compute(carry)
        se_tile = se_ref[:, pl.ds(i * block_k, block_k)]
        q_lo = q_idx.astype(jnp.int32) * jnp.int32(bq)
        return jax.lax.cond(
            _flashmask_tile_full(se_tile, q_lo, q_lo + jnp.int32(bq)),
            lambda c: c, lambda c: compute(c, se_tile), carry)

    # causal: only iterate k blocks that intersect the band (and, under
    # a sliding window, skip blocks entirely left of the window too)
    hi = _causal_k_hi(q_idx, bq, diag_off, block_k, nblocks) if causal \
        else jnp.int32(nblocks)
    lo = _window_k_lo(q_idx, bq, diag_off, block_k, window, nblocks) \
        if (causal and window is not None) else jnp.int32(0)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m, l, acc))
    l_safe = jnp.maximum(l, jnp.float32(1e-30))
    # fully-masked rows (causal, seq_q > seq_k) would otherwise emit the
    # mean of visited V (p = exp(s - m) = 1 when every s == m == NEG_INF)
    row_valid = m[:, :1] > jnp.float32(ROW_INVALID_LSE)
    o_ref[...] = jnp.where(row_valid, acc / l_safe[:, :1],
                           jnp.float32(0.0)).astype(o_ref.dtype)
    if maybe_lse:
        lse_ref = maybe_lse[0]
        lse = jnp.where(row_valid, (m + jnp.log(l_safe))[:, :1], neg_inf)
        lse_ref[...] = lse[:, :STAT_LANES].astype(lse_ref.dtype)


def _kv_index_map(h, h_kv):
    """Grid index (batch*q_head) → flat (batch*kv_head) block index.

    GQA/MQA: q head ``qh`` reads kv head ``qh // rep`` — the kernels
    never materialize the repeated K/V heads the way the XLA path (and
    the reference's repeat_interleave) must. Identity when h == h_kv.
    """
    if h == h_kv:
        return lambda i, j: (i, 0, 0)
    rep = h // h_kv
    return lambda i, j: ((i // h) * h_kv + (i % h) // rep, 0, 0)


def _flash_pallas_fwd(q, k, v, causal, scale, interpret=False,
                      want_lse=True, window=None, se=None):
    """q: [B, H, S, D], k/v: [B, H_kv, S, D] (H_kv divides H; GQA served
    in-kernel) → (out [B, H, S, D], lse [B*H, S, STAT_LANES]).

    want_lse=False (inference / non-differentiated primal) skips the lse
    output entirely — no extra HBM write; returns (out, None).
    se: normalized flashmask bands [B, H_se, 4, S_k] (H_se dividing H) —
    streamed per key tile, so mask memory stays O(S), never O(S^2).
    """
    from jax.experimental import pallas as pl

    b, h, sq, d = q.shape
    h_kv, sk = k.shape[1], k.shape[2]
    bq = _pick_block(BLOCK_Q, sq)
    bk = _pick_block(BLOCK_K, sk)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h_kv, sk, d)
    vr = v.reshape(b * h_kv, sk, d)
    kv_map = _kv_index_map(h, h_kv)

    kernel = functools.partial(_flash_fwd_kernel, causal=causal, scale=scale,
                               block_k=bk, seq_k=sk, seq_q=sq,
                               diag_off=sk - sq, window=window,
                               has_mask=se is not None)
    in_specs = [
        # None squeezes the batch*head dim so refs are [S, D] tiles
        pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, sk, d), kv_map),
        pl.BlockSpec((None, sk, d), kv_map),
    ]
    inputs = [qr, kr, vr]
    if se is not None:
        if se.shape[0] != b:          # batch-1 mask broadcast
            se = jnp.broadcast_to(se, (b,) + se.shape[1:])
        h_se = se.shape[1]
        in_specs.append(
            pl.BlockSpec((None, 4, sk), _kv_index_map(h, h_se)))
        inputs.append(se.reshape(b * h_se, 4, sk))
    out_specs = [pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0))]
    out_shape = [jax.ShapeDtypeStruct((b * h, sq, d), q.dtype)]
    if want_lse:
        out_specs.append(
            pl.BlockSpec((None, bq, STAT_LANES), lambda i, j: (i, j, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, sq, STAT_LANES), jnp.float32))
    with _x32_trace():
        res = pl.pallas_call(
            kernel,
            grid=(b * h, sq // bq),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            name="flash_fwd",
        )(*inputs)
    if want_lse:
        out, lse = res
        return out.reshape(b, h, sq, d), lse
    return res[0].reshape(b, h, sq, d), None


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2 recompute style)
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         *rest, causal, scale, block_k, seq_k, diag_off,
                         window=None, has_mask=False):
    """One (batch*head, q_block) program accumulating dQ.

    dS = P ∘ (dO·Vᵀ − Δ) with P = exp(S − lse), Δ = rowsum(dO ∘ O);
    dQ = scale · dS·K.
    """
    from jax.experimental import pallas as pl

    if has_mask:
        se_ref, dq_ref = rest
    else:
        se_ref, (dq_ref,) = None, rest

    q = q_ref[...].astype(jnp.float32)
    bq, d = q.shape
    q_idx = pl.program_id(1)
    neg_inf = jnp.float32(NEG_INF)
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[:, :1].astype(jnp.float32)       # [bq, 1]
    delta = delta_ref[:, :1].astype(jnp.float32)   # [bq, 1]
    qs = q * jnp.float32(scale)

    nblocks = seq_k // block_k

    def body(i, acc):
        def compute(acc, se_tile=None):
            k_tile = k_ref[pl.ds(i * block_k, block_k), :].astype(
                jnp.float32)
            v_tile = v_ref[pl.ds(i * block_k, block_k), :].astype(
                jnp.float32)
            s = jax.lax.dot_general(
                qs, k_tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bq, bk]
            if causal:
                s = _band_mask(s, q_idx.astype(jnp.int32) * bq,
                               i * block_k, diag_off, neg_inf,
                               window=window)
            if se_tile is not None:
                s = _flashmask_tile(s, q_idx.astype(jnp.int32)
                                    * jnp.int32(bq), se_tile, neg_inf)
            p = jnp.where(lse > jnp.float32(ROW_INVALID_LSE),
                          jnp.exp(s - lse), jnp.float32(0.0))
            dp = jax.lax.dot_general(
                do, v_tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bq, bk]
            ds = p * (dp - delta)
            return acc + jax.lax.dot_general(
                ds, k_tile, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if not has_mask:
            return compute(acc)
        se_tile = se_ref[:, pl.ds(i * block_k, block_k)]
        q_lo = q_idx.astype(jnp.int32) * jnp.int32(bq)
        return jax.lax.cond(
            _flashmask_tile_full(se_tile, q_lo, q_lo + jnp.int32(bq)),
            lambda a: a, lambda a: compute(a, se_tile), acc)

    hi = _causal_k_hi(q_idx, bq, diag_off, block_k, nblocks) if causal \
        else jnp.int32(nblocks)
    lo = _window_k_lo(q_idx, bq, diag_off, block_k, window, nblocks) \
        if (causal and window is not None) else jnp.int32(0)
    acc = jax.lax.fori_loop(
        lo, hi, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[...] = (acc * jnp.float32(scale)).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          *rest, causal, scale, block_q, seq_q,
                          diag_off, window=None, has_mask=False):
    """One (batch*head, k_block) program accumulating dK and dV.

    dV = Pᵀ·dO; dK = scale · dSᵀ·Q.
    """
    from jax.experimental import pallas as pl

    if has_mask:
        se_ref, dk_ref, dv_ref = rest
    else:
        se_ref, (dk_ref, dv_ref) = None, rest

    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    bk, d = k.shape
    k_idx = pl.program_id(1)
    neg_inf = jnp.float32(NEG_INF)
    se_tile = se_ref[...] if has_mask else None    # [4, bk]

    nblocks = seq_q // block_q

    def body(j, carry):
        def compute(carry):
            dk_acc, dv_acc = carry
            q_tile = q_ref[pl.ds(j * block_q, block_q), :].astype(
                jnp.float32)
            do_tile = do_ref[pl.ds(j * block_q, block_q), :].astype(
                jnp.float32)
            lse = lse_ref[pl.ds(j * block_q, block_q), :1].astype(
                jnp.float32)
            delta = delta_ref[pl.ds(j * block_q, block_q), :1].astype(
                jnp.float32)
            s = jax.lax.dot_general(
                q_tile * jnp.float32(scale), k,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bq, bk]
            if causal:
                s = _band_mask(s, j * block_q,
                               k_idx.astype(jnp.int32) * bk,
                               diag_off, neg_inf, window=window)
            if se_tile is not None:
                s = _flashmask_tile(s, j * jnp.int32(block_q), se_tile,
                                    neg_inf)
            p = jnp.where(lse > jnp.float32(ROW_INVALID_LSE),
                          jnp.exp(s - lse), jnp.float32(0.0))  # [bq, bk]
            dv_acc = dv_acc + jax.lax.dot_general(
                p, do_tile, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bk, d]
            dp = jax.lax.dot_general(
                do_tile, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bq, bk]
            ds = p * (dp - delta)
            dk_acc = dk_acc + jax.lax.dot_general(
                ds, q_tile, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bk, d]
            return dk_acc, dv_acc

        if not has_mask:
            return compute(carry)
        q_lo = j * jnp.int32(block_q)
        return jax.lax.cond(
            _flashmask_tile_full(se_tile, q_lo,
                                 q_lo + jnp.int32(block_q)),
            lambda c: c, compute, carry)

    # causal: q blocks entirely above the band see nothing; under a
    # sliding window, q blocks entirely past the window see nothing too
    lo = _causal_q_lo(k_idx, bk, diag_off, block_q, nblocks) if causal \
        else jnp.int32(0)
    hi = _window_q_hi(k_idx, bk, diag_off, block_q, window, nblocks) \
        if (causal and window is not None) else jnp.int32(nblocks)
    zeros = jnp.zeros((bk, d), jnp.float32)
    dk_acc, dv_acc = jax.lax.fori_loop(
        lo, hi, body, (zeros, zeros))
    dk_ref[...] = (dk_acc * jnp.float32(scale)).astype(dk_ref.dtype)
    dv_ref[...] = dv_acc.astype(dv_ref.dtype)


def _flash_pallas_bwd(q, k, v, do, lse, delta, causal, scale,
                      interpret=False, window=None, se=None):
    """q/do [B, H, S, D], k/v [B, H_kv, S, D] (lse/delta
    [B*H, S, STAT_LANES]) → dq, dk, dv (dk/dv in the k/v GQA shape).
    se: normalized flashmask bands [B, H_se, 4, S_k] or None."""
    from jax.experimental import pallas as pl

    b, h, sq, d = q.shape
    h_kv, sk = k.shape[1], k.shape[2]
    bq = _pick_block(BLOCK_Q, sq)
    bk = _pick_block(BLOCK_K, sk)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h_kv, sk, d)
    vr = v.reshape(b * h_kv, sk, d)
    dor = do.reshape(b * h, sq, d)
    kv_map = _kv_index_map(h, h_kv)
    if se is not None and se.shape[0] != b:   # batch-1 mask broadcast
        se = jnp.broadcast_to(se, (b,) + se.shape[1:])
    se_map = _kv_index_map(h, se.shape[1]) if se is not None else None
    ser = se.reshape(-1, 4, sk) if se is not None else None

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, causal=causal, scale=scale, block_k=bk,
        seq_k=sk, diag_off=sk - sq, window=window, has_mask=se is not None)
    dq_in_specs = [
        pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, sk, d), kv_map),
        pl.BlockSpec((None, sk, d), kv_map),
        pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, bq, STAT_LANES), lambda i, j: (i, j, 0)),
        pl.BlockSpec((None, bq, STAT_LANES), lambda i, j: (i, j, 0)),
    ]
    dq_inputs = [qr, kr, vr, dor, lse, delta]
    if se is not None:
        dq_in_specs.append(pl.BlockSpec((None, 4, sk), se_map))
        dq_inputs.append(ser)
    with _x32_trace():
        dq = pl.pallas_call(
            dq_kernel,
            grid=(b * h, sq // bq),
            in_specs=dq_in_specs,
            out_specs=pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
            out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            interpret=interpret,
            name="flash_dq",
        )(*dq_inputs)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, causal=causal, scale=scale, block_q=bq,
        seq_q=sq, diag_off=sk - sq, window=window, has_mask=se is not None)
    dkv_in_specs = [
        pl.BlockSpec((None, sq, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, bk, d),
                     lambda i, j, _m=kv_map: (_m(i, j)[0], j, 0)),
        pl.BlockSpec((None, bk, d),
                     lambda i, j, _m=kv_map: (_m(i, j)[0], j, 0)),
        pl.BlockSpec((None, sq, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, sq, STAT_LANES), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((None, sq, STAT_LANES), lambda i, j: (i, 0, 0)),
    ]
    dkv_inputs = [qr, kr, vr, dor, lse, delta]
    if se is not None:
        dkv_in_specs.append(
            pl.BlockSpec((None, 4, bk),
                         lambda i, j, _m=se_map: (_m(i, j)[0], 0, j)))
        dkv_inputs.append(ser)
    with _x32_trace():
        dk, dv = pl.pallas_call(
            dkv_kernel,
            grid=(b * h, sk // bk),
            in_specs=dkv_in_specs,
            # per-q-head partials: rep programs share a kv head, so each
            # writes its own (b*h)-indexed slot; the group-sum happens
            # below in fp32 (exactly what repeat_interleave's VJP does,
            # minus ever materializing repeated K/V in forward)
            out_specs=[
                pl.BlockSpec((None, bk, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, bk, d), lambda i, j: (i, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
                jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
            ],
            interpret=interpret,
            name="flash_dkv",
        )(*dkv_inputs)
    dq = dq.reshape(b, h, sq, d)
    if h_kv != h:
        rep = h // h_kv
        dk = dk.reshape(b, h_kv, rep, sk, d).astype(jnp.float32) \
            .sum(axis=2).astype(k.dtype)
        dv = dv.reshape(b, h_kv, rep, sk, d).astype(jnp.float32) \
            .sum(axis=2).astype(v.dtype)
        return dq, dk, dv
    return dq, dk.reshape(b, h, sk, d), dv.reshape(b, h, sk, d)


# ---------------------------------------------------------------------------
# custom_vjp wrapper: the trainable Pallas path
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_pallas(q, k, v, se, causal, scale, interpret=False, window=None):
    """q/k/v: [B, H, S, D] → out [B, H, S, D]; differentiable in q/k/v.
    se: normalized flashmask bands [B, H_se, 4, S_k] int32 or None."""
    # non-differentiated primal: skip the lse output (no HBM write)
    out, _ = _flash_pallas_fwd(q, k, v, causal, scale, interpret=interpret,
                               want_lse=False, window=window, se=se)
    return out


def _flash_vjp_fwd(q, k, v, se, causal, scale, interpret, window):
    out, lse = _flash_pallas_fwd(q, k, v, causal, scale,
                                 interpret=interpret, window=window, se=se)
    return out, (q, k, v, se, out, lse)


def _flash_vjp_bwd(causal, scale, interpret, window, res, g):
    q, k, v, se, out, lse = res
    b, h, sq, d = q.shape
    # Δ = rowsum(dO ∘ O) — cheap elementwise+reduce; XLA fuses it.
    # Same narrow layout the kernels read lse in.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(b * h, sq, STAT_LANES)
    dq, dk, dv = _flash_pallas_bwd(
        q, k, v, g, lse, delta, causal, scale, interpret=interpret,
        window=window, se=se)
    return dq, dk, dv, None


_flash_pallas.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# XLA reference path + public entry points
# ---------------------------------------------------------------------------

def _flash_xla(q, k, v, causal, scale, window=None, se=None):
    h = q.shape[1]
    if k.shape[1] != h:
        # GQA on the fallback path: XLA has to materialize the repeated
        # heads (the Pallas kernels index kv = qh // rep instead);
        # repeat's VJP sums the group's cotangents for free
        rep = h // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    sq, sk = logits.shape[-2], logits.shape[-1]
    out_mask = None
    if causal:
        # static-shape mask built host-side so the fully-masked-row test
        # below stays concrete under jit
        mask = np.tril(np.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            # sliding window: also drop keys more than `window`-1
            # positions behind the (band-aligned) diagonal
            mask &= ~np.tril(np.ones((sq, sk), bool),
                             k=sk - sq - int(window))
        logits = jnp.where(mask, logits, NEG_INF)
        out_mask = mask.any(-1)  # rows with no visible key (sq > sk)
    if se is not None:
        # flashmask (dense fallback): build the [*, *, sq, sk] boolean
        # mask from the normalized bands — O(S^2), which is exactly what
        # the Pallas path avoids; acceptable only here
        rows = jnp.arange(sq, dtype=jnp.int32)[None, None, :, None]
        lts, lte, uts, ute = (se[:, :, i][:, :, None, :]
                              for i in range(4))
        fm = ((rows >= lts) & (rows < lte)) | ((rows >= uts)
                                               & (rows < ute))
        if fm.shape[1] not in (1, h):
            fm = jnp.repeat(fm, h // fm.shape[1], axis=1)
        logits = jnp.where(fm, NEG_INF, logits)
        # row validity turns dynamic once the mask is data-dependent
        valid = (logits > jnp.float32(ROW_INVALID_LSE)).any(-1)
        p = jax.nn.softmax(logits.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
        return jnp.where(valid[..., None], out, jnp.zeros_like(out))
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    if out_mask is not None and not out_mask.all():
        # fully-masked rows: emit zeros like the Pallas kernel (flash-attn
        # v2 convention) instead of softmax's uniform average of V, so
        # the two routes agree
        out = jnp.where(out_mask[:, None], out, jnp.zeros_like(out))
    return out


def _head_dim_ok(d):
    # 128-granular head dims fill the lane registers outright; 64 (the
    # BERT-base geometry, 768/12) under-fills them but compiles for the
    # v5e (tests/test_tpu_compile.py keeps that true)
    return d % 128 == 0 or d == 64


def _tileable(sq, sk, d):
    # _pick_block halves down to any power-of-two divisor, so 128-granular
    # sequences always tile; head dim must fill the 128-lane registers
    # (or be the 64-wide BERT geometry)
    return (sq % 128 == 0 and sk % 128 == 0
            and _head_dim_ok(d) and sq >= 128 and sk >= 128)


def pallas_path_eligible(sq, sk, d):
    """Would `flash_attention_arrays` take the Pallas kernel for these
    sequence/head dims (absent force_pallas)? The ONE predicate shared
    with the entry point itself, so callers that attribute the path
    (nn.functional sdpa counters, bench telemetry) can never drift
    from the routing decision."""
    return bool(place.accelerator_available() and _tileable(sq, sk, d))


def _per_device(kernel, qt, kt, vt, se):
    """Run ``kernel(q, k, v, se)`` ([B, H, S, D] layout) under the
    active hybrid mesh.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so under a multi-device mesh the call IS wrapped: a
    shard_map manual over every mesh axis, batch split over the data
    axes and heads over 'mp' wherever they divide (what does not divide
    stays whole on every device), and each device runs the kernel on its
    own block. A single device, no mesh, or a caller that is already
    inside a manual region calls the kernel directly.
    """
    from jax.sharding import AbstractMesh, PartitionSpec

    from ..distributed import mesh as mesh_mod

    mesh = mesh_mod.get_mesh()
    if mesh is None or isinstance(mesh, AbstractMesh) or mesh.size == 1 \
            or jax.sharding.get_abstract_mesh().manual_axes:
        return kernel(qt, kt, vt, se)
    sizes = mesh_mod.mesh_axis_sizes(mesh)
    b, h_kv = qt.shape[0], kt.shape[1]
    batch_axes, n = [], 1
    for ax in ("dp", "sharding"):
        if sizes.get(ax, 1) > 1 and b % (n * sizes[ax]) == 0:
            batch_axes.append(ax)
            n *= sizes[ax]
    mp = sizes.get("mp", 1)
    # the q-head -> mask-head map survives a head split only when the
    # mask heads split with it (or there is one mask head)
    se_heads_ok = se is None or se.shape[1] == 1 or se.shape[1] % mp == 0
    heads = "mp" if mp > 1 and h_kv % mp == 0 and se_heads_ok else None
    batch = tuple(batch_axes) or None
    spec = PartitionSpec(batch, heads, None, None)
    se_spec = None if se is None else PartitionSpec(
        batch if se.shape[0] == b else None,
        heads if se.shape[1] > 1 else None, None, None)
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec, se_spec),
        out_specs=spec, check_vma=False)(qt, kt, vt, se)


def flash_attention_arrays(q, k, v, causal=False, scale=None,
                           force_pallas=False, interpret=False,
                           window=None, startend_row_indices=None):
    """Array-level entry (paddle layout [B, S, H, D]).

    GQA/MQA: k/v may carry fewer heads than q (H_kv dividing H) — the
    Pallas kernels serve the group by index (no repeated-K/V
    materialization, reference capability flash_attn GQA:
    paddle/phi/kernels/gpu/flash_attn_kernel.cu num_heads_k); the XLA
    fallback repeats internally.

    window: sliding-window (Mistral-style local) attention — each query
    sees at most the `window` most recent keys up to the causal
    diagonal. Requires causal=True; None = full attention.

    startend_row_indices: flashmask column-sparse mask
    [b, h_se, s_k, {1,2,4}] int32 (reference flashmask_attention,
    nn/functional/flash_attention.py:1098). On the Pallas path the
    bands stream per key tile (O(S) mask memory) and fully-masked
    tiles are skipped; the XLA fallback materializes the dense mask.
    """
    if k.shape[2] != v.shape[2]:
        raise ValueError(
            f"key heads ({k.shape[2]}) != value heads ({v.shape[2]})")
    if k.shape[2] < 1 or q.shape[2] % k.shape[2] != 0:
        raise ValueError(
            f"GQA requires query heads ({q.shape[2]}) to be a multiple "
            f"of key/value heads ({k.shape[2]})")
    if window is not None:
        window = int(window)
        if not causal:
            raise ValueError(
                "flash attention window requires causal=True (the "
                "window is measured back from the causal diagonal)")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    se = None
    if startend_row_indices is not None:
        if q.shape[1] != k.shape[1]:
            raise ValueError(
                "flashmask startend_row_indices requires seq_q == seq_k "
                f"(got {q.shape[1]} vs {k.shape[1]})")
        h_se = startend_row_indices.shape[1]
        if k.shape[2] % h_se != 0:
            raise ValueError(
                f"startend_row_indices heads ({h_se}) must divide kv "
                f"heads ({k.shape[2]})")
        se = _normalize_startend(startend_row_indices, q.shape[1],
                                 k.shape[1], causal)
    # backend platform, not array placement: tracers have no devices
    use_pallas = force_pallas or pallas_path_eligible(
        qt.shape[2], kt.shape[2], qt.shape[3])
    if use_pallas:
        out = _per_device(
            lambda q_, k_, v_, se_: _flash_pallas(
                q_, k_, v_, se_, causal, s, interpret, window),
            qt, kt, vt, se)
    else:
        out = _flash_xla(qt, kt, vt, causal, s, window=window, se=se)
    return jnp.swapaxes(out, 1, 2)


def flash_attention(query, key, value, causal=False, scale=None,
                    window=None, startend_row_indices=None):
    """Tensor-level entry used by nn.functional.flash_attention.
    ``window`` selects sliding-window (local) attention;
    ``startend_row_indices`` the flashmask column-sparse mask; see
    flash_attention_arrays."""
    def fn(q, k, v, *rest):
        return flash_attention_arrays(
            q, k, v, causal=causal, scale=scale, window=window,
            startend_row_indices=rest[0] if rest else None)
    args = [query, key, value] + (
        [startend_row_indices] if startend_row_indices is not None else [])
    return run_op("flash_attention", fn, args)
