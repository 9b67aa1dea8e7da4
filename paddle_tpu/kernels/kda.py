"""Gated delta rule with channel-wise decay (KDA): the recurrence of a
linear-attention layer that keeps, instead of a cache that grows with the
context, one float32 matrix ``S`` [d_k, d_v] a head:

    S' = diag(alpha_t) S_{t-1}                  alpha_t = exp(a_t), a_t <= 0
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T    (the delta rule)
    o_t = S_t^T q_t

``a`` is the log-decay a key channel (``[.., d_k]``), ``beta`` one scalar
a head (up to 2: the transition may have negative eigenvalues). Three
forms of the one recurrence:

* ``kda_step_arrays``: one token a sequence in XLA ops. The CPU's decode
  path and the formulation the tests hold the other two to.
* ``kda_decode``: the same step as a Pallas kernel over (slot, head
  block) that reads each ``S`` once and writes it once IN PLACE
  (``input_output_aliases``); a slot that is not decoding has its rows
  copied through unchanged. Memory-bound: 2 x 64 KB a head at d = 128.
* ``kda_chunked``: many tokens a sequence (prefill), ``chunk`` at a time:
  inside a chunk the delta rule is a unit lower-triangular system solved
  once, across chunks ``S`` is carried by a ``lax.scan``.

Overflow guard of the chunked form. With G_i the cumulative log-decay
inside a chunk, the pair weights exp(G_i - G_j) (j <= i) are <= 1, but
the factorised form exp(G_i) * exp(-G_j) that turns them into a matmul
overflows float32 once a channel decays by more than e^88 inside a
chunk, which 64 tokens of a strongly decaying channel reach. So a chunk
is cut into sub-blocks of ``sub`` tokens: a pair in two different
sub-blocks is factored around the LATER block's start (both exponents
<= 0, a matmul), a pair inside one sub-block is computed directly from
the clamped difference (no factor at all).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST

CHUNK = 64
SUB = 16


def kda_step_arrays(S, q, k, v, a, beta, alive):
    """One token a sequence. S [b, H, dk, dv] float32; q, k, a [b, H, dk];
    v [b, H, dv]; beta [b, H]; alive [b] bool. Returns (o [b, H, dv]
    float32, S'): rows of sequences that are not alive come back
    bit-identical and their o is zero."""
    q, k, v, a, beta = (x.astype(_F32) for x in (q, k, v, a, beta))
    Sd = S * jnp.exp(a)[..., None]
    pred = jnp.sum(Sd * k[..., None], axis=-2)
    u = beta[..., None] * (v - pred)
    Sn = Sd + k[..., None] * u[..., None, :]
    o = jnp.sum(Sn * q[..., None], axis=-2)
    keep = alive[:, None, None]
    return jnp.where(keep, o, 0.0), jnp.where(keep[..., None], Sn, S)


def _kda_decode_kernel(alive_ref, s_ref, qT_ref, kT_ref, dT_ref, v_ref,
                       b_ref, o_ref, s_out_ref, *, hb):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(alive_ref[i] > 0)
    def _step():
        for h in range(hb):
            k = kT_ref[:, h:h + 1]                       # [dk, 1]
            Sd = s_ref[h] * dT_ref[:, h:h + 1]           # decay the rows
            pred = jnp.sum(Sd * k, axis=0, keepdims=True)     # [1, dv]
            u = b_ref[h:h + 1, :] * (v_ref[h:h + 1, :] - pred)
            Sn = Sd + k * u
            s_out_ref[h] = Sn
            o_ref[h:h + 1, :] = jnp.sum(Sn * qT_ref[:, h:h + 1], axis=0,
                                        keepdims=True)

    @pl.when(alive_ref[i] <= 0)
    def _through():
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def kda_decode_requirements(heads, dk, dv):
    """Why a state of this geometry cannot take `kda_decode` (None when
    it can): the kernel tiles [dk, dv] float32 matrices, 8 or more heads
    a block."""
    if dk % 128 or dv % 128:
        return (f"head dims ({dk}, {dv}) must be multiples of the 128-lane "
                f"tile")
    if heads % 8:
        return f"{heads} heads are not a multiple of 8"
    return None


def kda_decode(S, q, k, v, a, beta, alive, heads_per_block=None,
               interpret=False):
    """`kda_step_arrays` as one Pallas call: grid (slot, head block),
    each block's matrices read once and written once in place (`S` is
    aliased to the second output; donate it). Same arguments, same
    returns."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .flash_attention import _x32_trace

    b, H, dk, dv = S.shape
    hb = int(heads_per_block or (16 if H % 16 == 0 else 8))
    if H % hb:
        raise ValueError(f"heads_per_block {hb} does not divide {H} heads")
    nhb = H // hb

    def cols(x):
        """[b, H, dk] -> [b, nhb, dk, hb]: a head's vector as a column,
        the block's heads side by side on the lanes."""
        return jnp.swapaxes(x.astype(_F32).reshape(b, nhb, hb, dk), 2, 3)

    col = pl.BlockSpec((None, None, dk, hb), lambda i, j, *_: (i, j, 0, 0))
    row = pl.BlockSpec((None, hb, dv), lambda i, j, *_: (i, j, 0))
    mat = pl.BlockSpec((None, hb, dk, dv), lambda i, j, *_: (i, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b, nhb),
        in_specs=[mat, col, col, col, row, row],
        out_specs=[row, mat])
    with _x32_trace():
        o, S2 = pl.pallas_call(
            functools.partial(_kda_decode_kernel, hb=hb),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((b, H, dv), _F32),
                       jax.ShapeDtypeStruct(S.shape, _F32)],
            # operand 1 (S, after the prefetched scalars) IS output 1
            input_output_aliases={1: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="kda_decode",
        )(jnp.asarray(alive, jnp.int32), S, cols(q), cols(k),
          cols(jnp.exp(a.astype(_F32))), v.astype(_F32),
          jnp.broadcast_to(beta.astype(_F32)[..., None], (b, H, dv)))
    return o, S2


def _solve_unit_lower(M, rhs, sub):
    """X with M X = rhs for unit lower-triangular M [.., C, C], rhs
    [.., C, r], by block forward substitution over sub-blocks of `sub`
    rows: the diagonal blocks are inverted in one batched triangular
    solve of `sub` rows (the solver walks a matrix row by row, so its
    time goes with the rows, not with the batch), the rest is matmuls."""
    C = M.shape[-1]
    n = C // sub
    lead = M.shape[:-2]
    Mb = M.reshape(lead + (n, sub, n, sub))
    diag = jnp.stack([Mb[..., i, :, i, :] for i in range(n)], axis=-3)
    inv = jax.lax.linalg.triangular_solve(
        diag, jnp.broadcast_to(jnp.eye(sub, dtype=M.dtype), diag.shape),
        left_side=True, lower=True, unit_diagonal=True)
    rb = rhs.reshape(lead + (n, sub, rhs.shape[-1]))
    xs = []
    for i in range(n):
        r = rb[..., i, :, :]
        for j in range(i):
            r = r - jnp.einsum("...ce,...er->...cr", Mb[..., i, :, j, :],
                               xs[j], precision=_HI)
        xs.append(jnp.einsum("...ce,...er->...cr", inv[..., i, :, :], r,
                             precision=_HI))
    return jnp.concatenate(xs, axis=-2)


def _chunk_terms(q, k, v, a, beta, sub):
    """What a chunk of C tokens contributes whatever state it starts
    from, for every (sequence, head, chunk) at once: q, k, a [.., C, dk],
    v [.., C, dv], beta [.., C]. Returns (U0, W, Aqk, q_lam, k_end,
    lam_end) with U = U0 - W S, o = q_lam S + Aqk U and
    S_end = lam_end[:, None] * S + k_end^T U for the state S the chunk
    starts from."""
    C, dk = q.shape[-2], q.shape[-1]
    n = C // sub
    lead = q.shape[:-2]
    G = jnp.cumsum(a, axis=-2)                           # [.., C, dk] <= 0
    Gs = G.reshape(lead + (n, sub, dk))
    ks = k.reshape(lead + (n, sub, dk))
    qs = q.reshape(lead + (n, sub, dk))
    # R_I: the cumulative log-decay at the end of sub-block I - 1
    R = jnp.concatenate([jnp.zeros_like(Gs[..., :1, -1, :]),
                         Gs[..., :-1, -1, :]], axis=-2)  # [.., n, dk]
    left = jnp.exp(Gs - R[..., None, :])                 # exponents <= 0
    # a pair in sub-blocks J < I, factored around R_I
    right = ks[..., None, :, :, :] * jnp.exp(jnp.minimum(
        R[..., :, None, None, :] - Gs[..., None, :, :, :], 0.0))
    off_kk = jnp.einsum("...icd,...ijed->...icje", ks * left, right,
                        precision=_HI)
    off_qk = jnp.einsum("...icd,...ijed->...icje", qs * left, right,
                        precision=_HI)
    # a pair inside one sub-block, directly
    w = jnp.exp(jnp.minimum(Gs[..., :, None, :] - Gs[..., None, :, :], 0.0))
    kw = ks[..., None, :, :] * w                         # [.., n, c, c, dk]
    dia_kk = jnp.sum(ks[..., :, None, :] * kw, axis=-1)
    dia_qk = jnp.sum(qs[..., :, None, :] * kw, axis=-1)
    blk = jnp.arange(n)
    later = (blk[:, None] > blk[None, :])[:, None, :, None]
    same = (blk[:, None] == blk[None, :])[:, None, :, None]

    def whole(off, dia):
        a_ = jnp.where(later, off, jnp.where(same, dia[..., None, :], 0.0))
        return a_.reshape(lead + (C, C))

    tok = jnp.arange(C)
    Akk = jnp.where(tok[:, None] > tok[None, :], whole(off_kk, dia_kk), 0.0)
    Aqk = jnp.where(tok[:, None] >= tok[None, :], whole(off_qk, dia_qk), 0.0)
    lam = jnp.exp(G)                                     # decay since S
    # (I + diag(beta) Akk) [U0, W] = diag(beta) [V, K lam]
    M = jnp.eye(C, dtype=_F32) + beta[..., None] * Akk
    rhs = beta[..., None] * jnp.concatenate([v, k * lam], axis=-1)
    sol = _solve_unit_lower(M, rhs, sub)
    U0, W = sol[..., :v.shape[-1]], sol[..., v.shape[-1]:]
    k_end = k * jnp.exp(G[..., -1:, :] - G)              # decay to the end
    return U0, W, Aqk, q * lam, k_end, lam[..., -1, :]


def _carry_state(S, terms):
    """One chunk of the scan over chunks: the state in, (the state at
    the chunk's end, the chunk's outputs)."""
    U0, W, Aqk, q_lam, k_end, lam_end = terms
    U = U0 - jnp.einsum("...ck,...kv->...cv", W, S, precision=_HI)
    o = jnp.einsum("...ck,...kv->...cv", q_lam, S, precision=_HI) \
        + jnp.einsum("...ce,...ev->...cv", Aqk, U, precision=_HI)
    S2 = lam_end[..., None] * S \
        + jnp.einsum("...ck,...cv->...kv", k_end, U, precision=_HI)
    return S2, o


def kda_chunked(q, k, v, a, beta, S0, chunk=CHUNK, sub=SUB):
    """The recurrence over T tokens a sequence, `chunk` at a time.
    q, k, a [b, T, H, dk]; v [b, T, H, dv]; beta [b, T, H]; S0
    [b, H, dk, dv] float32. A token with a = 0 and beta = 0 leaves the
    state as it is (padding). What a chunk contributes is computed for
    all chunks at once; only the three products with the carried state
    run in the scan. Returns (o [b, T, H, dv] float32, S_T)."""
    b, T, H, dk = q.shape
    chunk = min(chunk, -(-T // sub) * sub)
    pad = -T % chunk
    N = (T + pad) // chunk

    def split(x):
        """[b, T, H, ...] -> [N, b, H, chunk, ...]"""
        x = x.astype(_F32)
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, N, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    terms = _chunk_terms(split(q), split(k), split(v), split(a),
                         split(beta[..., None])[..., 0], sub)
    S, o = jax.lax.scan(_carry_state, S0.astype(_F32), terms)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)        # [b, N, C, H, dv]
    return o.reshape(b, N * chunk, H, -1)[:, :T], S
