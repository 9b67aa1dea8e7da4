"""Selective state-space scan with a scalar decay a head (Mamba-2's SSD):
the recurrence of a state-space mixer that keeps, instead of a cache
that grows with the context, one float32 matrix ``S`` [P, N] a head
(P the head's width, N the state's):

    S_t = exp(a_t) S_{t-1} + (dt_t x_t) (outer) B_t      a_t = dt_t A <= 0
    y_t = S_t C_t

``dt`` (the step, after its softplus) and ``a`` (the log-decay) are one
scalar a head and token; ``B`` and ``C`` [N] belong to a GROUP of
heads: head h reads row h // (H / G). The skip ``D x`` and the gate are
the layer's, not the scan's. Two forms of the one recurrence:

* ``ssd_step_arrays``: one token a sequence in XLA ops, the decode
  program's step on every platform. Memory-bound: 2 x 128 KB a head at
  [128, 256]. On the chip XLA makes it ONE fusion a call that reads each
  ``S`` once and writes it once where it lies when the caller donates
  it (``tests/test_tpu_compile.py`` holds that), at 80% of the HBM peak
  (PERF.md section 6, PR 40): there is no Pallas kernel for it.
* ``ssd_chunked``: many tokens a sequence (prefill), ``chunk`` at a
  time: inside a chunk the quadratic form ``(C B^T * L) (dt x)`` with L
  the decay between two tokens of the chunk, across chunks ``S`` is
  carried by a ``lax.scan``.

Overflow guard of the chunked form. With G_i the cumulative log-decay
inside a chunk, every weight the form needs is exp of a DIFFERENCE that
is <= 0: G_i - G_j between two tokens (j <= i), G_end - G_j to the
chunk's end, G_i from its start. The decay is one scalar a head, so the
[chunk, chunk] matrix of pair weights is computed directly from the
clamped difference; nothing is ever factored into exp(G_i) * exp(-G_j),
whose second factor overflows float32 once a head decays by more than
e^88 inside a chunk (``kernels/kda.py`` has to factor, a decay a
channel, and cuts its chunks into sub-blocks for it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST

CHUNK = 128


def ssd_step_arrays(S, x, dt, a, B, C, alive):
    """One token a sequence. S [b, H, P, N] float32; x [b, H, P]; dt, a
    [b, H]; B, C [b, G, N]; alive [b] bool. Returns (y [b, H, P]
    float32, S'): rows of sequences that are not alive come back
    bit-identical and their y is zero."""
    x, dt, a, B, C = (t.astype(_F32) for t in (x, dt, a, B, C))
    rep = S.shape[1] // B.shape[1]
    Bh, Ch = (jnp.repeat(t, rep, axis=1)[:, :, None, :] for t in (B, C))
    Sn = S * jnp.exp(a)[..., None, None] \
        + (dt[..., None] * x)[..., None] * Bh
    y = jnp.sum(Sn * Ch, axis=-1)
    keep = alive[:, None, None]
    return jnp.where(keep, y, 0.0), jnp.where(keep[..., None], Sn, S)


def _chunk_terms(x, dt, a, B, C):
    """What a chunk of Q tokens contributes whatever state it starts
    from, for every (sequence, chunk) at once: x [.., Q, G, r, P]; dt, a
    [.., Q, G, r]; B, C [.., Q, G, N]. Returns (y0, C_in, add, lam_end)
    with y = y0 + C_in S and S_end = lam_end S + add for the state S the
    chunk starts from."""
    Q = x.shape[-4]
    Gc = jnp.cumsum(a, axis=-3)                          # [.., Q, G, r] <= 0
    xd = x * dt[..., None]
    tok = jnp.arange(Q)
    # a pair of tokens j <= i: the decay between them, from the clamped
    # difference
    pair = jnp.exp(jnp.minimum(
        Gc[..., :, None, :, :] - Gc[..., None, :, :, :], 0.0))
    pair = jnp.where((tok[:, None] >= tok[None, :])[:, :, None, None],
                     pair, 0.0)                          # [.., Qi, Qj, G, r]
    cb = jnp.einsum("...ign,...jgn->...ijg", C, B, precision=_HI)
    y0 = jnp.einsum("...ijgr,...jgrp->...igrp", cb[..., None] * pair, xd,
                    precision=_HI)
    to_end = jnp.exp(Gc[..., -1:, :, :] - Gc)            # <= 1
    add = jnp.einsum("...jgrp,...jgn->...grpn", xd * to_end[..., None], B,
                     precision=_HI)
    # C_in S: the carried state seen from token i, decayed from the start
    return y0, (C, jnp.exp(Gc)), add, jnp.exp(Gc[..., -1, :, :])


def _carry_state(S, terms):
    """One chunk of the scan over chunks: the state in, (the state at
    the chunk's end, the chunk's outputs)."""
    y0, (C, from_start), add, lam_end = terms
    y = y0 + jnp.einsum("...ign,...grpn->...igrp", C, S,
                        precision=_HI) * from_start[..., None]
    return lam_end[..., None, None] * S + add, y


def ssd_chunked(x, dt, a, B, C, S0, chunk=CHUNK):
    """The recurrence over T tokens a sequence, `chunk` at a time.
    x [b, T, H, P]; dt, a [b, T, H]; B, C [b, T, G, N]; S0 [b, H, P, N]
    float32. A token with dt = 0 and a = 0 leaves the state as it is
    (padding). What a chunk contributes is computed for all chunks at
    once; only the two products with the carried state run in the scan.
    Returns (y [b, T, H, P] float32, S_T)."""
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    chunk = min(chunk, T)
    pad = -T % chunk
    n = (T + pad) // chunk

    def split(t, heads):
        """[b, T, ...] -> [n, b, chunk, ...], heads as (group, in it)"""
        t = t.astype(_F32)
        if heads:
            t = t.reshape(t.shape[:2] + (G, H // G) + t.shape[3:])
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape((b, n, chunk) + t.shape[2:]), 1, 0)

    terms = _chunk_terms(split(x, True), split(dt, True), split(a, True),
                         split(B, False), split(C, False))
    S, y = jax.lax.scan(_carry_state,
                        S0.astype(_F32).reshape(b, G, H // G, P, N), terms)
    y = jnp.moveaxis(y, 0, 1).reshape(b, n * chunk, H, P)[:, :T]
    return y, S.reshape(b, H, P, N)
