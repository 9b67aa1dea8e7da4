"""Ring attention — context parallelism over the 'sep' mesh axis.

The reference snapshot has NO ring/blockwise context parallelism
(SURVEY.md §2.3: "Not present — the TPU build should still implement CP
as a first-class axis"); its longest-sequence support is the SEP process
group + flashmask attention. This module supplies the missing capability
TPU-natively: q/k/v are sequence-sharded over 'sep', and each device
computes flash-style online-softmax partial attention against k/v blocks
that rotate around the ring via `lax.ppermute` (one ICI hop per step),
so no device ever materialises the full sequence — memory O(S/n) and
exact numerics (Liu et al., Ring Attention with Blockwise Transformers;
see PAPERS.md).

Layout: [batch, heads, seq, head_dim]; manual only over `axis` so batch/
head dims still shard over dp/mp via GSPMD.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _ring_local(axis: str, n: int, causal: bool, scale: float,
                window=None):
    """Per-device ring attention body (under shard_map manual on axis).

    window: sliding-window (local) attention — query i sees keys in
    [i - window + 1, i]. Applied as an extra band on the mask; hops
    whose k block lies entirely outside every local band still rotate
    (the ring is a fixed scan) but contribute nothing.
    """

    def local(q, k, v):
        # q: [b, h, s_local, d]; k/v: [b, h_kv, s_local, d] with h_kv
        # dividing h (GQA/MQA): only the GROUPED k/v rotate around the
        # ring, so ICI traffic shrinks by h/h_kv. The q heads of a group
        # fold into the row dim (attention rows are independent), which
        # keeps the body MHA-shaped.
        idx = lax.axis_index(axis)
        b, h, s_local, d = q.shape
        h_kv = k.shape[1]
        rep = h // h_kv
        in_dtype = q.dtype
        q32 = q.astype(jnp.float32) * scale
        pos_q = idx * s_local + jnp.arange(s_local)
        if rep > 1:
            q32 = q32.reshape(b, h_kv, rep * s_local, d)
            pos_q = jnp.tile(pos_q, rep)   # row r*s+j sits at pos_q[j]

        from ..distributed.collective_utils import varying
        acc0 = varying(jnp.zeros(q32.shape[:3] + (v.shape[3],),
                                 jnp.float32), axis)
        m0 = varying(jnp.full(q32.shape[:3], NEG_INF, jnp.float32), axis)
        l0 = varying(jnp.zeros(q32.shape[:3], jnp.float32), axis)

        def body(carry, step):
            kv_k, kv_v, acc, m, l = carry
            # the block now held arrived from rank (idx - step) % n
            src = (idx - step) % n
            pos_k = src * s_local + jnp.arange(s_local)
            s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                           kv_k.astype(jnp.float32))
            if causal:
                mask = pos_q[:, None] >= pos_k[None, :]
                if window is not None:
                    mask &= (pos_q[:, None] - pos_k[None, :]) < window
                s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard fully-masked rows (exp(NEG_INF - NEG_INF) would be 1)
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(s > NEG_INF * 0.5, p, 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, kv_v.astype(jnp.float32))
            from ..distributed.collective_utils import ring_perm
            perm = ring_perm(n)
            kv_k = lax.ppermute(kv_k, axis, perm)
            kv_v = lax.ppermute(kv_v, axis, perm)
            return (kv_k, kv_v, acc, m_new, l), None

        (_, _, acc, m, l), _ = lax.scan(
            body, (k, v, acc0, m0, l0), jnp.arange(n))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        if rep > 1:
            out = out.reshape(b, h, s_local, d)
        return out.astype(in_dtype)

    return local


def ring_attention_arrays(q, k, v, mesh=None, axis: str = "sep",
                          causal: bool = False,
                          scale: Optional[float] = None,
                          window: Optional[int] = None):
    """Exact attention with q/k/v sequence-sharded over `axis`.

    q,k,v: global [b, h, s, d] arrays (sharding on s over `axis` is
    committed by the shard_map specs). Differentiable; jax.grad reverses
    the ring (the cotangent blocks counter-rotate via ppermute's
    transpose). window: sliding-window local attention (requires
    causal=True, like the flash entry).
    """
    from ..distributed import mesh as mesh_mod
    mesh = mesh or mesh_mod.ensure_mesh()
    n = mesh.shape[axis] if axis in mesh.axis_names else 1
    if window is not None:
        window = int(window)
        if not causal:
            raise ValueError("ring attention window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if n <= 1:
        # flash_attention_arrays takes paddle layout [B, S, H, D]; we are
        # [B, H, S, D] here
        from .flash_attention import flash_attention_arrays
        out = flash_attention_arrays(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), causal=causal, scale=scale,
            window=window)
        return jnp.swapaxes(out, 1, 2)
    if q.shape[2] % n:
        raise ValueError(
            f"seq len {q.shape[2]} not divisible by {axis} degree {n}")
    if k.shape[1] != v.shape[1] or k.shape[1] < 1 \
            or q.shape[1] % k.shape[1] != 0:
        raise ValueError(
            f"GQA requires query heads ({q.shape[1]}) to be a multiple "
            f"of key/value heads ({k.shape[1]}, v {v.shape[1]})")
    spec = P(None, None, axis, None)
    fn = jax.shard_map(
        _ring_local(axis, n, causal, float(scale), window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={axis})
    return fn(q, k, v)


def ring_flash_attention(query, key, value, causal=False, scale=None,
                         axis="sep", window=None):
    """Tensor-level API ([b, s, h, d] like paddle flash_attention;
    transposed internally to [b, h, s, d])."""
    from ..core.dispatch import run_op

    def fn(q, k, v):
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        out = ring_attention_arrays(qt, kt, vt, axis=axis, causal=causal,
                                    scale=scale, window=window)
        return jnp.swapaxes(out, 1, 2)

    return run_op("ring_flash_attention", fn, [query, key, value])
