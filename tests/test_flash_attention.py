"""Flash-attention Pallas kernel tests (interpret mode on the CPU mesh).

Reference test model: test/legacy_test/test_flash_attention.py (forward
vs naive attention + gradient checks against the unfused path). Here the
ground truth is the XLA einsum+softmax path, and the Pallas kernels run
in interpret mode so CI needs no TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.flash_attention import (_flash_pallas, _flash_xla,
                                                flash_attention_arrays)


def _mk(rng, b=1, h=2, s=256, d=128, dtype=np.float32):
    def one():
        return jnp.asarray(
            rng.standard_normal((b, h, s, d)).astype(dtype) * 0.3)
    return one(), one(), one()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_xla(rng, causal):
    q, k, v = _mk(rng)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = _flash_pallas(q, k, v, None, causal, scale, True)
    ref = _flash_xla(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_xla(rng, causal):
    q, k, v = _mk(rng)
    scale = 1.0 / np.sqrt(q.shape[-1])
    # weighted sum keeps the cotangent non-uniform across rows/cols
    w = jnp.asarray(rng.standard_normal(q.shape).astype(np.float32))

    def loss_pl(q, k, v):
        return jnp.sum(_flash_pallas(q, k, v, None, causal, scale, True) * w)

    def loss_xla(q, k, v):
        return jnp.sum(_flash_xla(q, k, v, causal, scale) * w)

    g_pl = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_pl, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3,
            err_msg=f"d{name} mismatch (causal={causal})")


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_rectangular(rng, causal):
    # cross-attention shape sq != sk; causal must be bottom-right aligned
    # (KV-cache decode convention) on BOTH paths
    q = jnp.asarray(rng.standard_normal((1, 2, 128, 128)).astype(np.float32)
                    * 0.3)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 128)).astype(np.float32)
                    * 0.3)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 128)).astype(np.float32)
                    * 0.3)
    scale = 1.0 / np.sqrt(128)

    def loss_pl(q, k, v):
        return jnp.sum(_flash_pallas(q, k, v, None, causal, scale, True) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(_flash_xla(q, k, v, causal, scale) ** 2)

    np.testing.assert_allclose(
        np.asarray(_flash_pallas(q, k, v, None, causal, scale, True)),
        np.asarray(_flash_xla(q, k, v, causal, scale)),
        rtol=2e-4, atol=2e-4)
    g_pl = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_pl, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


def test_flash_causal_sq_gt_sk(rng):
    """Bottom-right causal with seq_q > seq_k: rows attending zero keys
    emit 0 (flash-attn v2 convention) with zero, finite gradients —
    not exp(s - lse) = 1 garbage mass."""
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 128)).astype(np.float32)
                    * 0.3)
    k = jnp.asarray(rng.standard_normal((1, 2, 128, 128)).astype(np.float32)
                    * 0.3)
    v = jnp.asarray(rng.standard_normal((1, 2, 128, 128)).astype(np.float32)
                    * 0.3)
    scale = 1.0 / np.sqrt(128)
    out = _flash_pallas(q, k, v, None, True, scale, True)
    # diag_off = -128: rows 0..127 attend no keys -> exactly zero
    np.testing.assert_array_equal(np.asarray(out[:, :, :128]), 0.0)
    # rows 128.. attend keys 0..row-128; spot-check the last row, which
    # attends every key: plain softmax attention over all of k
    s_last = np.asarray(q[0, 0, -1] @ np.asarray(k[0, 0]).T) * scale
    p_last = np.exp(s_last - s_last.max())
    p_last /= p_last.sum()
    np.testing.assert_allclose(np.asarray(out[0, 0, -1]),
                               p_last @ np.asarray(v[0, 0]),
                               rtol=2e-4, atol=2e-4)

    def loss(q, k, v):
        return jnp.sum(_flash_pallas(q, k, v, None, True, scale, True) ** 2)

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in (gq, gk, gv):
        assert bool(jnp.all(jnp.isfinite(g)))
    # fully-masked rows contribute no gradient anywhere
    np.testing.assert_array_equal(np.asarray(gq[:, :, :128]), 0.0)


def test_flash_bf16_forward(rng):
    q, k, v = _mk(rng, dtype=np.float32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = _flash_pallas(q, k, v, None, True, scale, True)
    ref = _flash_xla(q, k, v, True, scale)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def test_force_pallas_trains(rng):
    """force_pallas=True path is trainable end-to-end (VERDICT item 2)."""
    q, k, v = _mk(rng, b=1, h=1, s=128, d=128)

    def step(q, k, v):
        # paddle layout [B, S, H, D]
        out = flash_attention_arrays(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), causal=True, force_pallas=True,
            interpret=True)
        return jnp.mean(out ** 2)

    val, grads = jax.value_and_grad(step, argnums=(0, 1, 2))(q, k, v)
    assert np.isfinite(float(val))
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))
        assert float(jnp.max(jnp.abs(g))) > 0


def test_kernel_failure_raises(rng, monkeypatch):
    """A kernel failure raises: no flag, no catch, no reroute to the
    XLA path — in the forward or when the VJP is pulled."""
    from paddle_tpu.kernels import flash_attention as mod

    def boom(*a, **kw):
        raise RuntimeError("mosaic exploded")

    q = jnp.ones((1, 128, 2, 128), jnp.float32)  # paddle layout [B,S,H,D]
    monkeypatch.setattr(mod, "_flash_pallas_bwd", boom)
    with pytest.raises(RuntimeError, match="mosaic exploded"):
        jax.grad(lambda x: mod.flash_attention_arrays(
            x, x, x, force_pallas=True, interpret=True).sum())(q)
    monkeypatch.setattr(mod, "_flash_pallas", boom)
    with pytest.raises(RuntimeError, match="mosaic exploded"):
        mod.flash_attention_arrays(q, q, q, force_pallas=True)


@pytest.mark.parametrize("window", [64, 128, 200, 256, 1000])
def test_flash_sliding_window_forward(rng, window):
    """Sliding-window (Mistral-style local) attention: the Pallas kernel
    matches the XLA masked reference for windows smaller than, equal to
    and larger than the block/sequence sizes (window >= seq == causal)."""
    q, k, v = _mk(rng, s=256)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = _flash_pallas(q, k, v, None, True, scale, True, window)
    ref = _flash_xla(q, k, v, True, scale, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    if window >= q.shape[2]:
        full = _flash_xla(q, k, v, True, scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [64, 192])
def test_flash_sliding_window_backward(rng, window):
    q, k, v = _mk(rng, s=256)
    scale = 1.0 / np.sqrt(q.shape[-1])

    def f_pallas(q, k, v):
        return jnp.sum(_flash_pallas(q, k, v, None, True, scale, True,
                                     window) ** 2)

    def f_xla(q, k, v):
        return jnp.sum(_flash_xla(q, k, v, True, scale,
                                  window=window) ** 2)

    gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(f_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-3, atol=3e-3)


def test_flash_window_entry_validation(rng):
    q, k, v = _mk(rng, s=128)
    q = jnp.swapaxes(q, 1, 2)
    k = jnp.swapaxes(k, 1, 2)
    v = jnp.swapaxes(v, 1, 2)
    with pytest.raises(ValueError, match="causal"):
        flash_attention_arrays(q, k, v, causal=False, window=64)
    with pytest.raises(ValueError, match=">= 1"):
        flash_attention_arrays(q, k, v, causal=True, window=0)
    # entry path with interpret + window runs end to end
    out = flash_attention_arrays(q, k, v, causal=True, window=64,
                                 force_pallas=True, interpret=True)
    assert out.shape == q.shape


@pytest.mark.parametrize("window", [32, 100, 160])
def test_flash_sliding_window_multiblock_bounds(rng, window, monkeypatch):
    """Shrunk 64x64 blocks over seq 256 give a 4x4 block grid, so the
    windowed k-loop lower bound (fwd/dq) and q-loop upper bound (dkv)
    actually skip blocks — gradients must still match the XLA mask."""
    import paddle_tpu.kernels.flash_attention as fa
    monkeypatch.setattr(fa, "BLOCK_Q", 64)
    monkeypatch.setattr(fa, "BLOCK_K", 64)
    q, k, v = _mk(rng, s=256)
    scale = 1.0 / np.sqrt(q.shape[-1])

    out = fa._flash_pallas(q, k, v, None, True, scale, True, window)
    ref = fa._flash_xla(q, k, v, True, scale, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    def f_pallas(q, k, v):
        return jnp.sum(fa._flash_pallas(q, k, v, None, True, scale, True,
                                        window) ** 2)

    def f_xla(q, k, v):
        return jnp.sum(fa._flash_xla(q, k, v, True, scale,
                                     window=window) ** 2)

    gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(f_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("h_kv", [1, 2])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_forward_matches_repeated(rng, causal, h_kv):
    """GQA/MQA: the kernel's kv-by-index path == attention against
    explicitly repeated K/V heads."""
    q, _, _ = _mk(rng, h=4)
    kg = jnp.asarray(rng.standard_normal(
        (1, h_kv, 256, 128)).astype(np.float32) * 0.3)
    vg = jnp.asarray(rng.standard_normal(
        (1, h_kv, 256, 128)).astype(np.float32) * 0.3)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = _flash_pallas(q, kg, vg, None, causal, scale, True)
    rep = 4 // h_kv
    ref = _flash_xla(q, jnp.repeat(kg, rep, axis=1),
                     jnp.repeat(vg, rep, axis=1), causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("h_kv", [1, 2])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_backward_matches_repeated(rng, causal, h_kv):
    """dk/dv come back in the GQA shape and equal the group-sum of the
    repeated-head gradients; dq matches per-head."""
    q, _, _ = _mk(rng, h=4)
    rep = 4 // h_kv
    kg = jnp.asarray(rng.standard_normal(
        (1, h_kv, 256, 128)).astype(np.float32) * 0.3)
    vg = jnp.asarray(rng.standard_normal(
        (1, h_kv, 256, 128)).astype(np.float32) * 0.3)
    scale = 1.0 / np.sqrt(q.shape[-1])
    w = jnp.asarray(rng.standard_normal(q.shape).astype(np.float32))

    def loss_pl(q, kg, vg):
        return jnp.sum(_flash_pallas(q, kg, vg, None, causal, scale, True) * w)

    def loss_ref(q, kg, vg):
        return jnp.sum(_flash_xla(q, jnp.repeat(kg, rep, axis=1),
                                  jnp.repeat(vg, rep, axis=1),
                                  causal, scale) * w)

    g_pl = jax.grad(loss_pl, argnums=(0, 1, 2))(q, kg, vg)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, kg, vg)
    assert g_pl[1].shape == (1, h_kv, 256, 128)
    for got, want, name in zip(g_pl, g_ref, ["dq", "dk", "dv"]):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3,
            err_msg=f"{name} mismatch (causal={causal})")


def test_flash_gqa_entry_validation(rng):
    q = jnp.zeros((1, 256, 4, 128), jnp.float32)   # paddle layout BSHD
    k = jnp.zeros((1, 256, 3, 128), jnp.float32)
    with pytest.raises(ValueError, match="multiple"):
        flash_attention_arrays(q, k, k, causal=True)


def test_public_functional_gqa_and_window(rng):
    """paddle.nn.functional.flash_attention TPU extensions: GQA head
    counts and the keyword-only sliding window."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    q = paddle.to_tensor(rng.standard_normal((1, 32, 4, 16)).astype(
        np.float32))
    kg = paddle.to_tensor(rng.standard_normal((1, 32, 2, 16)).astype(
        np.float32))
    out, sm = F.flash_attention(q, kg, kg, causal=True)
    assert list(out.shape) == [1, 32, 4, 16] and sm is None
    out_w, _ = F.flash_attention(q, kg, kg, causal=True, window=8)
    assert list(out_w.shape) == [1, 32, 4, 16]
    # windowed == full when the window covers the whole sequence
    out_full, _ = F.flash_attention(q, kg, kg, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(out_full.numpy()),
                               np.asarray(out.numpy()), rtol=1e-5,
                               atol=1e-6)
    with pytest.raises(ValueError, match="return_softmax"):
        F.flash_attention(q, kg, kg, causal=True, window=8,
                          return_softmax=True)
    # return_softmax yields the [B, H, Sq, Sk] probability matrix (GQA
    # heads repeated), causal rows summing to 1
    _, sm2 = F.flash_attention(q, kg, kg, causal=True,
                               return_softmax=True)
    p = np.asarray(sm2.numpy())
    assert p.shape == (1, 4, 32, 32)
    np.testing.assert_allclose(p.sum(-1), 1.0, rtol=1e-5)
    assert np.allclose(np.triu(p[0, 0], 1), 0, atol=1e-6)


def test_paged_attention_matches_dense(rng):
    """Paged-KV decode (block tables over a page pool) == dense masked
    attention over each sequence's contiguous KV, incl. GQA and ragged
    context lengths; paged_write lands the token where paged_attention
    reads it."""
    from paddle_tpu.kernels.paged_attention import (paged_attention_arrays,
                                                    paged_write_arrays)

    b, h, h_kv, d, bs, max_blocks = 2, 4, 2, 8, 4, 3
    nb = 8
    rep = h // h_kv
    # head-major page pool [nb, h_kv, bs, d]
    kc = jnp.asarray(rng.standard_normal((nb, h_kv, bs, d)).astype(
        np.float32))
    vc = jnp.asarray(rng.standard_normal((nb, h_kv, bs, d)).astype(
        np.float32))
    # seq 0 uses pages [5, 1, 2] with 9 tokens; seq 1 pages [0, 7, 3],
    # 5 tokens
    bt = jnp.asarray(np.array([[5, 1, 2], [0, 7, 3]], np.int32))
    cl = jnp.asarray(np.array([9, 5], np.int32))
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))

    out = np.asarray(paged_attention_arrays(q, kc, vc, bt, cl))

    for s in range(b):
        L = int(cl[s])
        k_seq = np.concatenate(
            [np.asarray(kc)[int(p)].transpose(1, 0, 2)
             for p in bt[s]])[:L]
        v_seq = np.concatenate(
            [np.asarray(vc)[int(p)].transpose(1, 0, 2)
             for p in bt[s]])[:L]
        k_rep = np.repeat(k_seq, rep, axis=1)       # [L, h, d]
        v_rep = np.repeat(v_seq, rep, axis=1)
        logits = np.einsum("hd,Lhd->hL", np.asarray(q)[s],
                           k_rep) / np.sqrt(d)
        p_ = np.exp(logits - logits.max(-1, keepdims=True))
        p_ /= p_.sum(-1, keepdims=True)
        want = np.einsum("hL,Lhd->hd", p_, v_rep)
        np.testing.assert_allclose(out[s], want, rtol=1e-4, atol=1e-5,
                                   err_msg=f"seq {s}")

    # write this step's k/v at each sequence's next position, then
    # attend with context_lens+1: the new token must be visible
    k_new = jnp.asarray(rng.standard_normal((b, h_kv, d)).astype(
        np.float32))
    v_new = jnp.asarray(rng.standard_normal((b, h_kv, d)).astype(
        np.float32))
    kc2, vc2 = paged_write_arrays(k_new, v_new, kc, vc, bt, cl)
    out2 = np.asarray(paged_attention_arrays(q, kc2, vc2, bt, cl + 1))
    # seq 0 pos 9 -> page bt[0, 2]=2 slot 1; seq 1 pos 5 -> page 7 slot 1
    assert np.allclose(np.asarray(kc2)[2, :, 1], np.asarray(k_new)[0])
    assert np.allclose(np.asarray(kc2)[7, :, 1], np.asarray(k_new)[1])
    assert not np.allclose(out2, out)   # the new token changed attention


def test_paged_attention_validation(rng):
    from paddle_tpu.kernels.paged_attention import paged_attention_arrays
    q = jnp.zeros((1, 4, 8), jnp.float32)
    kc = jnp.zeros((2, 3, 4, 8), jnp.float32)   # 3 kv heads !| 4
    bt = jnp.zeros((1, 1), jnp.int32)
    cl = jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="multiple"):
        paged_attention_arrays(q, kc, kc, bt, cl)


def test_paged_attention_padded_and_capacity(rng):
    """Padded slots (context_len 0) emit zeros; an over-capacity write
    raises instead of silently clipping into the last page."""
    from paddle_tpu.kernels.paged_attention import (paged_attention_arrays,
                                                    paged_write_arrays)
    b, h, h_kv, d, bs = 2, 4, 2, 8, 4
    # head-major pool [nb, h_kv, bs, d]
    kc = jnp.asarray(rng.standard_normal((4, h_kv, bs, d)).astype(
        np.float32))
    bt = jnp.asarray(np.array([[0, 1], [2, 3]], np.int32))
    cl = jnp.asarray(np.array([3, 0], np.int32))
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
    out = np.asarray(paged_attention_arrays(q, kc, kc, bt, cl))
    np.testing.assert_array_equal(out[1], 0.0)
    assert np.abs(out[0]).sum() > 0

    k1 = jnp.zeros((b, h_kv, d), jnp.float32)
    with pytest.raises(ValueError, match="capacity"):
        paged_write_arrays(k1, k1, kc, kc, bt,
                           jnp.asarray(np.array([8, 2], np.int32)))


def test_masked_multihead_attention_decode(rng):
    """incubate masked_multihead_attention (single-token decode vs a
    dense [2, b, h, L, d] cache): matches a numpy reference, writes
    this step's k/v at each sequence's position, honors bias and the
    additive src_mask, and supports per-sequence lengths."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn.functional import masked_multihead_attention

    b, h, d, L = 2, 2, 8, 6
    x = rng.standard_normal((b, 3 * h * d)).astype(np.float32)
    cache = rng.standard_normal((2, b, h, L, d)).astype(np.float32)
    bias = (rng.standard_normal((3, h, d)) * 0.1).astype(np.float32)
    lens = np.array([[3], [5]], np.int32)    # write positions per seq

    out, new_cache = masked_multihead_attention(
        paddle.to_tensor(x), paddle.to_tensor(cache.copy()),
        bias=paddle.to_tensor(bias),
        sequence_lengths=paddle.to_tensor(lens))
    out = np.asarray(out.numpy())
    nc = np.asarray(new_cache.numpy())

    qkv = x.reshape(b, 3, h, d) + bias[None]
    for s in range(b):
        pos = int(lens[s, 0])
        kref = cache[0, s].copy()
        vref = cache[1, s].copy()
        kref[:, pos] = qkv[s, 1]
        vref[:, pos] = qkv[s, 2]
        np.testing.assert_allclose(nc[0, s], kref, rtol=1e-5, atol=1e-6)
        logits = np.einsum("hd,hLd->hL", qkv[s, 0], kref) / np.sqrt(d)
        logits[:, pos + 1:] = -1e30
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("hL,hLd->hd", p, vref).reshape(h * d)
        np.testing.assert_allclose(out[s], want, rtol=1e-4, atol=1e-5,
                                   err_msg=f"seq {s}")

    # src_mask path: position from the mask length, additive bias on
    # visible slots
    mask = np.zeros((b, 1, 1, 4), np.float32)
    mask[0, ..., 1] = -1e30                  # hide slot 1 for seq 0
    out2, _ = masked_multihead_attention(
        paddle.to_tensor(x), paddle.to_tensor(cache.copy()),
        src_mask=paddle.to_tensor(mask))
    out2 = np.asarray(out2.numpy())
    qkv2 = x.reshape(b, 3, h, d)
    kref = cache[0, 0].copy(); vref = cache[1, 0].copy()
    kref[:, 3] = qkv2[0, 1]; vref[:, 3] = qkv2[0, 2]
    logits = np.einsum("hd,hLd->hL", qkv2[0, 0], kref) / np.sqrt(d)
    logits[:, 4:] = -1e30
    logits[:, 1] += -1e30
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want0 = np.einsum("hL,hLd->hd", p, vref).reshape(h * d)
    np.testing.assert_allclose(out2[0], want0, rtol=1e-4, atol=1e-5)

    import pytest as _pytest
    with _pytest.raises(NotImplementedError):
        masked_multihead_attention(paddle.to_tensor(x),
                                   paddle.to_tensor(cache.copy()),
                                   src_mask=paddle.to_tensor(mask),
                                   rotary_emb_dims=1)


def test_masked_multihead_attention_bounds(rng):
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn.functional import masked_multihead_attention

    x = paddle.to_tensor(rng.standard_normal((1, 3 * 2 * 8)).astype(
        np.float32))
    cache = paddle.to_tensor(rng.standard_normal((2, 1, 2, 4, 8)).astype(
        np.float32))
    with pytest.raises(ValueError, match="max_seq_len"):
        masked_multihead_attention(
            x, cache, sequence_lengths=paddle.to_tensor(
                np.array([[4]], np.int32)))


# ---------------------------------------------------------------------------
# flashmask (column-sparse startend_row_indices) kernel tests
# ---------------------------------------------------------------------------

def _doc_mask_indices(s, bounds, h=1):
    """Causal document mask (the flashmask flagship pattern): tokens of
    document [lo, hi) must not attend outside it. LT-start: for key j in
    [lo, hi), queries >= hi are masked."""
    idx = np.zeros((1, h, s, 1), np.int32)
    for lo, hi in bounds:
        idx[:, :, lo:hi, 0] = hi
    return idx


@pytest.mark.parametrize("causal", [True, False])
def test_flashmask_pallas_matches_dense(rng, causal):
    """Interpret-mode Pallas flashmask (fwd + all grads) matches the XLA
    dense-mask path exactly — the VERDICT r4 acceptance check."""
    from paddle_tpu.kernels.flash_attention import _normalize_startend

    q, k, v = _mk(rng, s=256)
    scale = 1.0 / np.sqrt(q.shape[-1])
    se_raw = jnp.asarray(_doc_mask_indices(256, [(0, 100), (100, 256)]))
    se = _normalize_startend(se_raw, 256, 256, causal)
    w = jnp.asarray(rng.standard_normal(q.shape).astype(np.float32))

    out = _flash_pallas(q, k, v, se, causal, scale, True)
    ref = _flash_xla(q, k, v, causal, scale, se=se)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    def loss_pl(q, k, v):
        return jnp.sum(_flash_pallas(q, k, v, se, causal, scale, True) * w)

    def loss_xla(q, k, v):
        return jnp.sum(_flash_xla(q, k, v, causal, scale, se=se) * w)

    g_pl = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_pl, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3,
            err_msg=f"d{name} mismatch (causal={causal})")


def test_flashmask_band_and_bidirectional(rng):
    """C=2 causal band, C=2 non-causal (LT+UT), and C=4 two-band forms
    all match a brute-force dense mask."""
    from paddle_tpu.kernels.flash_attention import _normalize_startend

    s = 128
    q, k, v = _mk(rng, s=s)
    scale = 1.0 / np.sqrt(q.shape[-1])

    def dense_ref(masked_bool, causal):
        logits = np.einsum("bhqd,bhkd->bhqk", np.asarray(q),
                           np.asarray(k)) * scale
        keep = ~np.broadcast_to(masked_bool, logits.shape)
        if causal:
            keep = keep & np.tril(np.ones((s, s), bool))[None, None]
        logits = np.where(keep, logits, -1e30)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out = np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v))
        # fully-masked rows emit 0 (flash-attn v2 convention)
        return np.where(keep.any(-1)[..., None], out, 0.0)

    rows = np.arange(s)[:, None]
    start = rng.integers(s // 2, s, s).astype(np.int32)
    end = np.minimum(start + 20, s).astype(np.int32)

    # causal C=2 band
    se_raw = jnp.asarray(
        np.stack([start, end], -1).reshape(1, 1, s, 2))
    se = _normalize_startend(se_raw, s, s, True)
    out = _flash_pallas(q, k, v, se, True, scale, True)
    masked = (rows >= start[None, :]) & (rows < end[None, :])
    np.testing.assert_allclose(
        np.asarray(out), dense_ref(masked[None, None], True),
        rtol=2e-4, atol=2e-4)

    # non-causal C=2: LT [start, s) + UT [0, ut_end)
    ut_end = rng.integers(0, s // 2, s).astype(np.int32)
    se_raw = jnp.asarray(
        np.stack([start, ut_end], -1).reshape(1, 1, s, 2))
    se = _normalize_startend(se_raw, s, s, False)
    out = _flash_pallas(q, k, v, se, False, scale, True)
    masked = (rows >= start[None, :]) | (rows < ut_end[None, :])
    np.testing.assert_allclose(
        np.asarray(out), dense_ref(masked[None, None], False),
        rtol=2e-4, atol=2e-4)

    # non-causal C=4: LT [s0, s1) + UT [s2, s3)
    s0, s1 = start, end
    s2 = ut_end
    s3 = np.minimum(s2 + 10, s).astype(np.int32)
    se_raw = jnp.asarray(
        np.stack([s0, s1, s2, s3], -1).reshape(1, 1, s, 4))
    se = _normalize_startend(se_raw, s, s, False)
    out = _flash_pallas(q, k, v, se, False, scale, True)
    masked = ((rows >= s0[None, :]) & (rows < s1[None, :])) | \
             ((rows >= s2[None, :]) & (rows < s3[None, :]))
    np.testing.assert_allclose(
        np.asarray(out), dense_ref(masked[None, None], False),
        rtol=2e-4, atol=2e-4)


def test_flashmask_gqa_broadcast_heads(rng):
    """startend_row_indices with h_se=1 broadcasts over GQA kv heads on
    the Pallas path (grads included)."""
    from paddle_tpu.kernels.flash_attention import _normalize_startend

    s = 128
    q, _, _ = _mk(rng, h=4, s=s)
    k = jnp.asarray(rng.standard_normal((1, 2, s, 128)).astype(np.float32)
                    * 0.3)
    v = jnp.asarray(rng.standard_normal((1, 2, s, 128)).astype(np.float32)
                    * 0.3)
    scale = 1.0 / np.sqrt(128)
    se_raw = jnp.asarray(_doc_mask_indices(s, [(0, 60), (60, s)]))
    se = _normalize_startend(se_raw, s, s, True)

    def loss_pl(q, k, v):
        return jnp.sum(_flash_pallas(q, k, v, se, True, scale, True) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(_flash_xla(q, k, v, True, scale, se=se) ** 2)

    np.testing.assert_allclose(
        np.asarray(_flash_pallas(q, k, v, se, True, scale, True)),
        np.asarray(_flash_xla(q, k, v, True, scale, se=se)),
        rtol=2e-4, atol=2e-4)
    g_pl = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_pl, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


def test_flashmask_block_skip_multiblock(rng, monkeypatch):
    """With 64-wide blocks and a two-document mask, cross-document tiles
    are fully masked and SKIPPED in-kernel — results must still match
    the dense path (fwd + grads), proving the skip predicate is safe."""
    import paddle_tpu.kernels.flash_attention as fa

    monkeypatch.setattr(fa, "BLOCK_Q", 64)
    monkeypatch.setattr(fa, "BLOCK_K", 64)
    s = 256
    q, k, v = _mk(rng, s=s)
    scale = 1.0 / np.sqrt(q.shape[-1])
    # documents [0,128) and [128,256): every (q>=128, k<128) tile is
    # fully masked -> whole 64x64 tiles skip
    se_raw = jnp.asarray(_doc_mask_indices(s, [(0, 128), (128, s)]))
    se = fa._normalize_startend(se_raw, s, s, True)

    out = fa._flash_pallas(q, k, v, se, True, scale, True)
    ref = fa._flash_xla(q, k, v, True, scale, se=se)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    def loss(q, k, v):
        return jnp.sum(fa._flash_pallas(q, k, v, se, True, scale,
                                        True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(fa._flash_xla(q, k, v, True, scale, se=se) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g, gr):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)
    # cross-document attention must be exactly zero: rows of doc 2 must
    # not read any doc-1 V — verify by zeroing doc-1 V and comparing
    out2 = fa._flash_pallas(q, k, v.at[:, :, :128].set(0.0), se, True,
                            scale, True)
    np.testing.assert_allclose(np.asarray(out2[:, :, 128:]),
                               np.asarray(out[:, :, 128:]),
                               rtol=1e-5, atol=1e-6)


def test_flashmask_functional_no_dense_mask(rng):
    """nn.functional.flashmask_attention routes through the kernel entry:
    O(S) mask memory on the Pallas path and reference shapes accepted."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    s = 128
    q = paddle.to_tensor(
        rng.standard_normal((1, s, 2, 128)).astype(np.float32))
    se = paddle.to_tensor(_doc_mask_indices(s, [(0, 50), (50, s)]))
    out = F.flashmask_attention(q, q, q, startend_row_indices=se,
                                causal=True)
    assert tuple(out.shape) == (1, s, 2, 128)
    # doc-mask semantics: query in doc 2 ignores doc-1 keys entirely
    qa = np.swapaxes(np.asarray(q.numpy()), 1, 2)
    scores = np.einsum("bhqd,bhkd->bhqk", qa, qa) / np.sqrt(128)
    tri = np.tril(np.ones((s, s), bool))
    dm = np.zeros((s, s), bool)
    dm[50:, :50] = True
    scores = np.where(tri[None, None] & ~dm[None, None], scores, -1e30)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.swapaxes(np.einsum("bhqk,bhkd->bhqd", p, qa), 1, 2)
    np.testing.assert_allclose(np.asarray(out.numpy()), want,
                               rtol=2e-4, atol=2e-4)


def test_flashmask_per_kv_head_masks(rng):
    """h_se = h_kv > 1 with DIFFERENT masks per kv head exercises the
    nontrivial se index map ((i // h) * h_se + (i % h) // rep) in all
    three kernels — a head-indexing bug would mix masks across heads."""
    from paddle_tpu.kernels.flash_attention import _normalize_startend

    s = 128
    q, _, _ = _mk(rng, h=4, s=s)
    k = jnp.asarray(rng.standard_normal((1, 2, s, 128)).astype(np.float32)
                    * 0.3)
    v = jnp.asarray(rng.standard_normal((1, 2, s, 128)).astype(np.float32)
                    * 0.3)
    scale = 1.0 / np.sqrt(128)
    # head 0: docs [0,40)+[40,s); head 1: docs [0,90)+[90,s)
    idx = np.concatenate([
        _doc_mask_indices(s, [(0, 40), (40, s)]),
        _doc_mask_indices(s, [(0, 90), (90, s)]),
    ], axis=1)
    se = _normalize_startend(jnp.asarray(idx), s, s, True)

    out = _flash_pallas(q, k, v, se, True, scale, True)
    ref = _flash_xla(q, k, v, True, scale, se=se)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    def loss_pl(q, k, v):
        return jnp.sum(_flash_pallas(q, k, v, se, True, scale, True) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(_flash_xla(q, k, v, True, scale, se=se) ** 2)

    g_pl = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_pl, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


def test_paged_decode_pallas_matches_gather(rng):
    """The Pallas paged-decode kernel (scalar-prefetched block tables,
    interpret mode) matches the XLA gather path exactly, incl. GQA,
    permuted tables, ragged context lengths and a sliding window."""
    from paddle_tpu.kernels.paged_attention import (paged_attention_arrays,
                                                    paged_decode_pallas)

    b, h, h_kv, d, bs, nblocks = 3, 8, 4, 128, 8, 5
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
    kc = jnp.asarray(rng.standard_normal(
        (b * nblocks, h_kv, bs, d)).astype(np.float32))
    vc = jnp.asarray(rng.standard_normal(
        (b * nblocks, h_kv, bs, d)).astype(np.float32))
    bt = jnp.asarray(rng.permutation(b * nblocks).astype(
        np.int32).reshape(b, nblocks))
    cl = jnp.asarray(np.array([13, 29, 40], np.int32))

    ref = paged_attention_arrays(q, kc, vc, bt, cl)
    out = paged_decode_pallas(q, kc, vc, bt, cl, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    # windowed: only the last `window` positions stay visible
    win = 9
    L = nblocks * bs
    kk = jnp.swapaxes(jnp.take(kc, bt, axis=0), 2, 3).reshape(
        b, L, h_kv, d)
    vv = jnp.swapaxes(jnp.take(vc, bt, axis=0), 2, 3).reshape(
        b, L, h_kv, d)
    qg = q.reshape(b, h_kv, 2, d).astype(jnp.float32)
    logits = jnp.einsum("bgrd,bLgd->bgrL", qg,
                        kk.astype(jnp.float32)) * (d ** -0.5)
    kpos = jnp.arange(L)
    valid = (kpos[None] < cl[:, None]) & \
        ((cl[:, None] - 1 - kpos[None]) < win)
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, -1)
    want = jnp.einsum("bgrL,bLgd->bgrd", p,
                      vv.astype(jnp.float32)).reshape(b, h, d)
    got = paged_decode_pallas(q, kc, vc, bt, cl, window=win,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_paged_decode_pallas_int8_interpret(rng):
    """The int8 paged-decode kernel (per-slot scale refs, in-VMEM
    dequant) matches the XLA gather+dequant path, incl. GQA, permuted
    tables, ragged context lengths and a window — interpret mode, so
    the quantized kernel is tier-1-covered with no TPU."""
    from paddle_tpu.kernels.paged_attention import (paged_attention_arrays,
                                                    paged_decode_pallas,
                                                    paged_pallas_eligible)
    from paddle_tpu.quantization.functional import kv_quantize_arrays

    b, h, h_kv, d, bs, nblocks = 3, 8, 4, 128, 32, 5
    # interpret mode runs any geometry; the chip takes int8 pools only
    # with whole 128-lane scale rows
    assert paged_pallas_eligible(d, 128, jnp.int8)
    assert not paged_pallas_eligible(d, bs, jnp.int8)
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
    kq, ks = kv_quantize_arrays(jnp.asarray(rng.standard_normal(
        (b * nblocks, h_kv, bs, d)).astype(np.float32)))
    vq, vs = kv_quantize_arrays(jnp.asarray(rng.standard_normal(
        (b * nblocks, h_kv, bs, d)).astype(np.float32)))
    bt = jnp.asarray(rng.permutation(b * nblocks).astype(
        np.int32).reshape(b, nblocks))
    cl = jnp.asarray(np.array([13, 129, 160], np.int32))
    ref = paged_attention_arrays(q, kq, vq, bt, cl,
                                 k_scale=ks, v_scale=vs)
    out = paged_decode_pallas(q, kq, vq, bt, cl, interpret=True,
                              k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    # windowed: dequantized dense reference with the window band
    win, L, rep = 9, nblocks * bs, h // h_kv
    kk = jnp.swapaxes(jnp.take(kq.astype(jnp.float32)
                               * ks[..., None], bt, axis=0), 2, 3
                      ).reshape(b, L, h_kv, d)
    vv = jnp.swapaxes(jnp.take(vq.astype(jnp.float32)
                               * vs[..., None], bt, axis=0), 2, 3
                      ).reshape(b, L, h_kv, d)
    qg = q.reshape(b, h_kv, rep, d).astype(jnp.float32)
    logits = jnp.einsum("bgrd,bLgd->bgrL", qg, kk) * (d ** -0.5)
    kpos = jnp.arange(L)
    valid = (kpos[None] < cl[:, None]) & \
        ((cl[:, None] - 1 - kpos[None]) < win)
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    want = jnp.einsum("bgrL,bLgd->bgrd", jax.nn.softmax(logits, -1),
                      vv).reshape(b, h, d)
    got = paged_decode_pallas(q, kq, vq, bt, cl, window=win,
                              interpret=True, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # ineligible geometry must be reported, not crash downstream
    assert not paged_pallas_eligible(d, 16, jnp.int8)
    assert not paged_pallas_eligible(64, bs, jnp.float32)


def test_paged_decode_pallas_page_clamp_short_context(rng):
    """Contexts much shorter than the block table: the clamped index
    maps re-request the last live page for dead grid steps (no fresh
    HBM copy on device) and the liveness guard skips their compute —
    output must still match the full-gather reference exactly,
    including a context that ends mid-page and a 1-token context."""
    from paddle_tpu.kernels.paged_attention import (paged_attention_arrays,
                                                    paged_decode_pallas)

    b, h, h_kv, d, bs, nblocks = 3, 4, 4, 128, 8, 6
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
    kc = jnp.asarray(rng.standard_normal(
        (b * nblocks, h_kv, bs, d)).astype(np.float32))
    vc = jnp.asarray(rng.standard_normal(
        (b * nblocks, h_kv, bs, d)).astype(np.float32))
    bt = jnp.asarray(rng.permutation(b * nblocks).astype(
        np.int32).reshape(b, nblocks))
    cl = jnp.asarray(np.array([1, 5, 17], np.int32))   # 1, 1, 3 pages
    ref = paged_attention_arrays(q, kc, vc, bt, cl)
    out = paged_decode_pallas(q, kc, vc, bt, cl, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_generate_cache_impls_token_exact(rng):
    """dense / paged / rolling cache layouts produce IDENTICAL greedy
    tokens through the compiled generate() loop (windowed model)."""
    import paddle_tpu as paddle
    from paddle_tpu.text.generation import generate
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=64, hidden=64, layers=2, heads=4)
    cfg.sliding_window = 6
    net = LlamaForCausalLM(cfg)
    net.eval()
    ids = paddle.to_tensor(rng.integers(0, 64, (3, 9)).astype(np.int64))
    dense = np.asarray(generate(net, ids, 10,
                                cache_impl="dense").numpy())
    rolling = np.asarray(generate(net, ids, 10).numpy())   # auto
    paged = np.asarray(generate(net, ids, 10, cache_impl="paged",
                                page_size=4).numpy())
    np.testing.assert_array_equal(rolling, dense)
    np.testing.assert_array_equal(paged, dense)


# ---------------------------------------------------------------------------
# encoder SDPA routing: padding masks as flashmask column bands
# ---------------------------------------------------------------------------

def _sdpa_ref(q, k, v, mask=None):
    from paddle_tpu.nn.functional.attention import _sdpa_core
    return _sdpa_core(q, k, v, mask)


def test_sdpa_routes_maskless_through_flash_entry(rng):
    """F.scaled_dot_product_attention without a mask takes the flash
    entry (counter-visible, honestly attributed) and agrees with the
    old XLA core."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import monitor

    b, s, h, d = 2, 128, 4, 64
    q, k, v = (rng.standard_normal((b, s, h, d)).astype(np.float32) * 0.3
               for _ in range(3))
    # on the CPU CI host the flash entry's XLA fallback serves — the
    # counter must say so (pallas only when the kernel will really run)
    c = monitor.counter("kernels.flash.sdpa.xla")
    c0 = c.get()
    out = F.scaled_dot_product_attention(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v))
    assert c.get() == c0 + 1
    ref = _sdpa_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(out.numpy()), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mask_shape", ["b11s", "b1s"])
def test_sdpa_padding_mask_matches_xla_core(rng, mask_shape):
    """Boolean key/padding masks convert to flashmask bands and agree
    exactly with the dense-mask XLA core (rows with >= 1 visible key)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import monitor

    b, s, h, d = 2, 128, 4, 64
    q, k, v = (rng.standard_normal((b, s, h, d)).astype(np.float32) * 0.3
               for _ in range(3))
    keep4 = np.ones((b, 1, 1, s), bool)
    keep4[1, ..., -32:] = False
    mask = keep4 if mask_shape == "b11s" else keep4[:, :, 0, :]
    c = monitor.counter("kernels.flash.sdpa.xla_mask")
    c0 = c.get()
    out = F.scaled_dot_product_attention(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        attn_mask=paddle.to_tensor(mask))
    assert c.get() == c0 + 1
    ref = _sdpa_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                    jnp.asarray(keep4))
    np.testing.assert_allclose(np.asarray(out.numpy()), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_sdpa_row_structured_and_float_masks_stay_on_xla(rng):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import monitor

    b, s, h, d = 1, 128, 2, 64
    q, k, v = (rng.standard_normal((b, s, h, d)).astype(np.float32) * 0.3
               for _ in range(3))
    c = monitor.counter("kernels.flash.sdpa.xla_dense_mask")
    # additive float mask
    fmask = np.zeros((b, h, s, s), np.float32)
    fmask[..., -16:] = -1e9
    c0 = c.get()
    out_f = F.scaled_dot_product_attention(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        attn_mask=paddle.to_tensor(fmask))
    assert c.get() == c0 + 1
    # bool mask with a real query-row structure
    bmask = np.tril(np.ones((s, s), bool))[None, None]
    c0 = c.get()
    out_b = F.scaled_dot_product_attention(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        attn_mask=paddle.to_tensor(bmask))
    assert c.get() == c0 + 1
    ref_f = _sdpa_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                      jnp.asarray(fmask))
    ref_b = _sdpa_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                      jnp.asarray(bmask))
    np.testing.assert_allclose(np.asarray(out_f.numpy()),
                               np.asarray(ref_f), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_b.numpy()),
                               np.asarray(ref_b), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("d", [64, 128])
def test_bert_padding_mask_flash_pallas_matches_xla(rng, d):
    """The BERT geometry through the PALLAS kernel (interpret): a
    bidirectional padding mask expressed as C=1 bands, head_dim 64 and
    128, forward AND backward vs the dense-mask XLA core."""
    b, s, h = 2, 128, 2
    q, k, v = _mk(rng, b=b, h=h, s=s, d=d)
    keep = np.ones((b, 1, s), bool)
    keep[1, :, -48:] = False
    # raw flashmask C=1: masked column -> band [0, s); kept -> empty
    se_raw = jnp.asarray(
        np.where(keep[:, :, None, :].transpose(0, 1, 3, 2), s, 0),
        jnp.int32)
    qp = jnp.swapaxes(q, 1, 2)   # arrays entry takes [B, S, H, D]
    kp = jnp.swapaxes(k, 1, 2)
    vp = jnp.swapaxes(v, 1, 2)

    def flash(q_, k_, v_):
        return flash_attention_arrays(
            q_, k_, v_, causal=False, force_pallas=True, interpret=True,
            startend_row_indices=se_raw)

    out = flash(qp, kp, vp)
    dense_keep = jnp.asarray(keep)[:, None, None, 0, :]   # [b,1,1,s]
    ref = _sdpa_ref(qp, kp, vp, dense_keep)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # backward
    w = jnp.asarray(rng.standard_normal(qp.shape).astype(np.float32))
    gk_ = jax.grad(lambda *a: jnp.sum(flash(*a) * w),
                   argnums=(0, 1, 2))(qp, kp, vp)
    gr_ = jax.grad(lambda *a: jnp.sum(_sdpa_ref(*a, dense_keep) * w),
                   argnums=(0, 1, 2))(qp, kp, vp)
    for name, a_, b_ in zip("qkv", gk_, gr_):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3, err_msg=name)


def test_pallas_route_splits_over_the_mesh(rng):
    """GSPMD cannot partition a Mosaic kernel, so under a multi-device
    mesh the Pallas route runs inside a shard_map — batch over the data
    axes, heads over 'mp', each device its own block — and still agrees
    with the XLA route, forward and backward."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import mesh as mesh_mod

    prev = mesh_mod.get_mesh()
    mesh = mesh_mod.build_mesh({"sharding": 2, "mp": 2},
                               devices=jax.devices()[:4])
    mesh_mod.set_mesh(mesh)
    try:
        sh = NamedSharding(mesh, P("sharding", None, "mp", None))
        q, k, v = (jax.device_put(jnp.asarray(
            rng.standard_normal((4, 128, 4, 128)), jnp.float32), sh)
            for _ in range(3))

        def run(**route):
            return jax.jit(jax.value_and_grad(
                lambda q_, k_, v_: (flash_attention_arrays(
                    q_, k_, v_, causal=True, **route) ** 2).sum(),
                argnums=(0, 1, 2)))

        pallas = run(force_pallas=True, interpret=True)
        assert "shard_map" in str(jax.make_jaxpr(pallas)(q, k, v))
        (lp, gp), (lx, gx) = pallas(q, k, v), run()(q, k, v)
        np.testing.assert_allclose(float(lp), float(lx), rtol=1e-5)
        for a, b_ in zip(gp, gx):
            assert a.sharding.spec == sh.spec
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=2e-4)
    finally:
        mesh_mod._global_mesh = prev


def test_head_dim_gating():
    """_tileable admits 128-granular head dims and the 64-wide BERT
    geometry (both compile for the v5e: tests/test_tpu_compile.py);
    everything else stays XLA — a rule of geometry, no probe."""
    from paddle_tpu.kernels import flash_attention as fa

    assert fa._head_dim_ok(128) and fa._head_dim_ok(256)
    assert fa._head_dim_ok(64)
    assert fa._tileable(128, 128, 64)
    assert not fa._head_dim_ok(96) and not fa._head_dim_ok(32)
    assert not fa._tileable(128, 128, 96)


def test_sdpa_fully_masked_rows_emit_zeros(rng):
    """A sequence whose keys are ALL padded: the flash path emits zero
    rows (flash-attn v2 convention) instead of the XLA softmax NaN."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    b, s, h, d = 2, 128, 2, 64
    q, k, v = (rng.standard_normal((b, s, h, d)).astype(np.float32) * 0.3
               for _ in range(3))
    keep = np.ones((b, 1, 1, s), bool)
    keep[1] = False
    out = np.asarray(F.scaled_dot_product_attention(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        attn_mask=paddle.to_tensor(keep)).numpy())
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[1], 0.0)


def test_document_startend_helper_and_llama_mask(rng):
    """document_startend_row_indices + LlamaForCausalLM's
    attn_mask_startend_row_indices input: packed documents behave
    exactly like separate forwards (rotary scores are relative, so a
    block-diagonal doc mask makes each document position-independent),
    and a single spanning document reduces to plain causal."""
    import paddle_tpu
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    se = F.document_startend_row_indices([5, 3])
    np.testing.assert_array_equal(
        np.asarray(se.numpy())[0, 0, :, 0],
        [5, 5, 5, 5, 5, 8, 8, 8])
    with pytest.raises(ValueError, match="sum"):
        F.document_startend_row_indices([5, 3], total=9)

    paddle_tpu.seed(0)
    cfg = LlamaConfig.tiny(vocab=64, hidden=64, layers=2, heads=4)
    cfg.use_flash_attention = True
    net = LlamaForCausalLM(cfg)
    net.eval()
    ids = rng.integers(0, 64, (1, 16)).astype(np.int64)
    se16 = F.document_startend_row_indices([10, 6])
    out = net(paddle.to_tensor(ids), None, se16).numpy()
    a = net(paddle.to_tensor(ids[:, :10])).numpy()
    b = net(paddle.to_tensor(ids[:, 10:])).numpy()
    np.testing.assert_allclose(out[:, :10], a, atol=2e-5)
    np.testing.assert_allclose(out[:, 10:], b, atol=2e-5)
    one = net(paddle.to_tensor(ids), None,
              F.document_startend_row_indices([16])).numpy()
    plain = net(paddle.to_tensor(ids)).numpy()
    np.testing.assert_allclose(one, plain, atol=2e-5)


def test_llama_flashmask_train_step_fused_ce_recompute(rng):
    """The seq-8K bench path in miniature: TrainStep with fused
    lm-head+CE, recompute, and the document mask riding as a traced
    input — losses finite and decreasing, and the mask actually
    changes the loss (vs unmasked)."""
    import paddle_tpu
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(vocab=64, hidden=64, layers=2, heads=4)
    cfg.use_flash_attention = True
    cfg.fused_linear_ce = True
    cfg.fused_ce_chunks = 2
    cfg.recompute = True
    paddle_tpu.seed(1)
    net = LlamaForCausalLM(cfg)
    ids = paddle.to_tensor(rng.integers(0, 64, (2, 16)).astype(np.int64))
    labels = paddle.to_tensor(
        rng.integers(0, 64, (2, 16)).astype(np.int64))
    se = F.document_startend_row_indices([8, 8])
    opt = paddle_tpu.optimizer.AdamW(1e-3, parameters=net.parameters())
    step = paddle_tpu.jit.TrainStep(net, lambda out, lab: out, opt)
    l0 = float(step((ids, labels, se), labels).numpy())
    l1 = float(step((ids, labels, se), labels).numpy())
    assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0 + 1.0
    # masked vs unmasked forward losses differ (the mask is live)
    net.eval()
    lm = float(net(ids, labels, se).numpy())
    lu = float(net(ids, labels).numpy())
    assert abs(lm - lu) > 1e-6


def test_llama_flashmask_rejects_unsupported_combos(rng):
    from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM
    import paddle_tpu
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    paddle_tpu.seed(0)
    cfg = LlamaConfig.tiny(vocab=64, hidden=64, layers=1, heads=4)
    cfg.use_flash_attention = False
    net = LlamaForCausalLM(cfg)
    net.eval()
    ids = paddle.to_tensor(rng.integers(0, 64, (1, 8)).astype(np.int64))
    se = F.document_startend_row_indices([4, 4])
    with pytest.raises(ValueError, match="use_flash_attention"):
        net(ids, None, se)
    cfg2 = LlamaConfig.tiny(vocab=64, hidden=64, layers=1, heads=4)
    cfg2.sliding_window = 4
    cfg2.use_flash_attention = True
    net2 = LlamaForCausalLM(cfg2)
    net2.eval()
    with pytest.raises(ValueError, match="sliding_window"):
        net2(ids, None, se)
