"""The Pallas grouped matmul under `kernels.moe.grouped_ffn_gated`
(`gmm`, K untiled) against `jax.lax.ragged_dot`, in interpret mode on small
shapes: where groups lie against the row tiles, what happens to rows in no
group, both row tiles, column tiles narrower than N; and the path the
CPU takes (three ragged_dots, counted, differentiable).

A case is (rows M, K, N, group sizes, column tile or None for
`gmm_tiles`'s own). M >= 512 takes the 128-row tile, M < 512 the 32-row
one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.incubate.distributed.models.moe import moe_layer
from paddle_tpu.kernels import moe


def _slab_sizes(counts, first, s):
    """`MoELayer._forward_sorted`'s clipped sizes: the part of each group
    that lies in the slab of `s` rows from row `first`."""
    ends = np.cumsum(counts)
    return list(np.clip(ends, first, first + s)
                - np.clip(ends - counts, first, first + s))


CASES = {
    "an-empty-group": (96, 128, 128, [20, 0, 30, 0, 0, 40], None),
    "a-group-inside-one-tile": (96, 128, 128, [0, 5, 0, 0], None),
    "a-group-over-three-tiles": (128, 128, 128, [10, 80, 6], None),
    "a-tile-holding-three-groups": (64, 128, 128, [34, 3, 4, 7], None),
    "rows-past-the-sum": (96, 128, 256, [3, 9, 2], None),
    "no-row-in-any-group": (64, 128, 128, [0, 0, 0], None),
    "sum-equals-m": (96, 128, 128, [32, 33, 31], None),
    "sum-equals-m-128-tile": (512, 128, 128, [128, 129, 127, 0, 128], None),
    "a-slab's-clipped-sizes": (
        96, 128, 128, _slab_sizes(np.array([50, 60, 10, 70, 30]), 96, 96),
        None),
    "the-last-slab's-clipped-sizes": (
        96, 128, 128, _slab_sizes(np.array([50, 60, 10, 70, 30]), 192, 96),
        None),
    "n-1280-scaled-down": (96, 256, 640, [7, 0, 50, 11], None),
    "n-in-five-column-tiles": (96, 256, 640, [7, 0, 50, 11], 128),
    "128-row-tile-many-groups": (640, 128, 256,
                                 [0, 200, 3, 1, 130, 0, 77, 128], None),
    "128-row-tile-two-column-tiles": (512, 256, 256, [300, 0, 100, 50], 128),
}


def _operands(m, k, n, groups, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    rows = jax.random.normal(keys[0], (m, k), jnp.bfloat16)
    return rows, [jax.random.normal(kk, (groups, k, n), jnp.bfloat16)
                  * k ** -0.5 for kk in keys[1:]]


def _f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("m,k,n,sizes,tn", CASES.values(), ids=CASES.keys())
def test_gmm_is_ragged_dot(m, k, n, sizes, tn):
    """One call with one matrix, and with two (silu(gate) * up): the rows
    of a group read as `ragged_dot` gives them (float32 over the whole K,
    one rounding: a last bit of bfloat16 may differ with the order of the
    sum), and no row outside a group is touched by another group's."""
    gs = jnp.asarray(sizes, jnp.int32)
    live = int(sum(sizes))
    assert live <= m
    rows, (w, w2) = _operands(m, k, n, len(sizes))
    tm = moe.gmm_row_tile(m)
    assert tm == (128 if m >= 512 else 32) and m % tm == 0
    meta = moe.gmm_metadata(gs, m, tm)
    visits = int(meta[3])
    spans = [(-(-e // tm) - (e - c) // tm) for e, c in
             zip(np.cumsum(sizes), sizes) if c]
    assert visits == sum(spans) <= m // tm + len(sizes) - 1
    dt = rows.dtype
    g = jax.lax.ragged_dot(rows, w, gs, preferred_element_type=dt)
    u = jax.lax.ragged_dot(rows, w2, gs, preferred_element_type=dt)
    one = moe.gmm(rows, (w,), meta, tn=tn, interpret=True)
    np.testing.assert_allclose(_f32(one[:live]), _f32(g[:live]),
                               rtol=2 ** -7, atol=2 ** -7)
    two = moe.gmm(rows, (w, w2), meta, tn=tn, interpret=True)
    mid = (jax.nn.silu(g.astype(jnp.float32))
           * u.astype(jnp.float32)).astype(dt)
    np.testing.assert_allclose(_f32(two[:live]), _f32(mid[:live]),
                               rtol=2 ** -6, atol=2 ** -6)


@pytest.mark.parametrize("m,sizes", [(96, [10, 0, 40, 5]),
                                     (512, [100, 200, 0, 60])],
                         ids=["32-row-tile", "128-row-tile"])
def test_the_kernel_path_zeroes_rows_in_no_group(m, sizes):
    """`grouped_ffn_gated` on the kernel path (interpreted: no TPU here):
    the ragged path's values on the live rows, zeros past the sum,
    `kernels.moe.gmm_pallas` bumped once, and the ragged formulation's
    derivative."""
    h, f = 128, 256
    rows, (w1, w3) = _operands(m, h, f, len(sizes))
    w2 = _operands(m, f, h, len(sizes), seed=1)[1][0]
    gs = jnp.asarray(sizes, jnp.int32)
    want = moe.grouped_ffn_gated(rows, w1, w3, w2, gs)
    used, fell = (monitor.counter("kernels.moe.gmm_pallas"),
                  monitor.counter("kernels.moe.gmm_fallback"))
    before = used.get(), fell.get()
    got = moe.grouped_ffn_gated(rows, w1, w3, w2, gs, interpret=True)
    assert (used.get(), fell.get()) == (before[0] + 1, before[1])
    live = sum(sizes)
    np.testing.assert_allclose(_f32(got[:live]), _f32(want[:live]),
                               rtol=2 ** -6, atol=2 ** -6)
    assert not _f32(got[live:]).any() and not _f32(want[live:]).any()

    def grads(**kw):
        return jax.grad(lambda *a: moe.grouped_ffn_gated(*a, gs, **kw).astype(
            jnp.float32).sum(), argnums=(0, 1, 2, 3))(rows, w1, w3, w2)
    for a, b in zip(grads(interpret=True), grads()):
        np.testing.assert_array_equal(_f32(a), _f32(b))


def test_the_cpu_path_is_counted_and_differentiable():
    rows, (w1, w3) = _operands(40, 16, 24, 3)
    w2 = _operands(40, 24, 16, 3, seed=1)[1][0]
    gs = jnp.asarray([10, 0, 21], jnp.int32)
    used, fell = (monitor.counter("kernels.moe.gmm_pallas"),
                  monitor.counter("kernels.moe.gmm_fallback"))
    before = used.get(), fell.get()
    out = moe.grouped_ffn_gated(rows, w1, w3, w2, gs)
    assert (used.get(), fell.get()) == (before[0], before[1] + 1)
    assert _f32(out[:31]).any() and not _f32(out[31:]).any()
    grads = jax.grad(lambda *a: moe.grouped_ffn_gated(*a, gs).astype(
        jnp.float32).sum(), argnums=(0, 1, 2, 3))(rows, w1, w3, w2)
    assert all(np.isfinite(_f32(g)).all() and _f32(g).any() for g in grads)
    # the middle expert has no row: no gradient reaches its matrices
    assert not _f32(grads[1][1]).any() and not _f32(grads[3][1]).any()


# rows, hidden, expert width -> the reason's first words, or None
@pytest.mark.parametrize("m,h,f,why", [
    (3200, 6144, 2048, None), (288, 6144, 2048, None),
    (224, 4096, 1280, None), (96, 5120, 1536, None),
    (100, 128, 128, "100 rows are not whole tiles of 32"),
    (576, 128, 128, "576 rows are not whole tiles of 128"),
    (96, 32, 128, "hidden width 32"), (96, 128, 16, "expert width 16")])
def test_gmm_requirements(m, h, f, why):
    got = moe.gmm_requirements(m, h, f)
    assert got is None if why is None else got.startswith(why)


def test_tiles_follow_the_slab_rule_and_the_vmem_budget():
    """The row tile is the one `_slab_rows` rounds a slab by; the column
    tile is all of N where the double-buffered blocks fit, else its
    widest lane-aligned divisor that does."""
    assert (moe.GMM_ROW_TILE, moe.GMM_SMALL_ROW_TILE, moe.GMM_SMALL_ROWS) == (
        moe_layer._SLAB_ROW_TILE, moe_layer._SLAB_SMALL_ROW_TILE,
        moe_layer._SLAB_SMALL)
    bf16 = jnp.bfloat16
    assert moe.gmm_tiles(3200, 6144, 2048, bf16)[:2] == (128, 2048)
    assert moe.gmm_tiles(3200, 6144, 2048, bf16, 2)[:2] == (128, 1024)
    assert moe.gmm_tiles(288, 2048, 6144, bf16)[:2] == (32, 6144)
    assert moe.gmm_tiles(2176, 4096, 1280, bf16, 2)[:2] == (128, 1280)
    assert moe.gmm_tiles(96, 5120, 1536, bf16, 2)[:2] == (32, 1536)
    for args in ((3200, 6144, 2048, bf16, 2), (4224, 1536, 5120, bf16)):
        assert moe.gmm_tiles(*args)[2] <= 100 << 20
