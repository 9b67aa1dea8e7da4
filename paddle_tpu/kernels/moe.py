"""Fused MoE grouped-matmul: Pallas TPU kernels (fwd + bwd) with an XLA
reference path.

Reference capability: paddle/phi/kernels/fusion/cutlass/fused_moe_kernel.cu
(the grouped-GEMM expert FFN behind the reference's fused MoE path).
TPU-native design (docs/KERNELS.md): tokens arrive already sorted by
expert — the routing scatter lands them in the per-expert capacity
buffer ``x [E, C, h]`` — and ONE blocked kernel runs the whole
two-matmul expert FFN over that buffer:

* grid ``(expert, token-block)``; per-expert live token counts ride in
  as scalar prefetch, so token blocks past an expert's occupancy (the
  capacity-factor headroom, empty experts) issue **no weight copy and
  no math** — with GShard's cf=2.0 roughly half the capacity slots are
  dead, and the einsum/scatter paths pay full FLOPs for every one;
* expert weights stay in HBM (``ANY``) and stream HBM→VMEM in
  ``block_f``-wide tiles through a two-slot rotating buffer of explicit
  ``pltpu.make_async_copy`` DMAs (the paged_attention.py schedule): the
  tile for step i+1 — which may belong to the next expert — is in
  flight while step i computes;
* dots run on the bf16 operands with **f32 accumulation**
  (``preferred_element_type``), and the ``h_mid [E, C, dff]``
  intermediate never exists in HBM — activation and both matmuls are
  one kernel;
* the epilogue applies the per-slot **combine weight** (router prob),
  so the combine on the way out is a pure gather+add — the mirrored
  half of the dispatch scatter.

Forward and backward are wrapped in ``jax.custom_vjp`` (flash-attention
pattern): bwd recomputes the activation per tile and splits, like the
flash dq/dkv pair, into a (expert, token-block) kernel for dx/dwslot/db2
and a (expert, ff-block) kernel for dw1/db1/dw2.

Shapes that don't tile, and non-TPU backends, route to the
batched-einsum reference (`grouped_ffn_reference`) — decided from
geometry and platform BEFORE the call. A kernel that fails to trace,
lower or compile raises: nothing here catches it and reroutes.

The gated three-matrix experts of the sigmoid top-k layer take another
road (`grouped_ffn_gated`, at the end of this file): no capacity buffer,
rows sorted by expert, and a grouped matmul `gmm` whose K is not tiled,
so that an expert's matrix is fetched once a call however many row
tiles its group spans (docs/KERNELS.md "Grouped matmul for gated
experts").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import place
from .flash_attention import _x32_trace

# token-block default: 256 rows feed the MXU [256, h] x [h, block_f]
# dots; _pick_token_block halves toward the sublane minimum for small
# capacities. ff-block 512 keeps one double-buffered w1+w2 tile pair
# under ~4 MB at h=4096 bf16.
BLOCK_TOKENS = 256
BLOCK_FF = 512

_SUBLANE = {"int8": 32, "bfloat16": 16, "float16": 16}


def _sublane(dtype) -> int:
    return _SUBLANE.get(jnp.dtype(dtype).name, 8)


def pick_token_block(capacity: int, dtype="float32") -> int:
    """Token-block size for a per-expert capacity: the smallest
    power-of-two >= capacity, clamped to [sublane-min, BLOCK_TOKENS]."""
    b = _sublane(dtype)
    while b < min(capacity, BLOCK_TOKENS):
        b *= 2
    return min(b, BLOCK_TOKENS)


def padded_capacity(capacity: int, dtype="float32") -> int:
    """Capacity rounded up to a whole number of token blocks. Routing
    still drops at the UNpadded capacity — the pad slots are permanently
    dead, and the kernel's count-based liveness skips them for free."""
    bt = pick_token_block(capacity, dtype)
    return -(-capacity // bt) * bt


def _pick_ff_block(d_hidden: int) -> int:
    """Largest lane-aligned divisor of d_hidden at most BLOCK_FF (falls
    back to power-of-two halving for untiled interpret-mode shapes)."""
    for cand in range(min(BLOCK_FF, d_hidden), 0, -128):
        if d_hidden % cand == 0 and cand % 128 == 0:
            return cand
    b = min(BLOCK_FF, d_hidden)
    while d_hidden % b:
        b //= 2
    return max(b, 1)


def moe_pallas_requirements(d_model, d_hidden, capacity, dtype):
    """Which Pallas-eligibility constraint a MoE geometry misses, as a
    human-readable string — or None when eligible. Mirrors
    paged_pallas_requirements (docs/KERNELS.md eligibility table).
    Only the lane-width constraints can fail: the token dimension is
    always sublane-aligned by construction (`pick_token_block` starts
    at the dtype's sublane minimum and doubles, and `padded_capacity`
    rounds the buffer to whole blocks); `capacity`/`dtype` stay in the
    signature so a future tiling change keeps its callers."""
    del capacity, dtype
    problems = []
    if d_model % 128:
        problems.append(
            f"d_model {d_model} is not a multiple of the 128 lane width")
    if d_hidden % 128:
        problems.append(
            f"d_hidden {d_hidden} is not a multiple of the 128 lane width")
    return "; ".join(problems) if problems else None


def moe_pallas_eligible(d_model, d_hidden, capacity, dtype):
    return moe_pallas_requirements(d_model, d_hidden, capacity,
                                   dtype) is None


# ---------------------------------------------------------------------------
# activation + hand-coded derivative (shared by fwd and both bwd kernels
# so they can never disagree; tanh-gelu matches jax.nn.gelu's default
# approximate=True, the GroupedExpertsFFN activation)
# ---------------------------------------------------------------------------

_GELU_C = 0.7978845608028654     # sqrt(2/pi)
_GELU_K = 0.044715


def _act_apply(z, activation):
    if activation == "gelu":
        return jax.nn.gelu(z, approximate=True)
    return jnp.maximum(z, jnp.float32(0.0))


def _act_grad(z, activation):
    if activation == "gelu":
        c = jnp.float32(_GELU_C)
        k = jnp.float32(_GELU_K)
        u = c * (z + k * z * z * z)
        t = jnp.tanh(u)
        du = c * (jnp.float32(1.0) + jnp.float32(3.0) * k * z * z)
        return (jnp.float32(0.5) * (jnp.float32(1.0) + t)
                + jnp.float32(0.5) * z * (jnp.float32(1.0) - t * t) * du)
    return (z > jnp.float32(0.0)).astype(jnp.float32)


def _row_mask(count, t, block_t, ncols):
    """[block_t, ncols] keep-mask for rows of token block t: slot ids at
    or past the expert's live count are dead (capacity padding, dropped
    tokens' trash slots live outside this buffer entirely)."""
    rows = (t * jnp.int32(block_t)
            + jax.lax.broadcasted_iota(jnp.int32, (block_t, ncols), 0))
    return rows < count


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _grouped_ffn_fwd_kernel(counts_ref, buf_ref, step_ref, x_ref, b1_ref,
                            b2_ref, ws_ref, w1_hbm, w2_hbm, o_ref,
                            w1_buf, w2_buf, sems, *, n_experts, block_t,
                            block_f, n_f, activation):
    """One (expert, token-block) program of the grouped expert FFN.

    Refs: counts [E] + two MUTABLE scalar cells (DMA buffer toggle and a
    "pipeline primed" step counter, the paged_attention.py pattern);
    x [BT, h] (clamped index map: dead blocks re-request the previous
    block, so they cost no HBM copy), b1 [1, dff], b2 [1, h],
    ws [BT, 1] combine weights; w1/w2 full pools in ANY; o [BT, h];
    scratch: two-slot w1/w2 tile buffers + one DMA semaphore per slot.

    The f-tile loop is a static python unroll (n_f = d_hidden/block_f,
    a small constant): tile f lives in buffer (buf+f)%2 while tile f+1
    — or, at the last tile, the NEXT live block's tile 0, which may be
    the next expert's — streams into the other slot.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e = pl.program_id(0)
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    count = counts_ref[e]
    live = t * jnp.int32(block_t) < count

    def copies(ei, fi, slot):
        return [
            pltpu.make_async_copy(
                w1_hbm.at[ei, :, pl.ds(fi * block_f, block_f)],
                w1_buf.at[slot], sems.at[slot]),
            pltpu.make_async_copy(
                w2_hbm.at[ei, pl.ds(fi * block_f, block_f), :],
                w2_buf.at[slot], sems.at[slot]),
        ]

    @pl.when(jnp.logical_not(live))
    def _dead():
        # dead blocks (capacity headroom / empty experts) emit zeros —
        # the combine gather never reads them, but a defined buffer
        # keeps NaN-checks and tests deterministic
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _work():
        b0 = buf_ref[0]

        @pl.when(step_ref[0] == 0)
        def _prime():
            # very first live block of the call: nobody prefetched its
            # f=0 tile (the one unavoidable pipeline bubble)
            for c in copies(e, 0, b0):
                c.start()

        # next live (expert, token-block) in grid order, for the
        # cross-step prefetch: an unrolled scan over the STATIC expert
        # count (the paged-decode next-live-slot pattern)
        within = jnp.logical_and(t + 1 < nt,
                                 (t + 1) * jnp.int32(block_t) < count)
        nxt = jnp.int32(n_experts)
        for cand in range(n_experts - 1, 0, -1):
            nxt = jnp.where(
                jnp.logical_and(cand > e, counts_ref[cand] > 0),
                jnp.int32(cand), nxt)
        ne = jnp.where(within, e, nxt)
        has_next = jnp.logical_or(within, nxt < n_experts)

        x = x_ref[...]
        h = x.shape[1]
        acc = jnp.zeros((block_t, h), jnp.float32)
        for f in range(n_f):
            slot = (b0 + jnp.int32(f)) % jnp.int32(2)
            for c in copies(e, f, slot):
                c.wait()
            if f + 1 < n_f:
                for c in copies(e, f + 1, (slot + jnp.int32(1)) % jnp.int32(2)):
                    c.start()
            else:
                @pl.when(has_next)
                def _prefetch():
                    for c in copies(ne, 0, (slot + jnp.int32(1)) % jnp.int32(2)):
                        c.start()
            z = jax.lax.dot_general(
                x, w1_buf[slot], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            z = z + b1_ref[:, pl.ds(f * block_f, block_f)].astype(
                jnp.float32)
            ha = _act_apply(z, activation)
            acc = acc + jax.lax.dot_general(
                ha.astype(x.dtype), w2_buf[slot],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        out = (acc + b2_ref[...].astype(jnp.float32)) \
            * ws_ref[...].astype(jnp.float32)
        out = jnp.where(_row_mask(count, t, block_t, h), out,
                        jnp.float32(0.0))
        o_ref[...] = out.astype(o_ref.dtype)
        buf_ref[0] = (b0 + jnp.int32(n_f)) % jnp.int32(2)
        step_ref[0] = step_ref[0] + 1


def _x_index_map(block_t):
    """Clamp the token-block index to the expert's last LIVE block:
    dead grid steps re-request the block already resident in VMEM, so
    Pallas issues no HBM copy for them (the PR-4 page-clamp trick)."""
    def index_map(e, t, counts, *_):
        nlive = jnp.maximum(
            (counts[e] + jnp.int32(block_t) - 1) // jnp.int32(block_t),
            jnp.int32(1))
        return (e, jnp.minimum(t, nlive - 1), 0)
    return index_map


def _grouped_ffn_fwd_pallas(x, w1, b1, w2, b2, ws, counts, activation,
                            block_t, block_f, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_experts, cap, h = x.shape
    dff = w1.shape[2]
    n_f = dff // block_f
    kernel = functools.partial(
        _grouped_ffn_fwd_kernel, n_experts=n_experts, block_t=block_t,
        block_f=block_f, n_f=n_f, activation=activation)
    xmap = _x_index_map(block_t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # counts + buf/step mutable cells
        grid=(n_experts, cap // block_t),
        in_specs=[
            pl.BlockSpec((None, block_t, h), xmap),
            pl.BlockSpec((None, 1, dff), lambda e, t, *_: (e, 0, 0)),
            pl.BlockSpec((None, 1, h), lambda e, t, *_: (e, 0, 0)),
            # same clamped (e, t, 0) tuple as x: dead blocks skip the copy
            pl.BlockSpec((None, block_t, 1), xmap),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((None, block_t, h),
                               lambda e, t, *_: (e, t, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, h, block_f), w1.dtype),
            pltpu.VMEM((2, block_f, h), w2.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    with _x32_trace():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_experts, cap, h), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="moe_ffn_fwd",
        )(jnp.asarray(counts, jnp.int32), jnp.zeros((1,), jnp.int32),
          jnp.zeros((1,), jnp.int32), x, b1, b2, ws, w1, w2)


# ---------------------------------------------------------------------------
# backward kernels (recompute style, flash dq/dkv split)
# ---------------------------------------------------------------------------

def _grouped_ffn_bwd_dx_kernel(counts_ref, buf_ref, step_ref, x_ref,
                               g_ref, b1_ref, b2_ref, ws_ref, w1_hbm,
                               w2_hbm, dx_ref, dws_ref, db2_ref,
                               w1_buf, w2_buf, sems, *, n_experts,
                               block_t, block_f, n_f, activation):
    """One (expert, token-block) program: dx, dwslot, and db2.

    With gw = g ∘ wslot: dh_mid = gw·w2ᵀ, dz = dh_mid ∘ act'(z),
    dx = dz·w1ᵀ; dwslot = Σ_h g ∘ (ffn + b2) (ffn recomputed);
    db2 = Σ_rows gw, accumulated across this expert's token blocks in
    the output block itself (its index map is constant in t, so the
    tile stays resident until the expert changes).

    NOTE: the DMA schedule (copies() descriptors, prime-on-step-0,
    next-live-block lookahead, buffer-toggle arithmetic) is
    deliberately kept IDENTICAL to _grouped_ffn_fwd_kernel's — any fix
    to the pipeline invariants must land in both, since interpret-mode
    tests cannot catch a DMA race that only exists on hardware.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e = pl.program_id(0)
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    count = counts_ref[e]
    live = t * jnp.int32(block_t) < count

    @pl.when(t == 0)
    def _init():
        db2_ref[...] = jnp.zeros_like(db2_ref)

    def copies(ei, fi, slot):
        return [
            pltpu.make_async_copy(
                w1_hbm.at[ei, :, pl.ds(fi * block_f, block_f)],
                w1_buf.at[slot], sems.at[slot]),
            pltpu.make_async_copy(
                w2_hbm.at[ei, pl.ds(fi * block_f, block_f), :],
                w2_buf.at[slot], sems.at[slot]),
        ]

    @pl.when(jnp.logical_not(live))
    def _dead():
        dx_ref[...] = jnp.zeros_like(dx_ref)
        dws_ref[...] = jnp.zeros_like(dws_ref)

    @pl.when(live)
    def _work():
        b0 = buf_ref[0]

        @pl.when(step_ref[0] == 0)
        def _prime():
            for c in copies(e, 0, b0):
                c.start()

        within = jnp.logical_and(t + 1 < nt,
                                 (t + 1) * jnp.int32(block_t) < count)
        nxt = jnp.int32(n_experts)
        for cand in range(n_experts - 1, 0, -1):
            nxt = jnp.where(
                jnp.logical_and(cand > e, counts_ref[cand] > 0),
                jnp.int32(cand), nxt)
        ne = jnp.where(within, e, nxt)
        has_next = jnp.logical_or(within, nxt < n_experts)

        x = x_ref[...]
        h = x.shape[1]
        keep = _row_mask(count, t, block_t, h)
        g32 = g_ref[...].astype(jnp.float32)
        gw32 = jnp.where(keep, g32 * ws_ref[...].astype(jnp.float32),
                         jnp.float32(0.0))
        gw = gw32.astype(x.dtype)
        ffn_acc = jnp.zeros((block_t, h), jnp.float32)
        dx_acc = jnp.zeros((block_t, h), jnp.float32)
        for f in range(n_f):
            slot = (b0 + jnp.int32(f)) % jnp.int32(2)
            for c in copies(e, f, slot):
                c.wait()
            if f + 1 < n_f:
                for c in copies(e, f + 1, (slot + jnp.int32(1)) % jnp.int32(2)):
                    c.start()
            else:
                @pl.when(has_next)
                def _prefetch():
                    for c in copies(ne, 0, (slot + jnp.int32(1)) % jnp.int32(2)):
                        c.start()
            w1t = w1_buf[slot]
            w2t = w2_buf[slot]
            z = jax.lax.dot_general(
                x, w1t, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            z = z + b1_ref[:, pl.ds(f * block_f, block_f)].astype(
                jnp.float32)
            ha = _act_apply(z, activation)
            ffn_acc = ffn_acc + jax.lax.dot_general(
                ha.astype(x.dtype), w2t, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dh = jax.lax.dot_general(
                gw, w2t, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dz = (dh * _act_grad(z, activation)).astype(x.dtype)
            dx_acc = dx_acc + jax.lax.dot_general(
                dz, w1t, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        dx_ref[...] = jnp.where(keep, dx_acc, jnp.float32(0.0)).astype(
            dx_ref.dtype)
        ffn = ffn_acc + b2_ref[...].astype(jnp.float32)
        dws = jnp.sum(jnp.where(keep, g32 * ffn, jnp.float32(0.0)),
                      axis=1, keepdims=True)
        dws_ref[...] = dws.astype(dws_ref.dtype)
        db2_ref[...] = db2_ref[...] + jnp.sum(gw32, axis=0,
                                              keepdims=True)
        buf_ref[0] = (b0 + jnp.int32(n_f)) % jnp.int32(2)
        step_ref[0] = step_ref[0] + 1


def _grouped_ffn_bwd_dw_kernel(counts_ref, x_hbm, gw_hbm, w1_ref,
                               w2_ref, b1_ref, dw1_ref, db1_ref, dw2_ref,
                               x_buf, gw_buf, sems, dw1_acc,
                               db1_acc, dw2_acc, *, block_t, block_f,
                               activation):
    """One (expert, ff-block) program: dw1[:, f], db1[f], dw2[f, :].

    The expert's weight tiles arrive via ordinary BlockSpecs (constant
    per grid step); the token blocks stream HBM→VMEM double-buffered
    over a fori_loop bounded by the expert's LIVE block count — dead
    capacity never touches the DMA engines. dw2 = h_midᵀ·gw,
    dz = (gw·w2ᵀ) ∘ act'(z), dw1 = xᵀ·dz, db1 = Σ_rows dz; accumulated
    in f32 scratch, written once.

    gw = g ∘ wslot arrives already multiplied (one XLA elementwise pass
    in the caller): the [E, C, 1] combine-weight array cannot be sliced
    out of HBM by token block — its trailing dimension of 1 is not a
    multiple of the 128-lane tiling and the v5e compiler refuses the
    memref slice — and one stream fewer per block is cheaper anyway.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e = pl.program_id(0)
    count = counts_ref[e]
    nlive = (count + jnp.int32(block_t) - 1) // jnp.int32(block_t)

    def copies(ti, slot):
        start = ti * jnp.int32(block_t)
        return [
            pltpu.make_async_copy(
                x_hbm.at[e, pl.ds(start, block_t)],
                x_buf.at[slot], sems.at[slot]),
            pltpu.make_async_copy(
                gw_hbm.at[e, pl.ds(start, block_t)],
                gw_buf.at[slot], sems.at[slot]),
        ]

    dw1_acc[...] = jnp.zeros_like(dw1_acc)
    db1_acc[...] = jnp.zeros_like(db1_acc)
    dw2_acc[...] = jnp.zeros_like(dw2_acc)

    @pl.when(nlive > 0)
    def _start():
        for c in copies(jnp.int32(0), jnp.int32(0)):
            c.start()

    def body(ti, carry):
        slot = ti % jnp.int32(2)
        for c in copies(ti, slot):
            c.wait()

        @pl.when(ti + jnp.int32(1) < nlive)
        def _prefetch():
            for c in copies(ti + jnp.int32(1), jnp.int32(1) - slot):
                c.start()

        x = x_buf[slot]
        keep = _row_mask(count, ti, block_t, x.shape[1])
        gw = jnp.where(keep, gw_buf[slot], jnp.zeros((), x.dtype))
        z = jax.lax.dot_general(
            x, w1_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        z = z + b1_ref[...].astype(jnp.float32)
        ha = _act_apply(z, activation).astype(x.dtype)
        dw2_acc[...] = dw2_acc[...] + jax.lax.dot_general(
            ha, gw, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dh = jax.lax.dot_general(
            gw, w2_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dz32 = dh * _act_grad(z, activation)
        dz = dz32.astype(x.dtype)
        dw1_acc[...] = dw1_acc[...] + jax.lax.dot_general(
            x, dz, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        db1_acc[...] = db1_acc[...] + jnp.sum(dz32, axis=0,
                                              keepdims=True)
        return carry

    # bounds/carry pinned i32: the package's global x64 would otherwise
    # give the loop an i64 induction var that Mosaic cannot legalize
    jax.lax.fori_loop(jnp.int32(0), nlive, body, jnp.int32(0))
    dw1_ref[...] = dw1_acc[...].astype(dw1_ref.dtype)
    db1_ref[...] = db1_acc[...].astype(db1_ref.dtype)
    dw2_ref[...] = dw2_acc[...].astype(dw2_ref.dtype)


def _grouped_ffn_bwd_pallas(x, w1, b1, w2, b2, ws, counts, g, activation,
                            block_t, block_f, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_experts, cap, h = x.shape
    dff = w1.shape[2]
    n_f = dff // block_f
    counts = jnp.asarray(counts, jnp.int32)

    dx_kernel = functools.partial(
        _grouped_ffn_bwd_dx_kernel, n_experts=n_experts, block_t=block_t,
        block_f=block_f, n_f=n_f, activation=activation)
    xmap = _x_index_map(block_t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_experts, cap // block_t),
        in_specs=[
            pl.BlockSpec((None, block_t, h), xmap),      # x
            pl.BlockSpec((None, block_t, h), xmap),      # g
            pl.BlockSpec((None, 1, dff), lambda e, t, *_: (e, 0, 0)),
            pl.BlockSpec((None, 1, h), lambda e, t, *_: (e, 0, 0)),
            pl.BlockSpec((None, block_t, 1), xmap),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=[
            pl.BlockSpec((None, block_t, h), lambda e, t, *_: (e, t, 0)),
            pl.BlockSpec((None, block_t, 1), lambda e, t, *_: (e, t, 0)),
            # db2: index constant in t -> the tile stays resident and
            # accumulates across the expert's token blocks
            pl.BlockSpec((None, 1, h), lambda e, t, *_: (e, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, h, block_f), w1.dtype),
            pltpu.VMEM((2, block_f, h), w2.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    with _x32_trace():
        dx, dws, db2 = pl.pallas_call(
            dx_kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((n_experts, cap, h), x.dtype),
                jax.ShapeDtypeStruct((n_experts, cap, 1), ws.dtype),
                jax.ShapeDtypeStruct((n_experts, 1, h), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="moe_ffn_dx",
        )(counts, jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
          x, g, b1, b2, ws, w1, w2)

    # the same f32 product the dx kernel forms in VMEM, rounded to the
    # matmul dtype exactly as it was when the dw kernel formed it
    gw = (g.astype(jnp.float32) * ws.astype(jnp.float32)).astype(x.dtype)
    dw_kernel = functools.partial(
        _grouped_ffn_bwd_dw_kernel, block_t=block_t, block_f=block_f,
        activation=activation)
    dw_grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_experts, n_f),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),  # x
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),  # g * ws
            pl.BlockSpec((None, h, block_f), lambda e, f, *_: (e, 0, f)),
            pl.BlockSpec((None, block_f, h), lambda e, f, *_: (e, f, 0)),
            pl.BlockSpec((None, 1, block_f), lambda e, f, *_: (e, 0, f)),
        ],
        out_specs=[
            pl.BlockSpec((None, h, block_f), lambda e, f, *_: (e, 0, f)),
            pl.BlockSpec((None, 1, block_f), lambda e, f, *_: (e, 0, f)),
            pl.BlockSpec((None, block_f, h), lambda e, f, *_: (e, f, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, block_t, h), x.dtype),
            pltpu.VMEM((2, block_t, h), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((h, block_f), jnp.float32),
            pltpu.VMEM((1, block_f), jnp.float32),
            pltpu.VMEM((block_f, h), jnp.float32),
        ],
    )
    with _x32_trace():
        dw1, db1, dw2 = pl.pallas_call(
            dw_kernel,
            grid_spec=dw_grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(w1.shape, w1.dtype),
                jax.ShapeDtypeStruct(b1.shape, b1.dtype),
                jax.ShapeDtypeStruct(w2.shape, w2.dtype),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="moe_ffn_dw",
        )(counts, x, gw, w1, w2, b1)
    return dx, dw1, db1, dw2, db2.astype(b2.dtype), dws


# ---------------------------------------------------------------------------
# custom_vjp wrapper + XLA reference
# ---------------------------------------------------------------------------

def grouped_ffn_reference(x, w1, b1, w2, b2, ws, counts=None,
                          activation="gelu"):
    """Batched-einsum reference (and the non-TPU / untileable route):
    the exact math of GroupedExpertsFFN with the combine weight applied, dead
    capacity slots (>= counts[e]) zeroed to match the kernel contract.
    """
    z = jnp.einsum("ech,ehf->ecf", x, w1) + b1
    ha = _act_apply(z, activation)
    out = jnp.einsum("ecf,efh->ech", ha, w2) + b2
    out = out * ws
    if counts is not None:
        slot = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :, None]
        out = jnp.where(slot < counts[:, None, None], out, 0.0)
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _grouped_ffn_pallas(x, w1, b1, w2, b2, ws, counts, activation,
                        block_t, block_f, interpret):
    """x [E, C, h], w1 [E, h, dff], b1 [E, 1, dff], w2 [E, dff, h],
    b2 [E, 1, h], ws [E, C, 1], counts [E] int32 → out [E, C, h];
    differentiable in everything but counts."""
    return _grouped_ffn_fwd_pallas(x, w1, b1, w2, b2, ws, counts,
                                   activation, block_t, block_f,
                                   interpret)


def _grouped_ffn_vjp_fwd(x, w1, b1, w2, b2, ws, counts, activation,
                         block_t, block_f, interpret):
    out = _grouped_ffn_fwd_pallas(x, w1, b1, w2, b2, ws, counts,
                                  activation, block_t, block_f, interpret)
    return out, (x, w1, b1, w2, b2, ws, counts)


def _grouped_ffn_vjp_bwd(activation, block_t, block_f, interpret, res, g):
    x, w1, b1, w2, b2, ws, counts = res
    dx, dw1, db1, dw2, db2, dws = _grouped_ffn_bwd_pallas(
        x, w1, b1, w2, b2, ws, counts, g, activation, block_t,
        block_f, interpret)
    return dx, dw1, db1, dw2, db2, dws, None


_grouped_ffn_pallas.defvjp(_grouped_ffn_vjp_fwd, _grouped_ffn_vjp_bwd)


def grouped_ffn(x, w1, b1, w2, b2, ws, counts, *, activation="gelu",
                interpret=False, force_pallas=False):
    """Fused grouped expert FFN over the sorted-by-expert capacity
    buffer: out[e, c] = (act(x[e, c]·w1[e] + b1[e])·w2[e] + b2[e])
    ∘ ws[e, c], with rows at or past counts[e] zeroed and skipped.

    Routes to the Pallas kernel pair when the geometry tiles (see
    moe_pallas_requirements) on a TPU backend; otherwise runs the
    batched-einsum reference.
    """
    n_experts, cap, h = x.shape
    dff = w1.shape[2]
    block_t = pick_token_block(cap, x.dtype)
    block_f = _pick_ff_block(dff)
    mm_dtype = jnp.promote_types(x.dtype, w1.dtype)
    eligible = (cap % block_t == 0
                and moe_pallas_eligible(h, dff, cap, mm_dtype))
    if force_pallas or (place.accelerator_available() and eligible):
        return _grouped_ffn_pallas(
            x.astype(mm_dtype), w1.astype(mm_dtype),
            b1.astype(jnp.float32), w2.astype(mm_dtype),
            b2.astype(jnp.float32), ws.astype(jnp.float32),
            jnp.asarray(counts, jnp.int32), activation, block_t,
            block_f, interpret).astype(x.dtype)
    return grouped_ffn_reference(x, w1, b1, w2, b2, ws, counts,
                                 activation)


# ---------------------------------------------------------------------------
# grouped matmul over rows SORTED by expert (the sigmoid top-k layer's slabs)
# ---------------------------------------------------------------------------

# row tile of a grouped matmul: 128 rows for a prefill slab, 32 for a
# decode tick's (fewer than GMM_SMALL_ROWS rows: 2-8 rows an expert, where
# a 128-row tile would multiply mostly rows of other groups). The same
# rule MoELayer._slab_rows rounds its slabs by.
GMM_ROW_TILE = 128
GMM_SMALL_ROW_TILE = 32
GMM_SMALL_ROWS = 512
# of the v5e's 128 MiB of VMEM, what the blocks of one call may take; the
# compiler's own default (16 MiB scoped) holds no [6144, 512] bf16 block
# twice beside its rows
_GMM_VMEM_BLOCKS = 88 << 20
_GMM_VMEM_HEADROOM = 8 << 20


def gmm_row_tile(m: int) -> int:
    return GMM_ROW_TILE if m >= GMM_SMALL_ROWS else GMM_SMALL_ROW_TILE


def gmm_tiles(m: int, k: int, n: int, dtype, n_rhs: int = 1):
    """(row tile, column tile, vmem_limit_bytes) of one grouped matmul
    [m, k] x n_rhs x [E, k, n], from the static shapes alone. K is never
    tiled: the weight block is [k, column tile], its index (group, column
    tile), so the row tiles one group spans share one fetch. The column
    tile is the widest lane-aligned divisor of n whose blocks fit
    ``_GMM_VMEM_BLOCKS``; the pipeline double-buffers every block:

        rows   2 x tm x k  x itemsize
        weight 2 x n_rhs x k x tn x itemsize
        out    2 x tm x tn x itemsize, + n_rhs float32 products tm x tn

    e.g. bf16 [3200, 6144] x [16, 6144, 2048]: tn 2048, 3 + 48 + 1 + 1 =
    53 MiB; gate and up in one call (n_rhs 2): tn 1024, 3 + 48 + 0.5 + 1."""
    tm = gmm_row_tile(m)
    item = jnp.dtype(dtype).itemsize
    need = 0
    for parts in range(1, n // 128 + 1):
        tn = n // parts
        if n % parts or tn % 128:
            continue
        need = (2 * item * (tm * k + n_rhs * k * tn + tm * tn)
                + 4 * n_rhs * tm * tn)
        if need <= _GMM_VMEM_BLOCKS:
            break
    return tm, tn, need + _GMM_VMEM_HEADROOM


def gmm_requirements(m: int, h: int, f: int):
    """Why `grouped_ffn_gated` cannot run its rows on the Pallas grouped
    matmul, or None: whole row tiles, lane-aligned widths (both are K of
    one call and N of another)."""
    problems = []
    if m % gmm_row_tile(m):
        problems.append(f"{m} rows are not whole tiles of "
                        f"{gmm_row_tile(m)}")
    for name, width in (("hidden", h), ("expert", f)):
        if width % 128:
            problems.append(f"{name} width {width} is not a multiple of "
                            "the 128 lane width")
    return "; ".join(problems) if problems else None


def gmm_metadata(group_sizes, m: int, tm: int):
    """What the grid of a grouped matmul over ``m`` sorted rows in tiles
    of ``tm`` walks: a VISIT is a (group, row tile) pair that shares a
    row, in the order of the rows. Returns (offsets [E + 1]: group g is
    rows offsets[g]:offsets[g + 1]; group_ids, tile_ids [m / tm + E - 1],
    of which the first ``visits`` are real; visits, a traced count). An
    empty group has no visit. Computed once a slab: the gate, up and
    down calls walk the same rows."""
    gs = jnp.asarray(group_sizes, jnp.int32)
    n_groups, tiles = gs.shape[0], m // tm
    ends = jnp.cumsum(gs)
    first = (ends - gs) // tm
    per_group = jnp.where(gs > 0, -(-ends // tm) - first, 0)
    most = tiles + n_groups - 1
    group_ids = jnp.repeat(jnp.arange(n_groups, dtype=jnp.int32), per_group,
                           total_repeat_length=most)
    nth = (jnp.arange(most, dtype=jnp.int32)
           - (jnp.cumsum(per_group) - per_group)[group_ids])
    tile_ids = jnp.clip(first[group_ids] + nth, 0, tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets, group_ids, tile_ids,
            jnp.sum(per_group, dtype=jnp.int32))


def _gmm_kernel(offsets_ref, group_ids_ref, tile_ids_ref, lhs_ref, *refs,
                tm):
    """One (column tile, visit) program: the visit's row tile times its
    group's [K, tn] block, float32 over the whole K, rounded once, stored
    to the rows of the tile that are the group's (a tile that holds
    several groups is visited once for each; its output block stays in
    VMEM between them). With two weight refs the store is
    silu(rows·w_gate) * (rows·w_up), each product rounded to the rows'
    dtype first, as two calls and an XLA pass would round them."""
    from jax.experimental import pallas as pl

    *rhs_refs, out_ref = refs
    v = pl.program_id(1)
    g = group_ids_ref[v]
    row = (tile_ids_ref[v] * jnp.int32(tm)
           + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0))
    mine = jnp.logical_and(row >= offsets_ref[g], row < offsets_ref[g + 1])
    x = lhs_ref[...]
    dt = out_ref.dtype
    prods = [jax.lax.dot_general(x, r[...], (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32
                                 ).astype(dt) for r in rhs_refs]
    if len(prods) == 2:
        val = (jax.nn.silu(prods[0].astype(jnp.float32))
               * prods[1].astype(jnp.float32)).astype(dt)
    else:
        val, = prods
    out_ref[...] = jnp.where(mine, val, out_ref[...])


def gmm(lhs, rhss, metadata, *, tn=None, interpret=False):
    """Grouped matmul on the Pallas kernel: lhs [M, K] sorted by group,
    rhss one [E, K, N] (out = lhs·rhs[group]) or two (out =
    silu(lhs·rhs0[group]) * (lhs·rhs1[group])), metadata from
    `gmm_metadata` at `gmm_row_tile(M)`. Rows in no group are NOT
    written: the caller zeroes them. Grid (column tiles, visits), the
    visits a traced count; see `gmm_tiles` for the blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = rhss[0].shape[2]
    offsets, group_ids, tile_ids, visits = metadata
    tm, tn_auto, vmem = gmm_tiles(m, k, n, lhs.dtype, len(rhss))
    tn = tn or tn_auto
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, visits),
        in_specs=[pl.BlockSpec((tm, k), lambda j, v, o, g, t: (t[v], 0))]
        + [pl.BlockSpec((None, k, tn), lambda j, v, o, g, t: (g[v], 0, j))
           for _ in rhss],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, o, g, t: (t[v], j)),
    )
    item = jnp.dtype(lhs.dtype).itemsize
    with _x32_trace():
        return pl.pallas_call(
            functools.partial(_gmm_kernel, tm=tm),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=vmem),
            cost_estimate=pl.CostEstimate(
                flops=2 * m * k * n * len(rhss),
                bytes_accessed=item * (m * k * (n // tn) + m * n + len(rhss)
                                       * rhss[0].shape[0] * k * n),
                transcendentals=m * n * (len(rhss) - 1)),
            interpret=interpret,
            name="moe_gmm",
        )(offsets, group_ids, tile_ids, lhs, *rhss)


def _ffn_gated_ragged(rows, w_gate, w_up, w_down, gs):
    """`grouped_ffn_gated` as three `jax.lax.ragged_dot`s and an XLA pass:
    the path where no Mosaic compiler is, and the derivative of both."""
    dt = rows.dtype
    g = jax.lax.ragged_dot(rows, w_gate, gs, preferred_element_type=dt)
    u = jax.lax.ragged_dot(rows, w_up, gs, preferred_element_type=dt)
    mid = (jax.nn.silu(g.astype(jnp.float32))
           * u.astype(jnp.float32)).astype(dt)
    return jax.lax.ragged_dot(mid, w_down, gs, preferred_element_type=dt)


# jitted under the custom_vjp: the expert layers of one program call it
# with the same shapes, and a nested jit is traced and lowered (Pallas to
# Mosaic, ~0.1 s of host time a call) once for all of them; set-up time,
# not device time
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
@functools.partial(jax.jit, static_argnames="interpret")
def _ffn_gated_pallas(rows, w_gate, w_up, w_down, gs, interpret=False):
    metadata = gmm_metadata(gs, rows.shape[0], gmm_row_tile(rows.shape[0]))
    mid = gmm(rows, (w_gate, w_up), metadata, interpret=interpret)
    return gmm(mid, (w_down,), metadata, interpret=interpret)


def _ffn_gated_vjp_fwd(rows, w_gate, w_up, w_down, gs, interpret):
    return (_ffn_gated_pallas(rows, w_gate, w_up, w_down, gs, interpret),
            (rows, w_gate, w_up, w_down, gs))


def _ffn_gated_vjp_bwd(interpret, res, ct):
    *operands, gs = res
    _, pull = jax.vjp(lambda *a: _ffn_gated_ragged(*a, gs), *operands)
    return (*pull(ct), None)


_ffn_gated_pallas.defvjp(_ffn_gated_vjp_fwd, _ffn_gated_vjp_bwd)


def grouped_ffn_gated(rows, w_gate, w_up, w_down, group_sizes, *,
                      interpret=False):
    """Gated three-matrix expert FFN over rows SORTED by expert:
    out[r] = (silu(rows[r]·w_gate[e]) * (rows[r]·w_up[e]))·w_down[e] for
    the expert e whose group row r falls in; rows [M, h], w_gate/w_up
    [E, h, f], w_down [E, f, h], group_sizes [E] int32 with
    sum(group_sizes) <= M (rows past the sum belong to no expert and
    come back as zeros). No capacity buffer, nothing dropped; operands
    in the rows' dtype, float32 accumulation over the whole K, one
    rounding a product.

    On a TPU: two calls of the Pallas grouped matmul `gmm` (gate and up
    in one, then down), which walk (group, row tile) pairs and fetch an
    expert's [K, column tile] block once however many row tiles its
    group spans: `gmm_tiles` sets the tiles from the static shapes.
    Weights follow the groups that have a row; TIME follows M as well
    (the rows are read a column sweep, every tile is multiplied once a
    group in it): give it the rows that can be live, as
    MoELayer._forward_sorted's slabs do. Elsewhere, and for shapes
    `gmm_requirements` names, three `jax.lax.ragged_dot`s (on the TPU
    XLA's own grouped matmul, K in tiles of 512: an expert's matrix is
    fetched again for every row tile of its group). The derivative is
    the ragged formulation's on both paths. Trace-time counters
    `kernels.moe.gmm_pallas` / `kernels.moe.gmm_fallback` say which.
    ``interpret=True`` (tests) runs the kernel path interpreted, on any
    backend."""
    from .. import monitor
    gs = jnp.asarray(group_sizes, jnp.int32)
    dt = rows.dtype
    weights = [w.astype(dt) for w in (w_gate, w_up, w_down)]
    if (interpret or place.accelerator_available()) and gmm_requirements(
            rows.shape[0], rows.shape[1], w_gate.shape[2]) is None:
        monitor.counter("kernels.moe.gmm_pallas").increase()
        out = _ffn_gated_pallas(rows, *weights, gs, interpret)
    else:
        monitor.counter("kernels.moe.gmm_fallback").increase()
        out = _ffn_gated_ragged(rows, *weights, gs)
    live = jnp.arange(rows.shape[0], dtype=jnp.int32) < jnp.sum(gs)
    return jnp.where(live[:, None], out, jnp.zeros((), dt))
