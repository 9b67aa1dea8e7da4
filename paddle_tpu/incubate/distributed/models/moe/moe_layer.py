"""MoELayer — mixture-of-experts with expert parallelism over 'ep'.

Reference: python/paddle/incubate/distributed/models/moe/moe_layer.py:263
(MoELayer routing through global_scatter/global_gather AllToAll kernels,
moe_utils.py:20,153) with gates in gate/.

TPU-native: experts live as STACKED parameters [E, ...] sharded over the
'ep' mesh axis; dispatch/combine are dense einsums against the gate's
one-hot tensors, so GSPMD lowers the token movement to exactly one
all-to-all each way over ICI (SURVEY.md §7.2 stage 7) and the per-expert
FFN to a grouped GEMM on the MXU. Static capacity keeps shapes fixed
across steps (XLA requirement); overflow tokens are dropped like the
reference's limit_by_capacity.
"""
from __future__ import annotations

import functools
import logging

from typing import Optional

import jax
import jax.numpy as jnp

from .....core.dispatch import run_op, run_op_nodiff, unwrap, wrap
from .....core import random as random_mod
from .....distributed import mesh as mesh_mod
from .....distributed.auto_parallel import Replicate, Shard, shard_tensor
from .....distributed.auto_parallel.process_mesh import ProcessMesh
from .....distributed.fleet.layers.mpu.mp_ops import mark_sharding
from .....nn.layer.layers import Layer
from .gate import (BaseGate, GShardGate, NaiveGate, SigmoidTopKGate,
                   SwitchGate)


# The sorted dispatch works its sorted picks off in slabs of twice the
# rows a uniform router sends to this chip's experts (the factor of
# GShard's default capacity, but as the size of a STEP: picks beyond it
# take another slab, none is dropped), in an ODD number of row tiles.
# A grouped matmul walks (group, row tile) pairs, and a pair costs the
# whole tile however few of the group's rows lie in it. On a TPU the
# tile is set by kernels.moe.gmm_row_tile, the same rule as these
# constants (tests/test_moe_gmm.py holds the two equal): measured on the
# v5e (PERF.md section 6, PR 35), 128 rows for a prefill chunk's 6-97
# rows an expert (a tile of 512 spends its time on dead rows, one of 32
# or 64 takes more pairs), 32 for a decode tick's 2-8. The odd count is
# for the path without that kernel: XLA's `ragged_dot` takes the largest
# power of two up to 512 that divides its row count as the tile, and an
# odd number of tiles makes the tile ours to choose there too.
_SLAB_FACTOR = 2
_SLAB_ROW_TILE = 128
_SLAB_SMALL_ROW_TILE = 32       # for a slab of fewer than 512 rows
_SLAB_SMALL = 512


def _slab_rows(m: int, of: int) -> int:
    """Rows of one slab for ``m`` picks on a layer that holds one
    ``of``-th of the experts; ``m`` itself when one slab takes them
    all."""
    rows = _SLAB_FACTOR * -(-m // of)
    tile = _SLAB_ROW_TILE if rows >= _SLAB_SMALL else _SLAB_SMALL_ROW_TILE
    tiles = -(-rows // tile)
    return min(m, (tiles + 1 - tiles % 2) * tile)


def _shard_expert_param(layer: Layer, name: str, axis: str = "ep"):
    """Commit layer.<name> (leading dim = experts) to Shard(0) on `axis`
    (skipped when the expert count doesn't divide the axis degree)."""
    p = getattr(layer, name)
    mesh = ProcessMesh(mesh_mod.ensure_mesh())
    placements = [Replicate() for _ in mesh.dim_names]
    deg = mesh_mod.axis_degree(axis)
    if axis in mesh.dim_names and deg > 1 and p.shape[0] % deg == 0:
        placements[mesh.dim_names.index(axis)] = Shard(0)
    sharded = shard_tensor(p, mesh, placements,
                           stop_gradient=p.stop_gradient)
    layer._parameters[name] = sharded
    return sharded


class GroupedExpertsFFN(Layer):
    """E parallel FFN experts as stacked weights [E, h, dff] / [E, dff, h]
    — the grouped-GEMM formulation of the reference's cutlass fused MoE
    kernel (paddle/phi/kernels/fusion/cutlass/fused_moe_kernel.cu)."""

    def __init__(self, num_experts: int, d_model: int, d_hidden: int,
                 activation="gelu", ep_axis: str = "ep"):
        super().__init__()
        self.num_experts = num_experts
        self._act = activation
        if activation == "swiglu":
            # gated three-matrix experts, no biases:
            # (silu(x w1) * (x w3)) w2, the LLaMA / DeepSeek expert
            from .....nn.initializer import Normal
            init = Normal(0.0, 0.02)
            self.w1 = self.create_parameter(
                [num_experts, d_model, d_hidden], default_initializer=init)
            self.w3 = self.create_parameter(
                [num_experts, d_model, d_hidden], default_initializer=init)
            self.w2 = self.create_parameter(
                [num_experts, d_hidden, d_model], default_initializer=init)
            names = ("w1", "w3", "w2")
        else:
            self.w1 = self.create_parameter(
                [num_experts, d_model, d_hidden])
            self.b1 = self.create_parameter([num_experts, 1, d_hidden],
                                            is_bias=True)
            self.w2 = self.create_parameter(
                [num_experts, d_hidden, d_model])
            self.b2 = self.create_parameter([num_experts, 1, d_model],
                                            is_bias=True)
            names = ("w1", "b1", "w2", "b2")
        for n in names:
            _shard_expert_param(self, n, ep_axis)

    def forward(self, x):
        """x: [E, C, h] -> [E, C, h] (batched per-expert GEMMs)."""
        if self._act == "swiglu":
            def gated(x, w1, w3, w2):
                mid = jax.nn.silu(jnp.einsum("ech,ehf->ecf", x, w1)) \
                    * jnp.einsum("ech,ehf->ecf", x, w3)
                return jnp.einsum("ecf,efh->ech", mid, w2)
            return run_op("grouped_experts_ffn", gated,
                          [x, self.w1, self.w3, self.w2])

        def fn(x, w1, b1, w2, b2):
            h = jnp.einsum("ech,ehf->ecf", x, w1) + b1
            h = jax.nn.gelu(h) if self._act == "gelu" else jnp.maximum(h, 0)
            return jnp.einsum("ecf,efh->ech", h, w2) + b2

        return run_op("grouped_experts_ffn", fn,
                      [x, self.w1, self.b1, self.w2, self.b2])


@functools.lru_cache(maxsize=None)
def _n_groups_cached(n, gs):
    """Largest divisor of n giving groups of >= gs tokens; warns ONCE
    per (n, gs) when the divisor search collapses toward one group (a
    prime-ish token count degrades the dispatch einsum back toward
    quadratic — visible, not silent). Also bumps the lint-style
    `lint.moe-group-degraded` counter so telemetry snapshots (bench,
    hapi) can see the degradation without scraping the log."""
    if not gs or n <= gs:
        return 1
    g = max(1, n // int(gs))
    while n % g:                # largest divisor of n at most n // gs
        g -= 1
    if n // g > 2 * int(gs):
        from ..... import monitor
        monitor.counter("lint.moe-group-degraded").increase()
        logging.getLogger(__name__).warning(
            "MoE group-wise dispatch: %d tokens has no divisor near "
            "group_size=%d (using %d groups of %d); pad batch*seq "
            "to a rounder number to keep dispatch cost linear",
            n, gs, g, n // g)
    return g


# ---------------------------------------------------------------------------
# dispatch/combine implementations, one named jit per mode: inside a
# traced program each shows up as a `pjit` equation carrying its
# function name, which is what analysis.jaxpr_lint's moe-slow-dispatch
# rule keys on to flag einsum/scatter dispatch as a perf finding
# (docs/ANALYSIS.md) — and the eager path gets the fused executable for
# free.
# ---------------------------------------------------------------------------

@jax.jit
def moe_dispatch_einsum(tok, d):
    """Dense one-hot dispatch einsum — O(N*E*C*H) per group."""
    h = tok.shape[-1]
    if d.ndim == 3:
        return jnp.einsum("nh,nec->ech", tok, d)
    g, gn, e, c = d.shape
    ei = jnp.einsum("gnh,gnec->gech", tok.reshape(g, gn, h), d)
    return ei.transpose(1, 0, 2, 3).reshape(e, g * c, h)


@jax.jit
def moe_combine_einsum(eo, c):
    """Mirrored dense combine einsum."""
    h = eo.shape[-1]
    if c.ndim == 3:
        return jnp.einsum("ech,nec->nh", eo, c)
    g, gn, e, cc = c.shape
    eg = eo.reshape(e, g, cc, h).transpose(1, 0, 2, 3)
    return jnp.einsum("gech,gnec->gnh", eg, c).reshape(g * gn, h)


@functools.partial(jax.jit, static_argnums=(4, 5))
def moe_dispatch_scatter(tok, idx, pos, keep, e, cap):
    """Sparse dispatch: scatter tokens into the flat [E*C, h] expert
    buffer by (expert, slot) index; dropped tokens land in a trash
    slot e*cap."""
    dst = jnp.where(keep, idx * cap + pos, e * cap)  # [k, N]
    buf = jnp.zeros((e * cap + 1, tok.shape[1]), tok.dtype)
    for r in range(idx.shape[0]):
        buf = buf.at[dst[r]].add(tok)
    return buf[:e * cap].reshape(e, cap, tok.shape[1])


@functools.partial(jax.jit, static_argnums=(5, 6))
def moe_combine_scatter(eo, idx, pos, keep, w, e, cap):
    """Mirrored gather + weighted sum."""
    flat = eo.reshape(e * cap, eo.shape[-1])
    dst = jnp.where(keep, idx * cap + pos, 0)
    out = 0.0
    for r in range(idx.shape[0]):
        out = out + flat[dst[r]] * (w[r] * keep[r])[:, None]
    return out.astype(eo.dtype)


# one-time (per reason) trace-log when dispatch_mode="pallas" degrades
_pallas_fallback_logged = set()

# test hooks (monkeypatched by tests/test_moe_kernel.py): force the
# Pallas dispatch on a non-TPU backend / run its kernels in interpret
# mode — mirrors flash_attention_arrays' force_pallas/interpret knobs
_FORCE_PALLAS = False
_PALLAS_INTERPRET = False


class MoELayer(Layer):
    """Mixture of experts (reference moe_layer.py:263).

    Args:
        d_model: token hidden size.
        d_hidden: expert FFN hidden size.
        num_experts: global expert count (sharded over 'ep').
        gate: "gshard" | "switch" | "naive" | a BaseGate instance
            (reference accepts a gate config dict the same way).
        top_k / capacity_factor: routing config for the named gates.
        experts: optional custom GroupedExpertsFFN-like Layer taking
            [E, C, h] -> [E, C, h].
        group_size: dispatch tokens in routing groups of ~this many
            tokens (GShard's group-wise dispatch). The dense dispatch
            einsum costs N*E*C*H with C proportional to N/E, i.e.
            QUADRATIC in tokens for a single group; per-group capacity
            makes it linear (cost ~ N * group_size * top_k * cf * H).
            None = one group (exact legacy semantics).
        dispatch_mode: "pallas" (the default — sparse routing indices,
            scatter into the per-expert capacity buffer, then the
            fused Pallas grouped-matmul kernel of kernels/moe.py:
            O(N*k*H) token movement AND an expert FFN that skips dead
            capacity slots, streams weights HBM→VMEM double-buffered,
            and never materializes h_mid in HBM; degrades to "einsum"
            — counter-visible and logged, never silent — when the
            geometry/platform is ineligible, see
            `_pallas_fallback_reason`), "einsum" (dense one-hot
            dispatch/combine, the GShard formulation), or "scatter"
            (sparse routing indices + scatter-add dispatch / gather
            combine, O(N * k * H) with no E- or C-proportional term;
            group_size is ignored, the cost is already linear in
            tokens). Routing decisions are identical in all three.
        gate="sigmoid_topk" (or a SigmoidTopKGate): sigmoid scores, the
            top_k of score + `e_score_correction_bias` (a buffer, zero
            until something sets it), weights normalised over the picks.
            It has no capacity, so it takes its own dispatch whatever
            dispatch_mode says: picks sorted by expert into one
            ragged grouped matmul (`kernels.moe.grouped_ffn_gated`),
            nothing dropped, in training and serving alike.
        activation: "gelu" | "relu" (two matrices and biases) or
            "swiglu" (gated, three matrices, no biases; sorted dispatch
            only).
        expert_share: (index, of) — this layer HOLDS the experts
            [index * E/of, (index + 1) * E/of) of the E it routes over
            (one chip's share of an expert-parallel layer). The router
            keeps its E outputs and its top_k; a pick that falls on an
            expert held elsewhere adds nothing here, and nothing stands
            in for it. Sorted dispatch only.
        shared_experts: a Layer applied to every token and added to the
            routed result (what every chip of the layer computes alike).

    After forward, `self.l_aux` holds the load-balancing auxiliary loss
    (add `layer.l_aux * coeff` to the training loss, as the reference's
    examples do).
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 gate="gshard", top_k: Optional[int] = None,
                 capacity_factor: Optional[float] = None,
                 experts: Optional[Layer] = None, moe_group=None,
                 ep_axis: str = "ep", group_size: Optional[int] = None,
                 dispatch_mode: str = "pallas", name=None,
                 activation: str = "gelu", expert_share=None,
                 shared_experts: Optional[Layer] = None):
        super().__init__()
        if dispatch_mode not in ("pallas", "einsum", "scatter"):
            raise ValueError(
                f"dispatch_mode must be 'pallas', 'einsum' or "
                f"'scatter', got {dispatch_mode!r}")
        self.d_model = d_model
        self.num_experts = num_experts
        self._group_size = group_size
        self._dispatch_mode = dispatch_mode
        self.gate_weight = self.create_parameter([d_model, num_experts])
        if isinstance(gate, BaseGate):
            self.gate = gate
        elif gate == "switch":
            self.gate = SwitchGate(num_experts,
                                   capacity_factor or 1.25)
        elif gate == "naive":
            self.gate = NaiveGate(num_experts, top_k or 2,
                                  capacity_factor or 1.25)
        elif gate == "gshard":
            self.gate = GShardGate(num_experts, capacity_factor or 2.0)
        elif gate == "sigmoid_topk":
            self.gate = SigmoidTopKGate(num_experts, top_k or 8)
        else:
            raise ValueError(
                f"unknown gate {gate!r}: expected 'gshard', 'switch', "
                "'naive', 'sigmoid_topk', or a BaseGate instance")
        if top_k is not None:
            self.gate.top_k = top_k
        self._sorted = isinstance(self.gate, SigmoidTopKGate)
        index, of = expert_share or (0, 1)
        if of < 1 or num_experts % of or not 0 <= index < of:
            raise ValueError(
                f"expert_share=({index}, {of}): `of` must divide the "
                f"{num_experts} experts and 0 <= index < of")
        if not self._sorted and (of > 1 or activation == "swiglu"):
            raise ValueError(
                "expert_share and activation='swiglu' need the capacity-"
                "free sorted dispatch: pass gate='sigmoid_topk'")
        self.expert_share = (int(index), int(of))
        self.num_held = num_experts // of
        self.first_held = index * self.num_held
        self.experts = experts if experts is not None else \
            GroupedExpertsFFN(self.num_held, d_model, d_hidden,
                              activation=activation, ep_axis=ep_axis)
        if self._sorted:
            if getattr(self.experts, "_act", None) != "swiglu":
                raise ValueError(
                    "gate='sigmoid_topk' dispatches to gated experts "
                    "only: pass activation='swiglu'")
            self.register_buffer("e_score_correction_bias", wrap(
                jnp.zeros((num_experts,), jnp.float32)))
        self.shared_experts = shared_experts
        self._ep_axis = ep_axis
        self.l_aux = None
        # [picks held here, picks made, held experts touched, slabs run]
        # of the last sorted forward: traced values inside a compiled
        # step, which the serving engine returns with the tick
        self.last_stats = None

    def _n_groups(self, n):
        return _n_groups_cached(n, self._group_size)

    def _sparse_route(self, tokens, cap, token_mask):
        """The ONE sparse routing call both the scatter and the fused
        Pallas dispatch build on (they must route byte-identically —
        the serving token-exactness contract rides on it): jittered
        top-k gating at ``cap`` with optional dead-token masking.
        Sets ``self.l_aux``; returns (idx, pos, keep, w)."""
        top_k = self.gate.top_k
        jitter = getattr(self.gate, "jitter", 0.0)
        training = self.training
        key = random_mod.next_key() if (jitter and training) else None

        def route(tok, wg, *rest):
            from .gate import topk_gating_sparse
            return topk_gating_sparse(tok @ wg, top_k, cap,
                                      train=training, key=key,
                                      switch_jitter=jitter,
                                      token_mask=rest[0] if rest
                                      else None)

        gate_args = [tokens, self.gate_weight]
        if token_mask is not None:
            gate_args.append(token_mask)
        idx, pos, keep, w, aux = run_op(
            "moe_gate_sparse", route, gate_args)
        self.l_aux = aux
        return idx, pos, keep, w

    def _forward_scatter(self, tokens, orig_shape, token_mask=None,
                         cap=None):
        """Sparse dispatch: scatter tokens into the [E*C, h] expert
        buffer by flat (expert, slot) index, gather+weight on the way
        back. No [N, E, C] tensors anywhere — cost O(N*k*H) vs the
        einsum's O(N*E*C*H).

        ``token_mask``/``cap`` are the serving decode-mode knobs (see
        ``forward``): dead tokens routed nowhere, capacity overridden
        to the no-drop worst case."""
        n, h = tokens.shape
        e = self.num_experts
        if cap is None:
            cap = self.gate.capacity(int(n))
        idx, pos, keep, w = self._sparse_route(tokens, cap, token_mask)

        expert_in = run_op(
            "moe_dispatch_scatter",
            lambda t, i, p, k: moe_dispatch_scatter(t, i, p, k, e, cap),
            [tokens, idx, pos, keep])
        deg = mesh_mod.axis_degree(self._ep_axis)
        ep_entry = self._ep_axis if (
            deg > 1 and e % deg == 0) else None
        expert_in = mark_sharding(expert_in, ep_entry, None, None)
        expert_out = self.experts(expert_in)
        expert_out = mark_sharding(expert_out, ep_entry, None, None)

        out = run_op(
            "moe_combine_gather",
            lambda o, i, p, k, ww: moe_combine_scatter(o, i, p, k, ww,
                                                       e, cap),
            [expert_out, idx, pos, keep, w])
        return out.reshape(orig_shape)

    def _pallas_fallback_reason(self, n_tokens, dtype, cap=None):
        """None when the fused Pallas grouped-matmul dispatch can serve
        this forward; else a short site tag naming why not (the
        `kernels.moe.dispatch_path.fallback.<site>` counter suffix and
        the one-time log). ``cap`` overrides the gate capacity (the
        decode-mode no-drop sizing)."""
        from .....core import place
        from .....kernels import moe as moe_kernels
        if not isinstance(self.experts, GroupedExpertsFFN):
            return "custom-experts"
        if self.experts._act not in ("gelu", "relu"):
            return "activation"
        if mesh_mod.axis_degree(self._ep_axis) > 1:
            # a pallas_call is a single opaque custom call: GSPMD
            # cannot shard it over 'ep', so expert-parallel meshes keep
            # the einsum dispatch (whose expert dim GSPMD turns into
            # the all-to-all)
            return "ep-sharded"
        if cap is None:
            cap = self.gate.capacity(int(n_tokens))
        d_hidden = int(self.experts.w1.shape[-1])
        if not moe_kernels.moe_pallas_eligible(self.d_model, d_hidden,
                                               cap, dtype):
            return "geometry"
        if _FORCE_PALLAS:
            return None
        if not place.accelerator_available():
            return "platform"
        return None

    def _forward_pallas(self, tokens, orig_shape, token_mask=None,
                        cap=None):
        """Fused dispatch: identical routing to dispatch_mode="scatter"
        (topk_gating_sparse), tokens scattered by (expert, slot) into a
        block-padded [E, cap_pad, h] buffer WITH their combine weights,
        then ONE Pallas grouped-matmul kernel runs both expert matmuls
        + activation + the combine-weight epilogue over only the LIVE
        token blocks (kernels/moe.py); the combine is the mirrored
        gather + add — the per-token weights were already applied in
        the kernel epilogue."""
        from .....kernels import moe as moe_kernels
        n, h = tokens.shape
        e = self.num_experts
        top_k = self.gate.top_k
        if cap is None:
            cap = self.gate.capacity(int(n))
        cap_pad = moe_kernels.padded_capacity(cap, unwrap(tokens).dtype)
        idx, pos, keep, w = self._sparse_route(tokens, cap, token_mask)

        def moe_dispatch_pallas(tok, idx, pos, keep, w):
            dst = jnp.where(keep, idx * cap_pad + pos, e * cap_pad)
            buf = jnp.zeros((e * cap_pad + 1, tok.shape[1]), tok.dtype)
            wbuf = jnp.zeros((e * cap_pad + 1, 1), jnp.float32)
            for r in range(top_k):
                buf = buf.at[dst[r]].add(tok)
                wbuf = wbuf.at[dst[r]].add(
                    (w[r] * keep[r]).astype(jnp.float32)[:, None])
            return (buf[:e * cap_pad].reshape(e, cap_pad, tok.shape[1]),
                    wbuf[:e * cap_pad].reshape(e, cap_pad, 1))

        expert_in, wslot = run_op("moe_dispatch_pallas",
                                  moe_dispatch_pallas,
                                  [tokens, idx, pos, keep, w])

        def count_fn(idx, keep):
            # kept assignments per expert (<= cap by construction):
            # the kernel's liveness prefix — everything at or past
            # counts[e] is capacity headroom it skips
            cbuf = jnp.zeros((e + 1,), jnp.int32)
            cbuf = cbuf.at[jnp.where(keep, idx, e).reshape(-1)].add(
                keep.reshape(-1).astype(jnp.int32))
            return cbuf[:e]

        counts = run_op_nodiff("moe_dispatch_counts", count_fn,
                               [idx, keep])

        ex = self.experts
        act = ex._act
        interpret = _PALLAS_INTERPRET
        force = _FORCE_PALLAS

        def grouped(xb, w1, b1, w2, b2, ws, cnt):
            return moe_kernels.grouped_ffn(
                xb, w1, b1, w2, b2, ws, cnt, activation=act,
                interpret=interpret, force_pallas=force)

        expert_out = run_op(
            "moe_grouped_ffn", grouped,
            [expert_in, ex.w1, ex.b1, ex.w2, ex.b2, wslot, counts])

        def moe_combine_pallas(eo, idx, pos, keep):
            flat = eo.reshape(e * cap_pad, eo.shape[-1])
            dst = jnp.where(keep, idx * cap_pad + pos, 0)
            out = 0.0
            for r in range(top_k):
                out = out + flat[dst[r]] * keep[r].astype(eo.dtype)[:, None]
            return out.astype(eo.dtype)

        out = run_op("moe_combine_pallas", moe_combine_pallas,
                     [expert_out, idx, pos, keep])
        return out.reshape(orig_shape)

    def _forward_decode(self, tokens, orig_shape, token_mask):
        """Serving decode mode (inference/engine.py, docs/SERVING.md
        "MoE serving"): the batch is a serving tick — engine decode
        lanes or a bucket-padded prefill chunk — not a training batch,
        so two rules change:

        * NO capacity drops: routing capacity is overridden to the
          token count (every token's top-k experts always fit).
          Capacity overflow is a training regularization; a SERVED
          token must never lose an expert to batch composition —
          that's also what makes a request's tokens independent of
          whichever other requests share its tick, the engine's
          token-exactness contract vs b=1 generate.
        * dead-lane masking: ``token_mask`` (False = idle decode lane)
          drops dead tokens from routing up front — they claim no
          buffer slot and no combine weight, and the fused kernel's
          per-expert live counts are built from ``keep``, so a dead
          slot issues NO expert weight DMA and no math. The expert
          capacity buffers are statically sized for the full tick but
          effectively sized per-tick by the live counts.

        Dispatch is the fused Pallas grouped-matmul when eligible,
        else the SPARSE scatter path (never the dense einsum — decode
        must stay O(N*k*H)); `kernels.moe.decode_path.*` records which
        at trace time (the engine republishes the deltas as
        `serving.moe.decode_path.*`) — a fallback is counter-visible,
        never silent."""
        from ..... import monitor
        n = int(tokens.shape[0])
        mask = None
        if token_mask is not None:
            mask = jnp.reshape(unwrap(token_mask), (-1,)).astype(bool)
        dtype = getattr(unwrap(tokens), "dtype", None)
        reason = self._pallas_fallback_reason(n, dtype, cap=n)
        if reason is None:
            monitor.counter(
                "kernels.moe.decode_path.pallas").increase()
            return self._forward_pallas(tokens, orig_shape,
                                        token_mask=mask, cap=n)
        monitor.counter(
            f"kernels.moe.decode_path.fallback.{reason}").increase()
        key = f"decode:{reason}"
        if key not in _pallas_fallback_logged:
            _pallas_fallback_logged.add(key)
            logging.getLogger(__name__).info(
                "MoE decode dispatch falling back to the sparse "
                "scatter path: %s (docs/KERNELS.md eligibility)",
                reason)
        return self._forward_scatter(tokens, orig_shape,
                                     token_mask=mask, cap=n)

    def _forward_sorted(self, tokens, orig_shape, token_mask=None):
        """Capacity-free dispatch for the sigmoid top-k gate, over the
        rows this chip holds. Every (token, pick) is sorted by the held
        expert it fell on; picks on experts held elsewhere, and the
        picks of dead tokens (``token_mask`` False), sort past the last
        group. The sorted order is then worked off in SLABS of
        ``_slab_rows`` rows, as many as the held picks fill: a slab
        gathers its tokens, runs the gated experts as grouped matmuls
        (kernels.moe.grouped_ffn_gated) with the part of each expert's
        group that lies in it, weights its rows in float32 and adds
        them onto their tokens. No array of the routed path has
        N * top_k rows, nothing is dropped and there is no capacity: a
        router that sends every pick here runs every slab of the order
        (about ``of / 2``), a uniform one runs one.

        A token's picks are added one at a time in the order of their
        place in the sorted order, which for the picks held here is the
        order of their experts, and a pick outside the slab adds an
        exact 0.0: the sum is a function of the token's own picks,
        whatever the batch, the slab bounds or the number of slabs
        (``_forward_decode``'s token-exactness contract).

        With every row in one slab (``of <= 2``, or a batch too small
        to split) there is no loop. Else the loop runs ``ceil(held
        picks / slab)`` times, a traced count, which has no reverse-mode
        derivative: in training it runs the static ``ceil(M / slab)``
        (the whole order, at the whole order's cost)."""
        from ..... import monitor
        from .....kernels.moe import grouped_ffn_gated
        from .gate import sigmoid_topk_routing
        gate, ex = self.gate, self.experts
        k, lo, held_n = gate.top_k, self.first_held, self.num_held
        m = int(tokens.shape[0]) * k
        s = _slab_rows(m, self.expert_share[1])
        all_trips = -(-m // s)          # 1: one slab takes every row
        training = self.training
        monitor.counter("kernels.moe.sorted.whole" if s == m
                        else "kernels.moe.sorted.slab").increase()

        def fn(tok, wr, bias, w1, w3, w2, *rest):
            n = tok.shape[0]
            live = (rest[0].reshape(-1).astype(bool) if rest
                    else jnp.ones((n,), bool))
            logits = jnp.dot(tok, wr.astype(tok.dtype),
                             preferred_element_type=jnp.float32)
            idx, w = sigmoid_topk_routing(
                logits, bias, k, gate.norm_topk_prob,
                gate.routed_scaling_factor)
            local = idx - lo
            held = (local >= 0) & (local < held_n) & live[:, None]
            key = jnp.where(held, local, held_n).reshape(-1)
            order = jnp.argsort(key, stable=True)
            counts = jnp.sum(
                key[:, None] == jnp.arange(held_n, dtype=key.dtype)[None],
                axis=0, dtype=jnp.int32)
            ends = jnp.cumsum(counts)
            # each token's picks as places in the sorted order, ascending,
            # with their weights
            place, w_at = jax.lax.sort(
                (jnp.argsort(order).reshape(n, k), jnp.where(held, w, 0.0)),
                dimension=1, num_keys=1)
            order = jnp.pad(order, (0, all_trips * s - m))

            def slab(i, acc):
                first = i * s
                sizes = (jnp.clip(ends, first, first + s)
                         - jnp.clip(ends - counts, first, first + s))
                mine = jax.lax.dynamic_slice(order, (first,), (s,))
                rows = grouped_ffn_gated(
                    jnp.take(tok, mine // k, axis=0, mode="clip"),
                    w1, w3, w2, sizes)
                for j in range(k):
                    at = place[:, j] - first
                    row = jnp.take(rows, at, axis=0, mode="clip")
                    acc = acc + jnp.where(
                        ((at >= 0) & (at < s))[:, None],
                        row.astype(jnp.float32) * w_at[:, j:j + 1], 0.0)
                return acc

            acc = jnp.zeros((n, tok.shape[1]), jnp.float32)
            if all_trips == 1:
                trips, out = 1, slab(0, acc)
            else:
                trips = all_trips if training else -(-ends[-1] // s)
                out = jax.lax.fori_loop(0, trips, slab, acc)
            stats = jnp.stack([jnp.sum(held), jnp.sum(live) * k,
                               jnp.sum(counts > 0),
                               jnp.asarray(trips)]).astype(jnp.int32)
            return out.astype(tok.dtype), stats

        args = [tokens, self.gate_weight, self.e_score_correction_bias,
                ex.w1, ex.w3, ex.w2]
        if token_mask is not None:
            args.append(token_mask)
        out, self.last_stats = run_op("moe_sorted_ffn", fn, args)
        out = out.reshape(orig_shape)
        if self.shared_experts is not None:
            out = out + self.shared_experts(tokens).reshape(orig_shape)
        return out

    def forward(self, x, token_mask=None, decode_mode=False):
        """x: [batch, seq, h] or [N, h]. Bumps the trace-time
        `kernels.moe.dispatch_path.*` counter for whichever dispatch
        implementation this forward bakes in (docs/OBSERVABILITY.md
        "MoE dispatch path counters") — a pallas layer that degrades to
        einsum is counter-visible, never silent.

        ``decode_mode=True`` is the serving engine's KV-cache decode
        path (see ``_forward_decode``): no-drop routing capacity plus
        ``token_mask`` dead-lane masking, dispatched on the fused
        Pallas kernel or the sparse scatter path."""
        from ..... import monitor
        orig_shape = list(x.shape)
        h = orig_shape[-1]
        tokens = x.reshape([-1, h])
        if self._sorted:
            monitor.counter(
                "kernels.moe.decode_path.ragged" if decode_mode
                else "kernels.moe.dispatch_path.ragged").increase()
            return self._forward_sorted(tokens, orig_shape, token_mask)
        if decode_mode:
            return self._forward_decode(tokens, orig_shape, token_mask)
        mode = self._dispatch_mode
        if mode == "pallas":
            dtype = getattr(unwrap(tokens), "dtype", None)
            reason = self._pallas_fallback_reason(tokens.shape[0], dtype)
            if reason is None:
                monitor.counter(
                    "kernels.moe.dispatch_path.pallas").increase()
                return self._forward_pallas(tokens, orig_shape)
            monitor.counter(
                f"kernels.moe.dispatch_path.fallback.{reason}").increase()
            if reason not in _pallas_fallback_logged:
                _pallas_fallback_logged.add(reason)
                logging.getLogger(__name__).info(
                    "MoE dispatch_mode='pallas' falling back to the "
                    "einsum dispatch: %s (docs/KERNELS.md eligibility)",
                    reason)
            mode = "einsum"
        if mode == "scatter":
            monitor.counter("kernels.moe.dispatch_path.scatter").increase()
            return self._forward_scatter(tokens, orig_shape)
        monitor.counter("kernels.moe.dispatch_path.einsum").increase()
        n = tokens.shape[0]
        top_k = self.gate.top_k
        ng = self._n_groups(int(n))
        cap = self.gate.capacity(int(n) // ng)
        jitter = getattr(self.gate, "jitter", 0.0)
        training = self.training
        key = random_mod.next_key() if (jitter and training) else None
        e = self.num_experts

        def gating(tok, wg):
            from .gate import topk_gating
            logits = tok @ wg
            if ng == 1:
                return topk_gating(logits, top_k, cap, train=training,
                                   key=key, switch_jitter=jitter)
            # group-wise dispatch: jitter once over all tokens, then
            # route each group with its own capacity (aux = group mean)
            from .gate import apply_router_jitter
            logits = apply_router_jitter(logits, jitter, training, key)
            lg = logits.reshape(ng, n // ng, e)
            d, c, aux = jax.vmap(
                lambda l: topk_gating(l, top_k, cap, train=training))(lg)
            return d, c, jnp.mean(aux)

        dispatch, combine, aux = run_op(
            "moe_gate", gating, [tokens, self.gate_weight])
        self.l_aux = aux

        expert_in = run_op("moe_dispatch", moe_dispatch_einsum,
                           [tokens, dispatch])
        # commit the all-to-all: expert dim sharded over 'ep' (only when
        # the expert count divides the axis degree)
        deg = mesh_mod.axis_degree(self._ep_axis)
        ep_entry = self._ep_axis if (
            deg > 1 and self.num_experts % deg == 0) else None
        expert_in = mark_sharding(expert_in, ep_entry, None, None)
        expert_out = self.experts(expert_in)
        expert_out = mark_sharding(expert_out, ep_entry, None, None)

        out = run_op("moe_combine", moe_combine_einsum,
                     [expert_out, combine])
        return out.reshape(orig_shape)
