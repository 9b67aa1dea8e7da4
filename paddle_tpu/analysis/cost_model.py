"""Static cost model over a closed jaxpr — bytes moved, FLOPs, peak HBM.

The runtime profiler measures these after a step has executed; this
pass derives the same three numbers from the abstract trace alone, so a
partition plan can be rejected before any device is attached (the
plan-selection move of arXiv 2112.01075 / 2412.14374, surfaced as lint
output instead of a planner).

All estimates are per *rank* when the jaxpr came from a shard_map
manual region (shapes in the jaxpr are already per-device there) and
global otherwise — shard_lint's entry points trace through shard_map,
so its reports are per-rank. Plain-jit traces under a mesh
(`inspect(mesh=...)`) carry a `note` saying so: GSPMD-auto programs
get their collectives from the XLA partitioner, which a static jaxpr
walk cannot see.

Deliberately distinct from `paddle_tpu.cost_model` (the roofline
CostModel): that package turns op shapes into *time* on a specific
chip (peak FLOP/s, HBM/ICI bandwidth, in-place calibration); this one
derives *counts* (bytes, FLOPs, live bytes) from a program. Feed these
counts into `CostModel.collective_time`/`matmul_time` to get seconds —
the ring factors here and there must agree.

Formulas (docs/ANALYSIS.md "cost model"):

* collective bytes, per rank, for an n-device axis group over an
  operand of b bytes:
    - psum / pmax / pmin (all_reduce):   2 * b * (n-1)/n   (ring)
    - all_gather:                        b * (n-1)          (b = shard)
    - psum_scatter (reduce_scatter):     b * (n-1)/n
    - all_to_all:                        b * (n-1)/n
    - ppermute (send+recv one hop):      b
* FLOPs: 2*M*N*K per dot_general contraction (x batch),
  2 * out_numel * (Cin/groups * prod(kernel)) per conv, 1 FLOP per
  output element for everything else that computes.
* peak HBM: liveness walk over the equations in program order —
  allocate outvars, free invars at their last use; the running
  maximum plus closed-over constants is the estimate. Control-flow
  bodies (scan/cond/pjit/shard_map) contribute max(inner peak) on top
  of the live set at their call site.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
from jax.extend import core as jcore
import numpy as np

# primitives that move data across mesh axes, with their per-rank byte
# multiplier as a function of the axis-group size n
_COLLECTIVE_FACTORS = {
    "psum": lambda n: 2.0 * (n - 1) / n,
    "pmax": lambda n: 2.0 * (n - 1) / n,
    "pmin": lambda n: 2.0 * (n - 1) / n,
    "all_gather": lambda n: float(n - 1),
    "psum_scatter": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
    "pshuffle": lambda n: 1.0,
}

# pure layout/metadata plumbing: zero FLOPs
_ZERO_FLOP = {"broadcast_in_dim", "reshape", "convert_element_type",
              "squeeze", "expand_dims", "transpose", "slice", "iota",
              "copy", "stop_gradient", "pvary", "pcast", "constant",
              "dynamic_slice", "dynamic_update_slice", "concatenate",
              "gather", "scatter", "pad", "rev", "device_put",
              "sharding_constraint"}


def _nbytes(aval) -> int:
    shape = tuple(getattr(aval, "shape", ()) or ())
    try:
        itemsize = np.dtype(aval.dtype).itemsize
    except TypeError:
        itemsize = 2 if str(getattr(aval, "dtype", "")) == "bfloat16" else 4
    return int(math.prod(shape)) * itemsize


def axis_sizes(mesh) -> Dict[str, int]:
    """{axis name: degree} for a jax Mesh OR AbstractMesh (device-free).
    One implementation only — distributed.mesh owns it."""
    if mesh is None:
        return {}
    from ..distributed.mesh import mesh_axis_sizes
    return mesh_axis_sizes(mesh)


def _eqn_axes(eqn) -> tuple:
    """Mesh axis names a collective eqn moves data over."""
    axes = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(str(ax) for ax in axes)


def _group_size(eqn, sizes: Dict[str, int]) -> int:
    n = 1
    for ax in _eqn_axes(eqn):
        n *= int(sizes.get(ax, 1))
    groups = eqn.params.get("axis_index_groups")
    if groups:
        n = len(groups[0])
    return max(n, 1)


def _dot_flops(eqn) -> float:
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = math.prod(lhs[i] for i in lb) if lb else 1
    k = math.prod(lhs[i] for i in lc) if lc else 1
    m = math.prod(d for i, d in enumerate(lhs) if i not in lc and i not in lb)
    n = math.prod(d for i, d in enumerate(rhs) if i not in rc and i not in rb)
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape  # kernel, layout-dependent
    # MACs = out_numel * (kernel numel / out_channels) regardless of the
    # dimension_numbers layout: kernel numel already folds Cin/groups,
    # so NO extra division by feature_group_count
    out_numel = math.prod(out)
    kernel = math.prod(rhs)
    dn = eqn.params.get("dimension_numbers")
    if hasattr(dn, "rhs_spec"):  # rhs_spec[0] = kernel out-channel dim
        out_ch = rhs[dn.rhs_spec[0]]
    else:
        out_ch = max(1, min(rhs))  # conservative when layout is unknown
    return 2.0 * out_numel * (kernel / max(out_ch, 1))


@dataclasses.dataclass
class CostEstimate:
    """Static per-rank cost of one staged program."""
    flops: float = 0.0
    collective_bytes: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    collective_calls: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    # same bytes keyed by the mesh axes they ride ("dp", "dp,sharding",
    # ...) — the planner's tier split (ICI vs DCN) reads this
    collective_bytes_by_axis: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    peak_hbm_bytes: float = 0.0
    n_devices: int = 1
    # qualifier printed with the table, e.g. the GSPMD-auto caveat (the
    # partitioner inserts collectives this static walk cannot see)
    note: str = ""

    @property
    def total_collective_bytes(self) -> float:
        return float(sum(self.collective_bytes.values()))

    def tier_bytes(self, dcn_axes=()) -> "tuple[float, float]":
        """Split the per-rank collective bytes into (ici, dcn) tiers: a
        collective whose group touches ANY axis in `dcn_axes` is charged
        to the DCN tier wholesale (its ring spans slices, so the slow
        hop gates the whole rotation)."""
        dcn_axes = set(dcn_axes)
        ici = dcn = 0.0
        for key, b in self.collective_bytes_by_axis.items():
            if dcn_axes and set(key.split(",")) & dcn_axes:
                dcn += b
            else:
                ici += b
        return ici, dcn

    def merge(self, other: "CostEstimate") -> "CostEstimate":
        self.flops += other.flops
        for k, v in other.collective_bytes.items():
            self.collective_bytes[k] = self.collective_bytes.get(k, 0) + v
        for k, v in other.collective_calls.items():
            self.collective_calls[k] = self.collective_calls.get(k, 0) + v
        for k, v in other.collective_bytes_by_axis.items():
            self.collective_bytes_by_axis[k] = \
                self.collective_bytes_by_axis.get(k, 0) + v
        self.peak_hbm_bytes = max(self.peak_hbm_bytes, other.peak_hbm_bytes)
        self.n_devices = max(self.n_devices, other.n_devices)
        self.note = self.note or other.note
        return self

    def to_dict(self) -> Dict[str, object]:
        return {
            "flops": self.flops,
            "collective_bytes": dict(self.collective_bytes),
            "collective_calls": dict(self.collective_calls),
            "collective_bytes_by_axis": dict(self.collective_bytes_by_axis),
            "total_collective_bytes": self.total_collective_bytes,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "n_devices": self.n_devices,
            "note": self.note,
        }

    @staticmethod
    def _human(n: float) -> str:
        for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
            if abs(n) < 1024 or unit == "TiB":
                return f"{n:.1f} {unit}" if unit != "B" \
                    else f"{n:.0f} {unit}"
            n /= 1024.0
        return f"{n:.1f} TiB"

    def format_table(self) -> str:
        lines = ["-- static cost (per rank) --",
                 f"  flops            {self.flops:.3e}",
                 f"  peak HBM         {self._human(self.peak_hbm_bytes)}"]
        if self.collective_bytes:
            lines.append(f"  collective bytes "
                         f"{self._human(self.total_collective_bytes)}")
            for kind in sorted(self.collective_bytes):
                lines.append(
                    f"    {kind:<14} {self.collective_calls[kind]:>4} "
                    f"call(s)  {self._human(self.collective_bytes[kind])}")
        else:
            lines.append("  collective bytes 0 B (no explicit "
                         "collectives traced)")
        if self.note:
            lines.append(f"  note: {self.note}")
        return "\n".join(lines)


def _walk(jaxpr, sizes: Dict[str, int], est: CostEstimate,
          repeat: float = 1.0) -> float:
    """Accumulate flops/bytes of `jaxpr` into `est` and return its peak
    live-bytes estimate (invars/consts excluded — charged by caller)."""
    # last-use index per var id for the liveness walk
    last_use: Dict[int, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, jcore.Literal):
                last_use[id(v)] = i
    for v in jaxpr.outvars:
        last_use[id(v)] = len(jaxpr.eqns)

    live: Dict[int, int] = {}
    peak = 0.0
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        inner_peak = 0.0
        inner_repeat = repeat
        sub = []
        if name == "scan":
            inner_repeat *= int(eqn.params.get("length", 1) or 1)
        for p in eqn.params.values():
            if isinstance(p, jcore.ClosedJaxpr):
                sub.append(p.jaxpr)
            elif isinstance(p, jcore.Jaxpr):
                sub.append(p)
            elif isinstance(p, (list, tuple)):
                sub.extend(q.jaxpr if isinstance(q, jcore.ClosedJaxpr)
                           else q for q in p
                           if isinstance(q, (jcore.Jaxpr,
                                             jcore.ClosedJaxpr)))
        if name == "cond":
            # branches are alternatives: flops of the widest branch,
            # peak of the most memory-hungry one (they may differ)
            branch_est = []
            for s in sub:
                e = CostEstimate()
                pk = _walk(s, sizes, e, repeat)
                branch_est.append((e, pk))
            if branch_est:
                widest, _ = max(branch_est, key=lambda t: t[0].flops)
                est.merge(widest)
                inner_peak = max(pk for _, pk in branch_est)
        else:
            for s in sub:
                inner_peak = max(inner_peak,
                                 _walk(s, sizes, est, inner_repeat))

        if name in _COLLECTIVE_FACTORS:
            n = _group_size(eqn, sizes)
            if n > 1:
                b = sum(_nbytes(v.aval) for v in eqn.invars
                        if hasattr(v, "aval"))
                kind = "all_reduce" if name in ("psum", "pmax", "pmin") \
                    else name
                moved = _COLLECTIVE_FACTORS[name](n) * b * repeat
                est.collective_bytes[kind] = \
                    est.collective_bytes.get(kind, 0.0) + moved
                est.collective_calls[kind] = \
                    est.collective_calls.get(kind, 0) + int(repeat)
                axes_key = ",".join(_eqn_axes(eqn)) or "<group>"
                est.collective_bytes_by_axis[axes_key] = \
                    est.collective_bytes_by_axis.get(axes_key, 0.0) + moved
                est.n_devices = max(est.n_devices, n)
        elif name == "dot_general":
            est.flops += _dot_flops(eqn) * repeat
        elif name == "conv_general_dilated":
            est.flops += _conv_flops(eqn) * repeat
        elif not sub and name not in _ZERO_FLOP:
            est.flops += sum(
                math.prod(getattr(v.aval, "shape", ()) or ())
                for v in eqn.outvars if hasattr(v, "aval")) * repeat

        # liveness accounting
        for v in eqn.outvars:
            if hasattr(v, "aval"):
                live[id(v)] = _nbytes(v.aval)
        peak = max(peak, sum(live.values()) + inner_peak)
        for v in list(eqn.invars) + list(eqn.outvars):
            if not isinstance(v, jcore.Literal) \
                    and last_use.get(id(v), -1) <= i:
                live.pop(id(v), None)
    return peak


def estimate_jaxpr(closed, mesh=None) -> CostEstimate:
    """Static cost of a ClosedJaxpr: FLOPs, per-collective bytes moved,
    and a peak-HBM estimate. Never executes anything."""
    est = CostEstimate()
    sizes = axis_sizes(mesh)
    est.n_devices = max(1, math.prod(sizes.values()) if sizes else 1)
    base = sum(_nbytes(v.aval) for v in closed.jaxpr.invars)
    base += sum(int(getattr(c, "nbytes", 0)) for c in closed.consts)
    inner = _walk(closed.jaxpr, sizes, est)
    est.peak_hbm_bytes = base + inner
    return est


def emit_cost(est: Optional[CostEstimate]):
    """Publish a cost estimate as lint.cost.* monitor gauges (same
    registry the runtime telemetry uses, docs/OBSERVABILITY.md)."""
    if est is None:
        return
    from .. import monitor
    monitor.gauge("lint.cost.flops").set(est.flops)
    monitor.gauge("lint.cost.collective_bytes").set(
        est.total_collective_bytes)
    monitor.gauge("lint.cost.peak_hbm_bytes").set(est.peak_hbm_bytes)
