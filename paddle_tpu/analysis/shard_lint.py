"""shard_lint — ahead-of-time SPMD/collective analyzer.

`jaxpr_lint` checks single-device programs; this pass checks the layers
the system actually scales on — mesh/SPMD, collectives, pipeline and
zero-bubble schedules — with ZERO devices attached. A bad partition
spec, an indivisible all_to_all, or a stage-imbalanced pipeline today
only fails (or silently wastes HBM/ICI bandwidth) once hardware is
present; here the same defects fall out of an abstract
`jax.make_jaxpr` trace under a *fake mesh* (`jax.sharding.AbstractMesh`
— no device array, so an 8-rank plan lints on a 1-CPU laptop).

Two sources of evidence, one Report:

* **Collective call records.** While the abstract trace runs, a
  recorder installed into `distributed.communication.collectives`
  captures every collective entry point's (op, group, operand shape,
  list arity, split sizes) with the USER file:line. Validation against
  the fake mesh catches axis names that match no mesh axis (the
  runtime path would silently degrade to the eager identity),
  rank-misaligned groups, indivisible dim-0 splits, uneven
  `alltoall_single` splits, wrong tensor-list arity, and `send`/`recv`
  inside traced code.
* **The staged jaxpr.** Rule passes walk the traced program for
  `ppermute` permutations that do not cover the axis ring (uncovered
  ranks silently receive zeros), and the static cost model
  (`analysis.cost_model`) folds every collective/contraction into
  per-rank bytes-moved / FLOPs / peak-HBM numbers — the quantities
  arXiv 2112.01075 and 2412.14374 plan with, emitted here as
  `lint.cost.*` gauges and a `--cost` CLI table.

`lint_pipeline` checks schedule plans (PipelineLayer /
PipelineParallel) without tracing shard_map at all: stage
parameter/FLOP imbalance, bubble fraction per schedule mode (the exact
`schedule_stats` formulas the compiled schedules use), microbatch
arity, and heterogeneous-segment mismatches.
"""
from __future__ import annotations

import contextlib
import inspect as _inspect
import math
import os
import traceback
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from . import cost_model
from .findings import (BAD_AXIS_NAME, BUBBLE_FRACTION, ERROR, GRAPH_BREAK,
                      INDIVISIBLE_COLLECTIVE, MICROBATCH_ARITY,
                      NON_RING_PERMUTE, P2P_IN_TRACE, SEGMENT_MISMATCH,
                      STAGE_IMBALANCE, TENSOR_LIST_ARITY, TRACE_FAILED,
                      UNALIGNED_GROUP, UNEVEN_SPLIT, WARNING, Finding,
                      Report)
from .jaxpr_lint import _eqn_loc, _walk_eqns, to_shape_struct

# a schedule spending more than this fraction of wall ticks in bubbles
# is flagged (GPipe at the common accumulate_steps == pp setting sits
# at (S-1)/(2S-1) ~ 0.43 — exactly the config worth a warning)
BUBBLE_WARN_FRACTION = 0.30
# max/mean per-stage weight above this flags a lopsided segmentation
STAGE_IMBALANCE_RATIO = 1.5

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _user_loc() -> Tuple[str, int]:
    """First stack frame outside paddle_tpu (and jax) — the user call
    site a finding should point at."""
    for frame in reversed(traceback.extract_stack()):
        fn = frame.filename
        if fn.startswith(_PKG_DIR) or f"{os.sep}jax{os.sep}" in fn \
                or fn.startswith("<"):
            continue
        return fn, int(frame.lineno or 0)
    return "<unknown>", 0


def _layer_loc(obj) -> Tuple[str, int]:
    """Best-effort file:line of a layer/callable's definition."""
    try:
        target = obj if _inspect.isfunction(obj) else type(obj)
        fn = _inspect.getsourcefile(target) or "<unknown>"
        line = _inspect.getsourcelines(target)[1]
        return fn, int(line)
    except (OSError, TypeError):
        return "<unknown>", 0


def as_mesh(mesh):
    """Accept a Mesh, AbstractMesh, or {axis: degree} dict (turned into
    a device-free fake mesh)."""
    if isinstance(mesh, dict):
        from ..distributed import mesh as mesh_mod
        return mesh_mod.fake_mesh(mesh)
    return mesh


class CollectiveRecorder:
    """Collects one record per collective call made during an abstract
    trace (installed via `recording()`); group metadata is extracted
    defensively so a broken group still yields a record, not a crash."""

    def __init__(self):
        self.records: List[Dict[str, Any]] = []

    def add(self, op: str, group, shape=(), dtype="", n_list=None,
            splits=None):
        axes: Optional[Tuple[str, ...]]
        unaligned = False
        try:
            axes = tuple(group.axis_names)
        except ValueError:
            axes, unaligned = None, True
        except Exception:
            axes = None
        try:
            nranks = int(group.nranks)
        except Exception:
            nranks = 1
        fname, line = _user_loc()
        self.records.append({
            "op": op, "axes": axes, "unaligned": unaligned,
            "nranks": nranks, "ranks": getattr(group, "_ranks", None),
            "group": getattr(group, "name", ""), "shape": tuple(shape),
            "dtype": dtype, "n_list": n_list, "splits": splits,
            "file": fname, "line": line,
        })


@contextlib.contextmanager
def recording(mesh=None):
    """Install the collective recorder (and, when given, the fake mesh
    as the global paddle mesh so Group/axis introspection resolves
    device-free). Restores both on exit — lint must never leak state
    into the program under analysis.

    LINT-INTERNAL, and process-global: while the recorder is installed,
    collective arg validation is reported as findings instead of raised,
    and invalid calls degrade to identity so one abstract trace can
    surface every defect. Never wrap code that actually EXECUTES — it
    would run with validation off (deliberately not exported from
    paddle_tpu.analysis for this reason)."""
    from ..distributed import mesh as mesh_mod
    from ..distributed.communication import collectives as coll
    rec = CollectiveRecorder()
    prev_rec = coll._collective_recorder
    prev_mesh = mesh_mod.get_mesh()
    coll._collective_recorder = rec
    if mesh is not None:
        mesh_mod._global_mesh = as_mesh(mesh)
    try:
        yield rec
    finally:
        coll._collective_recorder = prev_rec
        mesh_mod._global_mesh = prev_mesh


# -- record validation -------------------------------------------------------

_SPLITTING = ("all_to_all", "alltoall_single", "reduce_scatter")


def lint_records(records: Sequence[Dict[str, Any]],
                 mesh) -> List[Finding]:
    """Validate recorded collective calls against the (fake) mesh."""
    sizes = cost_model.axis_sizes(as_mesh(mesh))
    findings: List[Finding] = []
    seen = set()

    def add(f: Finding):
        key = (f.rule, f.file, f.line, f.message)
        if key not in seen:
            seen.add(key)
            findings.append(f)

    for r in records:
        op, fname, line = r["op"], r["file"], r["line"]
        if op in ("send", "recv"):
            add(Finding(
                rule=P2P_IN_TRACE, severity=ERROR,
                message=f"{op}() inside traced code — raw p2p has no XLA "
                        "lowering on TPU (RuntimeError when the axis is "
                        "bound, silent no-op otherwise)",
                file=fname, line=line,
                suggestion="use p2p_shift (lax.ppermute) or a compiled "
                           "pipeline schedule for stage-to-stage "
                           "transfer"))
            continue
        if r["unaligned"]:
            add(Finding(
                rule=UNALIGNED_GROUP, severity=ERROR,
                message=f"{op} over group built from ranks={r['ranks']} "
                        "which match no axis-group of the mesh — compiled "
                        "collectives need axis-aligned groups",
                file=fname, line=line,
                suggestion="build the mesh so the group is one axis, or "
                           "pass axis_name= to new_group"))
            continue
        axes = r["axes"] or ()
        missing = [ax for ax in axes if ax not in sizes]
        if missing:
            add(Finding(
                rule=BAD_AXIS_NAME, severity=ERROR,
                message=f"{op} over axis name(s) {missing} not in the "
                        f"mesh ({tuple(sizes) or 'no axes'}) — at runtime "
                        "the axis never binds, so the collective SILENTLY "
                        "degrades to the eager identity path",
                file=fname, line=line,
                suggestion="fix the axis name (mesh axes are "
                           f"{tuple(sizes)}) or add the axis to "
                           "build_mesh(degrees=...)"))
            continue
        n = 1
        for ax in axes:
            n *= sizes.get(ax, 1)
        if not axes:
            n = max(1, r["nranks"])
        if n <= 1:
            continue
        if op in _SPLITTING:
            if r["n_list"] is not None and r["n_list"] > 0 \
                    and r["n_list"] != n:
                add(Finding(
                    rule=TENSOR_LIST_ARITY, severity=ERROR,
                    message=f"{op}: tensor list has {r['n_list']} "
                            f"entries but the group spans {n} rank(s) — "
                            "one entry per rank required",
                    file=fname, line=line,
                    suggestion=f"pass exactly {n} tensors (group axes "
                               f"{axes})"))
            elif r["n_list"] is None and r["shape"]:
                dim0 = r["shape"][0]
                # single-tensor all_to_all lowers UNTILED: dim 0 must
                # EQUAL the group size; the tiled forms need dim 0
                # divisible by it
                bad = dim0 != n if op == "all_to_all" else dim0 % n != 0
                if bad:
                    req = ("must equal" if op == "all_to_all"
                           else "is not divisible by")
                    add(Finding(
                        rule=INDIVISIBLE_COLLECTIVE, severity=ERROR,
                        message=f"{op}: input dim 0 ({dim0}) {req} the "
                                f"group size ({n}) — lax rejects the "
                                "split at trace time, after a device is "
                                "attached",
                        file=fname, line=line,
                        suggestion=("pass one dim-0 slice per rank (or "
                                    "use alltoall_single for the tiled "
                                    "even-split form)"
                                    if op == "all_to_all" else
                                    "pad dim 0 to a multiple of the "
                                    "axis degree, or change the mesh "
                                    "degree")))
        if op == "alltoall_single" and r["splits"]:
            for sizes_ in r["splits"]:
                if sizes_ and len(set(sizes_)) > 1:
                    add(Finding(
                        rule=UNEVEN_SPLIT, severity=ERROR,
                        message=f"alltoall_single with uneven split "
                                f"sizes {list(sizes_)} — lax.all_to_all "
                                "is tiled; this raises "
                                "NotImplementedError at runtime",
                        file=fname, line=line,
                        suggestion="pad the shards to equal size (even "
                                   "splits) or drop the split_sizes "
                                   "arguments"))
                    break
        if op == "scatter" and r["n_list"] not in (None, 0) \
                and r["n_list"] != n:
            add(Finding(
                rule=TENSOR_LIST_ARITY, severity=ERROR,
                message=f"scatter: tensor_list has {r['n_list']} entries "
                        f"but the group spans {n} rank(s)",
                file=fname, line=line,
                suggestion=f"pass exactly {n} tensors"))
    return findings


# -- jaxpr passes ------------------------------------------------------------

def lint_jaxpr_collectives(closed, mesh) -> List[Finding]:
    """Walk the staged program for collective defects the record pass
    cannot see: raw lax.ppermute rings that do not cover the axis."""
    sizes = cost_model.axis_sizes(as_mesh(mesh))
    findings: List[Finding] = []
    seen = set()
    for eqn in _walk_eqns(closed.jaxpr):
        if eqn.primitive.name != "ppermute":
            continue
        axes = eqn.params.get("axis_name")
        if not isinstance(axes, (tuple, list)):
            axes = (axes,)
        n = 1
        for ax in axes:
            n *= sizes.get(ax, 1)
        perm = list(eqn.params.get("perm") or ())
        srcs = [p[0] for p in perm]
        dsts = [p[1] for p in perm]
        full = set(range(n))
        ok = (len(set(srcs)) == len(srcs) and len(set(dsts)) == len(dsts)
              and set(srcs) == full and set(dsts) == full)
        if ok or n <= 1:
            continue
        fname, line = _eqn_loc(eqn)
        key = (fname, line, tuple(perm))
        if key in seen:
            continue
        seen.add(key)
        uncovered = sorted(full - set(dsts))
        findings.append(Finding(
            rule=NON_RING_PERMUTE, severity=WARNING,
            message=f"ppermute over axis {axes} (size {n}) with perm "
                    f"{perm} is not a full permutation — rank(s) "
                    f"{uncovered[:4]}{'...' if len(uncovered) > 4 else ''} "
                    "silently receive zeros",
            file=fname, line=line,
            suggestion="cover every rank, e.g. ring_perm(n): "
                       "[(i, (i+shift) % n) for i in range(n)]"))
    return findings


# -- sharded-program entry point --------------------------------------------

def lint_sharded(fn, args=(), kwargs=None, *, mesh,
                 in_specs=None, out_specs=None,
                 subject: Optional[str] = None,
                 with_cost: bool = True) -> Report:
    """Abstract-trace `fn` inside a shard_map manual region over ALL
    axes of the (fake) mesh and run every shard rule + the cost model.

    `args` may be InputSpec / Tensor / array / ShapeDtypeStruct — only
    shapes and dtypes are read; nothing executes on any device.
    `in_specs` defaults to fully-replicated (each rank sees the whole
    example), so per-rank shapes equal the given shapes."""
    from jax.sharding import PartitionSpec as P

    mesh = as_mesh(mesh)
    kwargs = dict(kwargs or {})
    report = Report(subject=subject
                    or getattr(fn, "__qualname__", repr(fn)))

    structs = []
    for a in args:
        s = to_shape_struct(a)
        structs.append(s if s is not None else a)
    if in_specs is None:
        in_specs = tuple(P() for _ in structs)
    if out_specs is None:
        out_specs = P()

    def call(*xs):
        return fn(*xs, **kwargs)

    wrapped = jax.shard_map(call, mesh=mesh, in_specs=tuple(in_specs),
                            out_specs=out_specs, check_vma=False)
    closed = None
    with recording(mesh) as rec:
        try:
            closed = jax.make_jaxpr(wrapped)(*structs)
        except Exception as exc:  # classified below — inspect stays total
            report.add(_classify_trace_error(exc))
    report.extend(lint_records(rec.records, mesh))
    if closed is not None:
        report.extend(lint_jaxpr_collectives(closed, mesh))
        if with_cost:
            report.cost = cost_model.estimate_jaxpr(closed, mesh)
    return report


def _classify_trace_error(exc: Exception) -> Finding:
    """Turn an abstract-trace failure into the most specific finding:
    raw-lax collective errors get their own rules, graph breaks keep
    jaxpr_lint's classification, the rest is trace-failed."""
    msg = str(exc).strip().splitlines()[0] if str(exc).strip() else ""
    if "divisible by the size of the named axis" in msg \
            or "to be divisible by" in msg and "axis" in msg:
        return Finding(
            rule=INDIVISIBLE_COLLECTIVE, severity=ERROR,
            message=f"collective split rejected at trace time: {msg}",
            suggestion="pad the split dim to a multiple of the axis "
                       "degree, or change the mesh degree")
    if isinstance(exc, NameError) and "unbound axis name" in msg:
        return Finding(
            rule=BAD_AXIS_NAME, severity=ERROR,
            message=f"collective over an axis the mesh does not bind: "
                    f"{msg}",
            suggestion="fix the axis name or add it to the mesh degrees")
    from .jaxpr_lint import _break_errors
    if isinstance(exc, _break_errors()):
        return Finding(
            rule=GRAPH_BREAK, severity=ERROR,
            message=f"the sharded trace itself breaks: {msg}",
            breaks_with=type(exc).__name__,
            suggestion="restructure with lax.cond/while_loop so the "
                       "sharded program stays compiled")
    return Finding(
        rule=TRACE_FAILED, severity=WARNING,
        message=f"abstract sharded trace failed "
                f"({type(exc).__name__}): {msg}",
        suggestion="shard rules were skipped; check the example "
                   "shapes/specs and in_specs match the function")


# -- pipeline / schedule entry point -----------------------------------------

def _stage_param_numel(items) -> int:
    total = 0
    seen = set()
    for item in items:
        lyr = item[0] if isinstance(item, tuple) else item
        params = getattr(lyr, "parameters", None)
        if params is None:
            continue
        for p in params():
            if id(p) in seen:
                continue
            seen.add(id(p))
            total += int(math.prod(p.shape) if p.shape else 1)
    return total


def _imbalance(values: List[float]) -> float:
    live = [v for v in values if v > 0]
    if len(live) < 2:
        return 1.0
    return max(live) / (sum(live) / len(live))


def lint_pipeline(pipe, *, n_micro: Optional[int] = None,
                  schedule_mode: Optional[str] = None,
                  vpp_degree: Optional[int] = None,
                  input_spec=None,
                  subject: Optional[str] = None,
                  with_cost: bool = True) -> Report:
    """Statically check a pipeline plan — no mesh, no devices, no
    shard_map: stage imbalance, bubble fraction, microbatch arity,
    heterogeneous-segment mismatches, plus (with an input_spec) a
    per-stage FLOP profile and schedule cost estimate.

    `pipe` is a PipelineLayer or a PipelineParallel (whose strategy
    supplies n_micro/schedule_mode/vpp_degree defaults)."""
    model = None
    if hasattr(pipe, "_layers") and hasattr(pipe, "accumulate_steps"):
        model, pipe = pipe, pipe._layers
    S = int(pipe.get_num_stages())
    V = int(vpp_degree if vpp_degree is not None else
            (model.vpp_degree if model is not None
             else getattr(pipe, "_num_virtual_stages", 1)) or 1)
    M = int(n_micro if n_micro is not None else
            (model.accumulate_steps if model is not None else S) or S)
    mode = (schedule_mode if schedule_mode is not None else
            (model.schedule_mode if model is not None else "")) or \
        ("VPP" if V > 1 else "FThenB")

    report = Report(subject=subject or f"pipeline({type(pipe).__name__}, "
                    f"S={S}, M={M}, mode={mode})")
    if S <= 1:
        return report

    first_item = pipe.stage_items(0)[0] if pipe.stage_items(0) else pipe
    pfile, pline = _layer_loc(first_item[0] if isinstance(first_item, tuple)
                              else first_item)

    # -- microbatch arity ---------------------------------------------------
    if V > 1 and M < S:
        report.add(Finding(
            rule=MICROBATCH_ARITY, severity=ERROR,
            message=f"interleaved (VPP/ZBVPP) schedule needs "
                    f"accumulate_steps >= pp degree, got M={M} < S={S} — "
                    "the schedule constructor raises ValueError",
            file=pfile, line=pline,
            suggestion=f"set pipeline_configs['accumulate_steps'] >= {S}"))

    # -- het / segment checks -----------------------------------------------
    bounds = pipe.segment_parts
    stage_sizes = [bounds[i + 1] - bounds[i] for i in range(S)]
    explicit = isinstance(getattr(pipe, "_seg_method", None), (list, tuple))
    uniform = len(set(stage_sizes)) == 1
    if explicit and not uniform and mode.upper() in ("ZBH1", "ZBVPP"):
        report.add(Finding(
            rule=SEGMENT_MISMATCH, severity=ERROR,
            message=f"non-uniform explicit segments {stage_sizes} compose "
                    f"with FThenB only — schedule_mode={mode!r} raises "
                    "ValueError at construction",
            file=pfile, line=pline,
            suggestion="use FThenB with the het schedule, or re-balance "
                       "the segments uniformly"))

    # -- stage parameter imbalance ------------------------------------------
    param_numels = [float(_stage_param_numel(pipe.stage_items(s)))
                    for s in range(S)]
    ratio = _imbalance(param_numels)
    if ratio > STAGE_IMBALANCE_RATIO:
        worst = int(np.argmax(param_numels))
        report.add(Finding(
            rule=STAGE_IMBALANCE, severity=WARNING,
            message=f"per-stage parameter counts "
                    f"{[int(v) for v in param_numels]} are imbalanced "
                    f"(max/mean = {ratio:.2f}x, stage {worst} heaviest) — "
                    "every other stage idles while it computes",
            file=pfile, line=pline,
            suggestion="re-segment (seg_method) so stage parameter/FLOP "
                       "weights are within ~1.5x of the mean"))

    # -- per-stage FLOPs + activation-shape chain (needs shapes) ------------
    stage_flops: List[float] = []
    act_bytes = 0
    if input_spec is not None:
        x = to_shape_struct(input_spec)
        act_bytes = int(math.prod(x.shape)) * np.dtype(x.dtype).itemsize \
            if x is not None else 0
        from ..core import tape as tape_mod
        from ..core.tensor import Tensor
        for s in range(S):
            items = pipe.stage_items(s)

            def stage_fn(arr, _items=items):
                with tape_mod.no_grad_guard():
                    t = Tensor._from_array(arr)
                    for item in _items:
                        t = pipe._apply(item, t)
                return t._data if isinstance(t, Tensor) else t

            try:
                closed, out_shape = jax.make_jaxpr(
                    stage_fn, return_shape=True)(x)
            except Exception as exc:
                first = str(exc).strip().splitlines()[0]
                report.add(Finding(
                    rule=TRACE_FAILED, severity=WARNING,
                    message=f"stage {s} abstract trace failed "
                            f"({type(exc).__name__}): {first}",
                    file=pfile, line=pline,
                    suggestion="per-stage FLOP/segment checks were "
                               "skipped from this stage on"))
                break
            stage_flops.append(
                cost_model.estimate_jaxpr(closed).flops)
            out = jax.tree_util.tree_leaves(out_shape)[0]
            if tuple(out.shape) != tuple(x.shape) and \
                    not (explicit and not uniform
                         and mode.upper() in ("", "FTHENB", "1F1B")):
                it0 = items[0]
                sfile, sline = _layer_loc(
                    it0[0] if isinstance(it0, tuple) else it0)
                report.add(Finding(
                    rule=SEGMENT_MISMATCH, severity=ERROR,
                    message=f"stage {s} maps activation "
                            f"{tuple(x.shape)} -> {tuple(out.shape)} but "
                            f"the {mode} schedule's ppermute ring needs "
                            "identical shapes on every stage boundary",
                    file=sfile, line=sline,
                    suggestion="make stages shape-homogeneous, or use an "
                               "explicit non-uniform seg_method with "
                               "FThenB (the het path)"))
            x = jax.ShapeDtypeStruct(out.shape, out.dtype)
        if len(stage_flops) == S:
            fratio = _imbalance(stage_flops)
            if fratio > STAGE_IMBALANCE_RATIO:
                worst = int(np.argmax(stage_flops))
                report.add(Finding(
                    rule=STAGE_IMBALANCE, severity=WARNING,
                    message=f"per-stage FLOPs "
                            f"{[f'{v:.2e}' for v in stage_flops]} are "
                            f"imbalanced (max/mean = {fratio:.2f}x, stage "
                            f"{worst} heaviest)",
                    file=pfile, line=pline,
                    suggestion="re-segment so per-stage FLOPs are within "
                               "~1.5x of the mean"))

    # -- bubble fraction ----------------------------------------------------
    from ..distributed.pipeline import schedule_stats
    try:
        stats = schedule_stats(mode, S, max(M, 1), V)
    except ValueError:
        stats = None
    if stats is not None and M >= 1 and not (V > 1 and M < S):
        bf = float(stats["bubble_fraction"])
        if bf > BUBBLE_WARN_FRACTION:
            # smallest M with an acceptable GPipe bubble, as a hint
            m_ok = math.ceil((S - 1) * (1 - BUBBLE_WARN_FRACTION)
                             / BUBBLE_WARN_FRACTION)
            report.add(Finding(
                rule=BUBBLE_FRACTION, severity=WARNING,
                message=f"{mode} with S={S} stages and M={M} microbatches "
                        f"idles {bf:.0%} of wall ticks in pipeline "
                        "bubbles",
                file=pfile, line=pline,
                suggestion=f"raise accumulate_steps (>= {m_ok} keeps "
                           f"GPipe under {BUBBLE_WARN_FRACTION:.0%}) or "
                           "switch to VPP/ZBH1 (vpp_degree>1 divides the "
                           "bubble by V)"))

    # -- schedule cost estimate ---------------------------------------------
    if with_cost and stats is not None:
        est = cost_model.CostEstimate(n_devices=S)
        per_stage = max(stage_flops) if stage_flops else 0.0
        est.flops = per_stage * M
        # every schedule's "ticks" is its forward-phase hop count (ZB's
        # weighted wall_units are cost units, not hops) — comparable
        # across modes, forward-pass traffic like the FLOP figure above
        ticks = int(stats.get("ticks", 0))
        if act_bytes and ticks:
            est.collective_bytes["ppermute"] = float(act_bytes * ticks)
            est.collective_calls["ppermute"] = ticks
        if act_bytes:
            # xs microbatch stack + double-buffered boundary activations
            est.peak_hbm_bytes = float(act_bytes * (M + 2))
        report.cost = est
    return report
