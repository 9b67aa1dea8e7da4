"""Jaxpr linter — abstract-trace a program and lint the staged IR.

The runtime telemetry layer (docs/OBSERVABILITY.md) reports graph
breaks, recompiles, and waste *after* they have cost a trace or a
compile. This pass gets the same signals ahead of time: the function is
traced with `jax.make_jaxpr` over `ShapeDtypeStruct`s (derived from
`InputSpec`s or sample inputs) — no device execution, no compile — and
rule passes walk the resulting jaxpr:

* dtype-promotion     — silent upcasts (f32->f64 under x64, f16/bf16
                        compute promoted to f32 by a stray numpy scalar)
* large-constant      — big arrays closed over and baked into every
                        executable copy of the program
* dead-computation    — equations unreachable from any output (traced,
                        compiled, executed for nothing)
* unused-input        — inputs (incl. donated ones) no output depends on
* constant-output     — outputs that do not depend on any input
* unrolled-loop       — long runs of identical equation blocks, the
                        signature of a Python loop traced inline
* static-arg-recompile— Python scalars in the call signature: every
                        distinct value is a new XLA executable

Entry points `lint_traceable` (plain fn), `lint_static_function`, and
`lint_train_step` mirror the three compile surfaces in paddle_tpu.jit.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
from jax.extend import core as jcore
import numpy as np

from .findings import (CONSTANT_OUTPUT, DEAD_COMPUTATION, DTYPE_PROMOTION,
                       ERROR, GRAPH_BREAK, INFO, LARGE_CONSTANT,
                       MOE_SLOW_DISPATCH, STATIC_ARG_RECOMPILE,
                       TRACE_FAILED, UNROLLED_LOOP, UNUSED_INPUT, WARNING,
                       Finding, Report)

def _break_errors():
    """jit.api's graph-break error set, not a copy — hitting one during
    the ABSTRACT trace is the linter predicting the runtime break, and
    the two sets must never diverge."""
    from ..jit.api import StaticFunction
    return StaticFunction._BREAK_ERRORS


def _abstract_trace(report: Report, fn, *args, **kwargs):
    """make_jaxpr that converts trace failures into findings instead of
    raising: inspect() must stay total on exactly the programs it
    exists to diagnose. Returns (closed_jaxpr, out_shape) or None."""
    break_errors = _break_errors()
    try:
        return jax.make_jaxpr(fn, return_shape=True)(*args, **kwargs)
    except break_errors as exc:
        first = str(exc).strip().splitlines()[0]
        report.add(Finding(
            rule=GRAPH_BREAK, severity=ERROR,
            message=f"the trace itself breaks: {first}",
            breaks_with=type(exc).__name__,
            suggestion="at runtime this call falls back to eager "
                       "(sublayer-segmented for Layers); restructure with "
                       "static.nn.cond/while_loop to keep it compiled"))
        return None
    except Exception as exc:  # infra/shape artifact — report, don't raise
        first = str(exc).strip().splitlines()[0]
        report.add(Finding(
            rule=TRACE_FAILED, severity=WARNING,
            message=f"abstract trace failed "
                    f"({type(exc).__name__}): {first}",
            suggestion="jaxpr rules were skipped; check the example "
                       "shapes/specs match what the function expects"))
        return None

# a closed-over constant this big belongs in the arguments (XLA embeds
# consts into the executable; donation can't reuse their memory)
CONST_BYTES_THRESHOLD = 256 * 1024
# identical equation blocks repeated this many times = Python loop
# unrolled into the trace (stacked same-shape layers below this count
# are normal model structure, not a finding)
UNROLL_MIN_REPEATS = 8
UNROLL_MAX_PERIOD = 64


def _float_width(dtype) -> int:
    try:
        d = np.dtype(dtype)
    except TypeError:
        return 0
    if d.kind == 'f':
        return d.itemsize * 8
    if str(dtype) == "bfloat16":
        return 16
    return 0


_FRAMEWORK_DIRS = (f"paddle_tpu{os.sep}ops", f"paddle_tpu{os.sep}core",
                   f"paddle_tpu{os.sep}nn", f"paddle_tpu{os.sep}jit",
                   f"paddle_tpu{os.sep}analysis")


def _eqn_loc(eqn) -> Tuple[str, int]:
    """Best-effort *user* file:line for an equation via jax source
    info — skipping paddle_tpu's own dispatch/op wrappers so findings
    point at model code, not the framework frame that issued the
    primitive."""
    try:
        from jax._src import source_info_util
        frames = list(source_info_util.user_frames(eqn.source_info))
        for frame in frames:
            if not any(d in frame.file_name for d in _FRAMEWORK_DIRS):
                return frame.file_name, frame.start_line
        if frames:
            return frames[0].file_name, frames[0].start_line
    except Exception:
        pass
    return "<jaxpr>", 0


def _eqn_sig(eqn) -> tuple:
    """Structural signature for repeated-block detection."""
    def aval_sig(v):
        aval = getattr(v, "aval", None)
        if aval is None:  # Literal
            return ("lit", repr(getattr(v, "val", v)))
        return (tuple(getattr(aval, "shape", ())),
                str(getattr(aval, "dtype", "?")))
    name = eqn.primitive.name
    if name == "jit":  # jnp ops like cumsum hide behind a nested jit
        name = f"jit:{eqn.params.get('name', '?')}"
    return (name,
            tuple(aval_sig(v) for v in eqn.invars),
            tuple(aval_sig(v) for v in eqn.outvars))


def _walk_eqns(jaxpr):
    """Yield equations of `jaxpr` and every sub-jaxpr (scan/cond/pjit
    bodies), so dtype rules see through structured control flow."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in _subjaxprs(p):
                yield from _walk_eqns(sub)


def _subjaxprs(p):
    if isinstance(p, jcore.ClosedJaxpr):
        yield p.jaxpr
    elif isinstance(p, jcore.Jaxpr):
        yield p
    elif isinstance(p, (list, tuple)):
        for item in p:
            yield from _subjaxprs(item)


# -- rule passes -------------------------------------------------------------

def _check_promotion(closed, findings: List[Finding]):
    jaxpr = closed.jaxpr
    widths = [_float_width(v.aval.dtype) for v in jaxpr.invars]
    # read const dtypes WITHOUT np.asarray: that would device-to-host
    # copy exactly the large baked arrays the next rule flags
    widths += [_float_width(getattr(c, "dtype", np.float32))
               for c in closed.consts
               if hasattr(c, "dtype") or isinstance(c, float)]
    base = max([w for w in widths if w], default=32)
    seen = set()
    for eqn in _walk_eqns(jaxpr):
        local = [_float_width(v.aval.dtype) for v in eqn.invars
                 if getattr(v, "aval", None) is not None]
        local_max = max([w for w in local if w], default=0)
        for out in eqn.outvars:
            aval = getattr(out, "aval", None)
            if aval is None:
                continue
            w = _float_width(getattr(aval, "dtype", None))
            if w <= base or w <= local_max:
                continue  # only the eqn doing the widening, once
            in_dtypes = sorted({str(v.aval.dtype) for v in eqn.invars
                                if getattr(v, "aval", None) is not None
                                and _float_width(v.aval.dtype)})
            key = (str(aval.dtype), tuple(in_dtypes))
            if key in seen:
                continue
            seen.add(key)
            fname, line = _eqn_loc(eqn)
            src = in_dtypes[0] if in_dtypes else f"float{base}"
            findings.append(Finding(
                rule=DTYPE_PROMOTION, severity=WARNING,
                message=f"silent dtype promotion {src} -> {aval.dtype} in "
                        f"'{eqn.primitive.name}' (widest input float is "
                        f"float{base})",
                file=fname, line=line,
                suggestion="a Python/numpy scalar or x64 mode is widening "
                           "the compute dtype; cast the constant to the "
                           "input dtype"))


def _check_large_consts(closed, findings: List[Finding],
                        threshold: int):
    for c in closed.consts:
        nbytes = getattr(c, "nbytes", 0)
        if nbytes >= threshold:
            findings.append(Finding(
                rule=LARGE_CONSTANT, severity=WARNING,
                message=f"{nbytes / 1024:.0f} KiB constant "
                        f"{tuple(getattr(c, 'shape', ()))} closed over and "
                        f"baked into the executable",
                suggestion="pass it as an argument (and donate it) instead "
                           "of capturing it — every signature's executable "
                           "embeds its own copy"))


def _live_eqn_mask(jaxpr) -> List[bool]:
    live_vars = {id(v) for v in jaxpr.outvars if hasattr(v, "aval")}
    mask = [False] * len(jaxpr.eqns)
    for i in range(len(jaxpr.eqns) - 1, -1, -1):
        eqn = jaxpr.eqns[i]
        if eqn.effects or any(id(v) in live_vars for v in eqn.outvars):
            mask[i] = True
            for v in eqn.invars:
                if hasattr(v, "aval") and not isinstance(v, jcore.Literal):
                    live_vars.add(id(v))
    return mask


# dead eqns of these primitives are free: layout/shape plumbing that
# XLA's own DCE strips before codegen. Autodiff partial-eval routinely
# leaves dead broadcasts behind in grad programs — only dead COMPUTE
# equations are worth a finding.
_TRIVIAL_DEAD = {"broadcast_in_dim", "reshape", "convert_element_type",
                 "squeeze", "expand_dims", "transpose", "slice", "iota",
                 "copy", "stop_gradient"}


def _check_dead_code(closed, findings: List[Finding]):
    jaxpr = closed.jaxpr
    mask = _live_eqn_mask(jaxpr)
    dead = [jaxpr.eqns[i] for i, alive in enumerate(mask)
            if not alive
            and jaxpr.eqns[i].primitive.name not in _TRIVIAL_DEAD]
    if not dead:
        return
    by_loc: Dict[Tuple[str, int], List[str]] = {}
    for eqn in dead:
        by_loc.setdefault(_eqn_loc(eqn), []).append(eqn.primitive.name)
    for (fname, line), prims in sorted(by_loc.items()):
        names = ", ".join(sorted(set(prims))[:4])
        findings.append(Finding(
            rule=DEAD_COMPUTATION, severity=WARNING,
            message=f"{len(prims)} equation(s) ({names}) feed no output — "
                    "traced and compiled for nothing",
            file=fname, line=line,
            suggestion="drop the computation or return its result"))


def _used_var_ids(jaxpr) -> set:
    used = set()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not isinstance(v, jcore.Literal):
                used.add(id(v))
    for v in jaxpr.outvars:
        if hasattr(v, "aval") and not isinstance(v, jcore.Literal):
            used.add(id(v))
    return used


def _check_unused_inputs(closed, findings: List[Finding],
                         check_idx: Sequence[int],
                         labels: Dict[int, str],
                         donated: Sequence[int] = ()):
    jaxpr = closed.jaxpr
    used = _used_var_ids(jaxpr)
    donated = set(donated)
    for i in check_idx:
        v = jaxpr.invars[i]
        if id(v) in used:
            continue
        name = labels.get(i, f"input #{i}")
        aval = v.aval
        if i in donated:
            findings.append(Finding(
                rule=UNUSED_INPUT, severity=WARNING,
                message=f"donated input {name} "
                        f"({tuple(aval.shape)}:{aval.dtype}) is never used "
                        "— its buffer is freed for nothing",
                suggestion="remove it from the step signature or stop "
                           "donating it"))
        else:
            findings.append(Finding(
                rule=UNUSED_INPUT, severity=WARNING,
                message=f"{name} ({tuple(aval.shape)}:{aval.dtype}) "
                        "does not contribute to any output",
                suggestion="remove the argument, or check for a "
                           "shadowed/overwritten name in the function body"))


def _check_constant_outputs(closed, findings: List[Finding],
                            n_user_out: Optional[int]):
    jaxpr = closed.jaxpr
    reachable = {id(v) for v in jaxpr.invars}
    for eqn in jaxpr.eqns:
        if any(not isinstance(v, jcore.Literal) and id(v) in reachable
               for v in eqn.invars):
            for v in eqn.outvars:
                reachable.add(id(v))
    outs = jaxpr.outvars if n_user_out is None \
        else jaxpr.outvars[:n_user_out]
    for k, v in enumerate(outs):
        is_const = isinstance(v, jcore.Literal) or id(v) not in reachable
        if is_const:
            aval = getattr(v, "aval", None)
            desc = (f"({tuple(aval.shape)}:{aval.dtype})"
                    if aval is not None else f"= {getattr(v, 'val', '?')!r}")
            findings.append(Finding(
                rule=CONSTANT_OUTPUT, severity=WARNING,
                message=f"output #{k} {desc} does not depend on any input "
                        "— it is a trace-time constant",
                suggestion="compute it once outside the compiled function"))


def _check_unrolled(closed, findings: List[Finding],
                    min_repeats: int):
    sigs = [_eqn_sig(e) for e in closed.jaxpr.eqns]
    n = len(sigs)
    best = None  # (repeats, period, end)
    for period in range(1, min(UNROLL_MAX_PERIOD, n // 2) + 1):
        run = 0
        for i in range(n - period):
            run = run + 1 if sigs[i] == sigs[i + period] else 0
            repeats = run // period + 1
            if repeats >= min_repeats and (
                    best is None or repeats > best[0]):
                best = (repeats, period, i + period)
    if best is None:
        return
    repeats, period, end = best
    start = end - period + 1  # one representative block
    eqn = closed.jaxpr.eqns[start]
    fname, line = _eqn_loc(eqn)
    prims = [s[0] for s in sigs[start:start + period]]
    findings.append(Finding(
        rule=UNROLLED_LOOP, severity=WARNING,
        message=f"a block of {period} equation(s) "
                f"({', '.join(prims[:4])}{'...' if period > 4 else ''}) "
                f"repeats {repeats}x with identical shapes — a Python "
                "loop unrolled into the trace",
        file=fname, line=line,
        suggestion="roll it with lax.scan / paddle.static.nn.while_loop: "
                   "same math, ~1/N the trace+compile time"))


# the named-jit dispatch/combine implementations MoELayer stages per
# mode (incubate/distributed/models/moe/moe_layer.py): their jit
# equations carry the function name, which is how a traced program
# reveals which MoE dispatch it baked in
_MOE_SLOW_DISPATCH_FNS = {"moe_dispatch_einsum": "einsum",
                          "moe_dispatch_scatter": "scatter"}


def _check_moe_dispatch(closed, findings: List[Finding]):
    """Perf rule (mirrors the recompile-risk rule's advisory role): an
    einsum/scatter MoE dispatch inside a traced program is the
    O(N*E*C*H) / no-dead-slot-skipping path — dispatch_mode="pallas"
    runs the fused grouped-matmul kernel instead (docs/KERNELS.md).
    One finding per dispatch mode found, at the first occurrence."""
    seen = set()
    for eqn in _walk_eqns(closed.jaxpr):
        if eqn.primitive.name != "jit":
            continue
        mode = _MOE_SLOW_DISPATCH_FNS.get(eqn.params.get("name"))
        if mode is None or mode in seen:
            continue
        seen.add(mode)
        fname, line = _eqn_loc(eqn)
        findings.append(Finding(
            rule=MOE_SLOW_DISPATCH, severity=INFO,
            message=f"MoE '{mode}' dispatch traced into this program "
                    "— token movement and the expert FFN run unfused "
                    "(dead capacity slots still pay full FLOPs)",
            file=fname, line=line,
            suggestion="construct the MoELayer with "
                       "dispatch_mode='pallas' (the default) so the "
                       "fused grouped-matmul kernel serves eligible "
                       "geometries — note a pallas-mode layer that "
                       "LEGITIMATELY degraded (ep-sharded mesh, "
                       "non-TPU trace) also stages this path; "
                       "kernels.moe.dispatch_path.fallback.* names "
                       "the reason"))


def lint_closed_jaxpr(closed, *,
                      user_invar_idx: Optional[Sequence[int]] = None,
                      invar_labels: Optional[Dict[int, str]] = None,
                      donated_idx: Sequence[int] = (),
                      n_user_out: Optional[int] = None,
                      const_bytes_threshold: int = CONST_BYTES_THRESHOLD,
                      unroll_min_repeats: int = UNROLL_MIN_REPEATS
                      ) -> List[Finding]:
    """Run every jaxpr rule pass over a ClosedJaxpr."""
    findings: List[Finding] = []
    if user_invar_idx is None:
        user_invar_idx = range(len(closed.jaxpr.invars))
    _check_promotion(closed, findings)
    _check_large_consts(closed, findings, const_bytes_threshold)
    _check_dead_code(closed, findings)
    _check_unused_inputs(closed, findings, user_invar_idx,
                         invar_labels or {}, donated_idx)
    _check_constant_outputs(closed, findings, n_user_out)
    _check_unrolled(closed, findings, unroll_min_repeats)
    _check_moe_dispatch(closed, findings)
    return findings


# -- spec handling -----------------------------------------------------------

def to_shape_struct(x, fill_dim: int = 2):
    """InputSpec / Tensor / array / ShapeDtypeStruct -> ShapeDtypeStruct.
    Returns None for host-side Python values (static args). Unknown
    InputSpec dims (None / -1) are filled with `fill_dim` — rule passes
    only need a representative concrete shape."""
    from ..core.tensor import Tensor
    from ..jit.api import InputSpec
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    if isinstance(x, InputSpec):
        from ..core import dtype as dtype_mod
        shape = tuple(fill_dim if d in (None, -1) else int(d)
                      for d in x.shape)
        return jax.ShapeDtypeStruct(shape, dtype_mod.dtype(x.dtype).np_dtype)
    if isinstance(x, Tensor):
        return jax.ShapeDtypeStruct(x._data.shape, x._data.dtype)
    if isinstance(x, (jax.Array, np.ndarray)):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return None


def _scalar_struct(v):
    if isinstance(v, bool):
        return jax.ShapeDtypeStruct((), np.bool_)
    if isinstance(v, int):
        return jax.ShapeDtypeStruct((), np.int32)
    if isinstance(v, float):
        return jax.ShapeDtypeStruct((), np.float32)
    return None


def lint_static_args(args, kwargs=None) -> List[Finding]:
    """The recompile-risk rule: every Python scalar in the example call
    lands in `_sig_of` by value — each distinct value is a separate
    trace + XLA compile."""
    findings: List[Finding] = []
    items = [(f"positional arg #{i}", a) for i, a in enumerate(args)]
    items += [(f"kwarg '{k}'", v) for k, v in sorted((kwargs or {}).items())]
    for where, v in items:
        if to_shape_struct(v) is not None or v is None:
            continue
        if isinstance(v, float) and not isinstance(v, bool):
            findings.append(Finding(
                rule=STATIC_ARG_RECOMPILE, severity=WARNING,
                message=f"{where} is a Python float ({v!r}): every "
                        "distinct value compiles a NEW executable "
                        "(float-valued keys explode the signature cache)",
                suggestion="pass it as a 0-d tensor "
                           "(paddle.to_tensor(v)) so one executable "
                           "serves all values"))
        elif isinstance(v, (bool, int, str)):
            findings.append(Finding(
                rule=STATIC_ARG_RECOMPILE, severity=INFO,
                message=f"{where} is a static {type(v).__name__} "
                        f"({v!r}): each distinct value is a separate "
                        "compile cache entry",
                suggestion="fine for a handful of values (flags, modes); "
                           "pass tensors for anything data-dependent"))
    return findings


# -- entry points ------------------------------------------------------------

def lint_traceable(fn, args=(), kwargs=None, *,
                   subject: Optional[str] = None,
                   **rule_opts) -> Report:
    """Abstract-trace a plain function at the given specs and lint it.

    `args`/`kwargs` may mix InputSpec / Tensor / arrays (traced) with
    Python scalars (static, checked by the recompile rule)."""
    kwargs = kwargs or {}
    report = Report(subject=subject
                    or getattr(fn, "__qualname__", repr(fn)))
    report.extend(lint_static_args(args, kwargs))

    structs, static_idx = [], []
    for i, a in enumerate(args):
        s = to_shape_struct(a)
        if s is None:
            s = _scalar_struct(a)
            if s is None:
                static_idx.append(i)
        structs.append(s)
    static_kwargs = {}
    traced_kwargs = {}
    for k, v in kwargs.items():
        s = to_shape_struct(v)
        if s is None:
            static_kwargs[k] = v
        else:
            traced_kwargs[k] = s

    def call(*traced, **tkw):
        full = list(traced)
        for i in static_idx:
            full.insert(i, args[i])
        return fn(*full, **tkw, **static_kwargs)

    traced_args = [s for i, s in enumerate(structs) if i not in static_idx]
    traced = _abstract_trace(report, call, *traced_args, **traced_kwargs)
    if traced is not None:
        report.extend(lint_closed_jaxpr(traced[0], **rule_opts))
    return report


def _with_mesh(lint_impl, mesh, *args, **kwargs) -> Report:
    """Run a lint entry point with shard_lint's collective recorder and
    a (fake) mesh installed: the same abstract trace then also yields
    SPMD/collective findings and a static cost estimate."""
    from . import cost_model
    from .shard_lint import (as_mesh, lint_jaxpr_collectives,
                             lint_records, recording)
    mesh = as_mesh(mesh)
    with recording(mesh) as rec:
        report, closed = lint_impl(*args, **kwargs)
    report.extend(lint_records(rec.records, mesh))
    if closed is not None:
        report.extend(lint_jaxpr_collectives(closed, mesh))
        report.cost = cost_model.estimate_jaxpr(closed, mesh)
        # this trace is a plain jit program, not a shard_map manual
        # region: GSPMD-auto partitioning will insert collectives (and
        # shrink per-rank shapes) at compile time — counts here cover
        # the explicit collectives only, and FLOPs/HBM are global-shape
        report.cost.note = (
            "GSPMD-auto trace: explicit collectives only; the XLA "
            "partitioner adds resharding traffic at compile time, and "
            "FLOPs/HBM are global (undivided) shapes")
    return report


def lint_static_function(sf, args=None, kwargs=None, mesh=None) -> Report:
    """Lint a jit.StaticFunction exactly as __call__ would stage it.

    With no sample `args`, the stored InputSpec list supplies the
    shapes — fully ahead-of-time inspection. With `mesh` (a Mesh,
    AbstractMesh, or {axis: degree} dict — no devices needed) the same
    trace additionally runs the shard_lint collective rules and
    attaches a static cost estimate."""
    if mesh is not None:
        return _with_mesh(_lint_static_function, mesh, sf, args, kwargs)
    return _lint_static_function(sf, args, kwargs)[0]


def _lint_static_function(sf, args=None, kwargs=None):
    from .ast_lint import lint_callable

    name = getattr(sf._fn, "__qualname__", repr(sf._fn))
    report = Report(subject=f"to_static({name})")
    report.extend(lint_callable(sf._layer if sf._layer is not None
                                else sf._fn))

    kwargs = dict(kwargs or {})
    if args is None:
        spec = sf._input_spec
        if spec is None:
            # nothing to trace against: AST findings only
            return report, None
        args = list(spec) if isinstance(spec, (list, tuple)) else [spec]

    tensor_args, kw_structs, static_kwargs = list(args), {}, {}
    for k, v in kwargs.items():
        s = to_shape_struct(v)
        if s is not None:
            kw_structs[k] = s  # traced by name, like __call__
        else:
            static_kwargs[k] = v
    report.extend(lint_static_args(args, static_kwargs))

    # mirror __call__'s argument handling exactly: arrays/specs trace
    # abstractly, Python scalars trace as 0-d weak-typed arrays (that
    # is what jax.jit does to them at runtime), anything else (None,
    # strings) passes through verbatim so arity and failure modes match
    # the real call
    arr_structs = []
    for a in tensor_args:
        s = to_shape_struct(a)
        if s is None:
            s = _scalar_struct(a)
        arr_structs.append(a if s is None else s)
    pure = sf._pure(static_kwargs)

    # pure's traced args flatten as (kw dict leaves in sorted-key
    # order, then positional arrays) — labels must respect that or an
    # unused-input finding names the wrong argument
    def user_labels(base):
        labels, i = {}, base
        for k in sorted(kw_structs):
            for _leaf in jax.tree_util.tree_leaves(kw_structs[k]):
                labels[i] = f"kwarg '{k}'"
                i += 1
        for j, s in enumerate(arr_structs):
            # None passthroughs contribute no invar leaves
            for _leaf in jax.tree_util.tree_leaves(s):
                labels[i] = f"input #{j}"
                i += 1
        return labels

    if sf._layer is None:
        traced = _abstract_trace(report, pure, kw_structs, *arr_structs)
        if traced is None:
            return report, None
        closed, _out_shape = traced
        labels = user_labels(0)
        report.extend(lint_closed_jaxpr(closed, invar_labels=labels))
        return report, closed

    from .functional_shapes import layer_state_structs, rng_key_struct
    params_s, buffers_s, frozen_s = layer_state_structs(sf._layer)
    key_s = rng_key_struct()
    traced = _abstract_trace(report, pure, params_s, buffers_s, frozen_s,
                             key_s, kw_structs, *arr_structs)
    if traced is None:
        return report, None
    closed, out_shape = traced
    n_state = sum(len(jax.tree_util.tree_leaves(t))
                  for t in (params_s, buffers_s, frozen_s)) + 1
    n_in = len(closed.jaxpr.invars)
    user_idx = list(range(n_state, n_in))
    labels = user_labels(n_state)
    n_user_out = len(jax.tree_util.tree_leaves(out_shape[0]))
    report.extend(lint_closed_jaxpr(
        closed, user_invar_idx=user_idx, invar_labels=labels,
        n_user_out=n_user_out))
    return report, closed


def lint_train_step(ts, inputs, labels, mesh=None) -> Report:
    """Lint a jit.TrainStep's fused step program at the given specs.

    Checks the same jaxpr rules plus unused *donated* inputs: a donated
    buffer no output depends on is memory freed for nothing. With
    `mesh`, shard_lint collective rules + the cost model run over the
    same trace (device-free)."""
    if mesh is not None:
        return _with_mesh(_lint_train_step, mesh, ts, inputs, labels)
    return _lint_train_step(ts, inputs, labels)[0]


def _lint_train_step(ts, inputs, labels):
    import jax.numpy as jnp

    from .ast_lint import lint_callable
    from .functional_shapes import rng_key_struct, tree_structs

    report = Report(subject=f"TrainStep({type(ts._model).__name__})")
    report.extend(lint_callable(ts._model))

    if not isinstance(inputs, (list, tuple)):
        inputs = (inputs,)
    in_structs = tuple(to_shape_struct(x) for x in inputs)
    lab_structs = jax.tree_util.tree_map(
        lambda t: to_shape_struct(t), labels,
        is_leaf=lambda t: to_shape_struct(t) is not None)
    params_s = tree_structs(ts._params)
    buffers_s = tree_structs(ts._buffers)
    frozen_s = tree_structs(ts._frozen)
    opt_s = tree_structs(ts._opt_state)
    key_s = rng_key_struct()
    lr_s = jax.ShapeDtypeStruct((), jnp.float32)

    step = ts._build_step()  # the un-jitted python step
    traced = _abstract_trace(report, step, params_s, buffers_s, frozen_s,
                             opt_s, key_s, lr_s, in_structs, lab_structs)
    if traced is None:
        return report, None
    closed, out_shape = traced

    counts = [len(jax.tree_util.tree_leaves(t))
              for t in (params_s, buffers_s, frozen_s, opt_s)]
    n_p, n_b, n_f, n_o = counts
    base = n_p + n_b + n_f + n_o + 2  # + key + lr
    n_in = len(closed.jaxpr.invars)
    labels_map: Dict[int, str] = {}
    # donated leaves: params (0), buffers (1), opt_state (3)
    donated = list(range(0, n_p)) + list(range(n_p, n_p + n_b)) + \
        list(range(n_p + n_b + n_f, n_p + n_b + n_f + n_o))
    for i, k in enumerate(sorted(params_s)):
        labels_map[i] = f"param '{k}'"
    for i, k in enumerate(sorted(buffers_s)):
        labels_map[n_p + i] = f"buffer '{k}'"
    for i in range(base, n_in):
        labels_map[i] = f"data input #{i - base}"
    check_idx = donated + list(range(base, n_in))
    report.extend(lint_closed_jaxpr(
        closed, user_invar_idx=check_idx, invar_labels=labels_map,
        donated_idx=donated))
    return report, closed
