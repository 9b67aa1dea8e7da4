"""hotpath_lint — device-free host/device boundary audit of a serving
tick (docs/ANALYSIS.md "Hot-path rules").

Where ast_lint/jaxpr_lint audit one traced function and shard_lint one
sharded program, this linter audits a serving SURFACE (Engine,
DisaggEngine, ServingFleet, BatchEncoder): the full inventory of its
compiled per-tick executables plus the scheduler source that drives
them. PR 15's gauges (``serving.host_ms_per_tick``) measure how much
host Python a tick pays; this pass names the causes, statically,
without a device:

* ``hotpath.missed-donation``   — a pool-sized argument (KV/scale/
  draft pools, resident decode state) flows to a same-shaped output
  without being donated: XLA must copy it in HBM every tick.
* ``hotpath.fetch-set-bloat``   — a per-tick output beyond the small
  token/ok vectors is materialized to host: every extra fetch is a
  forced sync.
* ``hotpath.host-sync-in-tick`` — the scheduler source syncs outside
  the attributed path: ``.item()``/``np.asarray``/implicit bool/len on
  a freshly dispatched device value that never went through
  ``_sync_timed``, a bare ``jax.block_until_ready``, host wall-clock
  (``time.time``/``time.sleep``) or host RNG inside the tick.
* ``hotpath.steady-tick-upload`` — the dirty-row-merge discipline: a
  steady tick uploads NOTHING, so any host->device transfer
  (``jnp.asarray``/``device_put``/``self._up``) in a steady-path
  function must sit under a dirty-flag ``if`` guard.
* ``hotpath.recompile-risk-key`` — an executable-cache dict keyed by a
  Python float/object that can vary per tick retraces instead of
  reusing a warm executable.

Everything here is abstract: executables are traced with
``jax.make_jaxpr`` over ShapeDtypeStructs (no device execution, CPU
container is enough) and the scheduler is walked as SOURCE — the same
discipline as jaxpr_lint. The runtime complement is the
``PADDLE_TPU_LINT=1`` transfer-guard the engines arm around steady
decode ticks, which turns any implicit transfer this pass missed into
a raise instead of a silent sync.

Scope note: device-value tracking in the scheduler walk is name-based
(results unpacked from a dispatched executable). Deliberate rare-path
attribute fetches (e.g. pulling an RNG row off the resident state at
preemption) are out of scope — they are commented host syncs on
non-steady paths, not per-tick costs.
"""
from __future__ import annotations

import ast
import dataclasses
import inspect
import textwrap
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from .findings import (FETCH_SET_BLOAT, HOST_SYNC_IN_TICK,
                       HOTPATH_RULES, MISSED_DONATION,
                       RECOMPILE_RISK_KEY, STEADY_TICK_UPLOAD, WARNING,
                       Finding, Report)

# "pool-sized": below this an undonated round trip is noise (rng keys,
# per-slot vectors), above it the per-tick HBM copy is real
POOL_BYTES_FLOOR = 64 * 1024
# the token/ok fetch vectors are O(max_slots) ints; anything past this
# on the per-tick fetch set is a bulk device->host pull
FETCH_BYTES_FLOOR = 16 * 1024


@dataclasses.dataclass
class ExecutableSpec:
    """One compiled per-tick surface: the UN-jitted body, abstract-
    traceable args (arrays or ShapeDtypeStructs), its donation set,
    and which top-level outputs the scheduler fetches to host.
    ``deliverable`` marks fetched outputs that ARE the service's
    payload (an embedding batch) and therefore exempt from the
    fetch-size floor."""
    name: str
    body: Callable
    args: Tuple
    donate: Tuple[int, ...] = ()
    fetched: Tuple[int, ...] = ()
    deliverable: Tuple[int, ...] = ()
    per_tick: bool = True


@dataclasses.dataclass
class HotpathInventory:
    """Everything hotpath_lint needs from a serving surface: its
    executables, the scheduler functions that run each tick, which of
    those are on the STEADY decode path (upload discipline applies),
    and its executable-cache key sets."""
    subject: str
    executables: List[ExecutableSpec]
    tick_functions: List[Callable]
    steady_functions: Tuple[str, ...] = ()
    cache_keys: Optional[Dict[str, Iterable]] = None
    file: str = "<unknown>"
    line: int = 0


def struct_of(tree):
    """Pytree of arrays/structs -> pytree of ShapeDtypeStructs (the
    abstract-trace currency; never touches device data)."""
    import jax
    import numpy as np

    def one(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        return jax.ShapeDtypeStruct(tuple(np.shape(x)),
                                    getattr(x, "dtype", np.int32))

    return jax.tree_util.tree_map(one, tree)


def _nbytes(leaf) -> int:
    n = 1
    for d in leaf.shape:
        n *= int(d)
    return n * leaf.dtype.itemsize


def _body_loc(body) -> Tuple[str, int]:
    code = getattr(body, "__code__", None)
    if code is None:
        return "<unknown>", 0
    return code.co_filename, code.co_firstlineno


def _lint_executable(report: Report, spec: ExecutableSpec) -> None:
    import jax

    from . import jaxpr_lint
    args = tuple(struct_of(a) for a in spec.args)
    traced = jaxpr_lint._abstract_trace(report, spec.body, *args)
    if traced is None:
        return                      # trace failure already reported
    _closed, out_shape = traced
    fname, fline = _body_loc(spec.body)
    out_leaves = jax.tree_util.tree_leaves(out_shape)
    out_keys = {(tuple(l.shape), str(l.dtype)) for l in out_leaves}
    donated = set(spec.donate)
    for i, arg in enumerate(args):
        if i in donated:
            continue
        hits = [l for l in jax.tree_util.tree_leaves(arg)
                if _nbytes(l) >= POOL_BYTES_FLOOR
                and (tuple(l.shape), str(l.dtype)) in out_keys]
        if hits:
            total = sum(_nbytes(l) for l in hits)
            report.add(Finding(
                MISSED_DONATION, WARNING,
                f"executable {spec.name}: argument {i} "
                f"({len(hits)} pool-sized leaf/leaves, {total} bytes) "
                f"flows to same-shaped outputs undonated — XLA copies "
                f"it in HBM every dispatch",
                file=fname, line=fline,
                suggestion=f"add {i} to donate_argnums so the update "
                           f"aliases in place"))
    outs = out_shape if isinstance(out_shape, (tuple, list)) \
        else (out_shape,)
    for idx in spec.fetched:
        if idx in spec.deliverable or idx >= len(outs):
            continue
        total = sum(_nbytes(l)
                    for l in jax.tree_util.tree_leaves(outs[idx]))
        if total > FETCH_BYTES_FLOOR:
            report.add(Finding(
                FETCH_SET_BLOAT, WARNING,
                f"executable {spec.name}: per-tick fetch of output "
                f"{idx} pulls {total} bytes to host — beyond the "
                f"token/ok vectors, every extra fetch is a forced "
                f"sync",
                file=fname, line=fline,
                suggestion="keep bulk results device-resident (feed "
                           "them to the next executable) or batch the "
                           "fetch outside the tick"))


def _lint_cache_keys(report: Report, inv: HotpathInventory) -> None:
    for name, keys in (inv.cache_keys or {}).items():
        bad = []
        for key in keys:
            parts = key if isinstance(key, tuple) else (key,)
            for p in parts:
                if p is None or isinstance(p, (bool, int, str, bytes)):
                    continue
                bad.append(f"{type(p).__name__} {p!r}")
                break
        if bad:
            report.add(Finding(
                RECOMPILE_RISK_KEY, WARNING,
                f"executable cache {name} keyed by {', '.join(bad)} — "
                f"a float/object key that varies per tick compiles a "
                f"fresh executable instead of reusing a warm one",
                file=inv.file, line=inv.line,
                suggestion="key on ints/strings (bucket sizes, "
                           "variant names); pass varying values as "
                           "traced arrays"))


# -- scheduler-source walk ----------------------------------------------------

_NP_FETCH = ("np.asarray", "np.array", "numpy.asarray", "numpy.array")
_UPLOAD_CALLS = ("jnp.asarray", "jnp.array", "jax.numpy.asarray",
                 "jax.numpy.array", "jax.device_put", "self._up")
_HOST_CLOCK = ("time.time", "time.sleep")
_HOST_RNG_PREFIXES = ("random.", "np.random.", "numpy.random.")
_FETCH_METHODS = ("item", "tolist", "numpy")
_SYNC_ATTR = "_sync_timed"


def _dotted(node) -> str:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else ""
    return ""


class _TickLinter(ast.NodeVisitor):
    """Walks ONE scheduler function. Names unpacked from a dispatched
    executable (``fn = self._get_*(...)``; ``a, b = fn(...)``) are
    DEVICE values; ``self._sync_timed(...)`` attributes their wait.
    Fetching, branching, or casting an unsynced device name is a
    finding; on steady-path functions, so is an unguarded upload."""

    def __init__(self, report: Report, filename: str, off: int,
                 fn_name: str, steady: bool):
        self.report = report
        self.filename = filename
        self.off = off
        self.fn_name = fn_name
        self.steady = steady
        self.fn_like: set = set()
        self.device: set = set()
        self.synced: set = set()
        self.if_depth = 0

    def _flag(self, rule: str, node, msg: str, suggestion: str = ""):
        self.report.add(Finding(
            rule, WARNING, f"{self.fn_name}: {msg}",
            file=self.filename, line=node.lineno + self.off,
            suggestion=suggestion))

    # -- assignments: track dispatchers and their device results -------------

    def visit_Assign(self, node: ast.Assign):
        val = node.value
        names = []
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                names.append(tgt.id)
            elif isinstance(tgt, (ast.Tuple, ast.List)):
                names.extend(e.id for e in tgt.elts
                             if isinstance(e, ast.Name))
        if isinstance(val, ast.Call):
            callee = _dotted(val.func)
            if callee.startswith("self._get_"):
                self.fn_like.update(names)
            elif (isinstance(val.func, ast.Name)
                  and val.func.id in self.fn_like) \
                    or callee == "self._dispatch_steady":
                self.device.update(names)
            elif callee in _NP_FETCH:
                # `x = np.asarray(x)` rebinds to a host array
                self.visit(val)
                for n in names:
                    self.device.discard(n)
                return
        self.visit(val)

    # -- calls: syncs, fetches, clocks, uploads ------------------------------

    def visit_Call(self, node: ast.Call):
        callee = _dotted(node.func)
        if callee == f"self.{_SYNC_ATTR}":
            for arg in node.args:
                self.synced.update(n.id for n in ast.walk(arg)
                                   if isinstance(n, ast.Name))
            return
        if callee in _NP_FETCH and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Name) and arg.id in self.device \
                    and arg.id not in self.synced:
                self._flag(
                    HOST_SYNC_IN_TICK, node,
                    f"np.asarray({arg.id}) fetches a dispatched "
                    f"device value that never went through "
                    f"{_SYNC_ATTR}",
                    suggestion=f"add {arg.id} to the "
                               f"{_SYNC_ATTR}(...) tuple so the wait "
                               f"is attributed to the device share")
        elif callee == "jax.block_until_ready" \
                and self.fn_name != _SYNC_ATTR:
            self._flag(
                HOST_SYNC_IN_TICK, node,
                "un-attributed jax.block_until_ready",
                suggestion=f"route the wait through {_SYNC_ATTR} so "
                           f"host/device tick attribution stays "
                           f"honest")
        elif callee in _HOST_CLOCK \
                or callee.startswith(_HOST_RNG_PREFIXES):
            self._flag(
                HOST_SYNC_IN_TICK, node,
                f"host {callee}() inside the tick path",
                suggestion="use the injectable clock / a monotonic "
                           "timer, and keep RNG in traced keys")
        elif callee in ("bool", "int", "float", "len") and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Name) and arg.id in self.device \
                    and arg.id not in self.synced:
                self._flag(
                    HOST_SYNC_IN_TICK, node,
                    f"{callee}({arg.id}) forces an unsynced device "
                    f"value to host",
                    suggestion=f"sync {arg.id} via {_SYNC_ATTR} "
                               f"first, then read the host copy")
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr in _FETCH_METHODS \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id in self.device:
            self._flag(
                HOST_SYNC_IN_TICK, node,
                f".{node.func.attr}() on dispatched device value "
                f"{node.func.value.id}",
                suggestion=f"sync via {_SYNC_ATTR} and read the "
                           f"np.asarray copy instead")
        if self.steady and callee in _UPLOAD_CALLS \
                and self.if_depth == 0:
            self._flag(
                STEADY_TICK_UPLOAD, node,
                f"unconditional host->device upload ({callee}) on the "
                f"steady decode path — a steady tick must upload "
                f"nothing",
                suggestion="guard the upload behind the dirty-row "
                           "flags (the merge-on-dirty discipline) or "
                           "keep the value device-resident")
        self.generic_visit(node)

    # -- implicit bool on a device value -------------------------------------

    def _check_test(self, test):
        name = None
        if isinstance(test, ast.Name):
            name = test.id
        elif isinstance(test, ast.UnaryOp) \
                and isinstance(test.op, ast.Not) \
                and isinstance(test.operand, ast.Name):
            name = test.operand.id
        if name is not None and name in self.device \
                and name not in self.synced:
            self._flag(
                HOST_SYNC_IN_TICK, test,
                f"implicit bool on unsynced device value {name} "
                f"(branch forces a host sync)",
                suggestion=f"sync {name} via {_SYNC_ATTR} and branch "
                           f"on the host copy")

    def visit_If(self, node: ast.If):
        self._check_test(node.test)
        self.visit(node.test)
        self.if_depth += 1
        for stmt in node.body + node.orelse:
            self.visit(stmt)
        self.if_depth -= 1

    def visit_IfExp(self, node: ast.IfExp):
        self._check_test(node.test)
        self.visit(node.test)
        self.if_depth += 1
        self.visit(node.body)
        self.visit(node.orelse)
        self.if_depth -= 1

    def visit_While(self, node: ast.While):
        self._check_test(node.test)
        self.generic_visit(node)


def _lint_tick_fn(report: Report, fn, steady_names) -> None:
    raw = inspect.unwrap(fn)
    code = getattr(raw, "__func__", raw)
    try:
        lines, first = inspect.getsourcelines(code)
        filename = inspect.getsourcefile(code) or "<unknown>"
    except (OSError, TypeError):
        return
    try:
        tree = ast.parse(textwrap.dedent("".join(lines)))
    except SyntaxError:
        return
    if not tree.body or not isinstance(
            tree.body[0], (ast.FunctionDef, ast.AsyncFunctionDef)):
        return
    fdef = tree.body[0]
    linter = _TickLinter(report, filename, first - 1, fdef.name,
                         steady=fdef.name in steady_names)
    for stmt in fdef.body:
        linter.visit(stmt)


# -- entry points -------------------------------------------------------------

def lint_inventory(inv: HotpathInventory) -> Report:
    """Run every hot-path rule over one surface's inventory."""
    report = Report(subject=inv.subject)
    for spec in inv.executables:
        _lint_executable(report, spec)
    _lint_cache_keys(report, inv)
    steady = tuple(inv.steady_functions or ())
    for fn in inv.tick_functions:
        _lint_tick_fn(report, fn, steady)
    return report


def lint_surface(obj) -> Report:
    """Lint any object exposing ``_hotpath_inventory()`` (Engine,
    DisaggEngine, ServingFleet, BatchEncoder, or a test double)."""
    return lint_inventory(obj._hotpath_inventory())


def emit_hotpath(report: Report) -> Report:
    """Route an inspect_hotpath() report through the monitor: always
    counts the inspection, and a non-empty report flows through the
    shared emit path — the ``hotpath.``-prefixed rule ids land as
    ``lint.hotpath.*`` counters."""
    from .. import monitor
    monitor.counter("lint.hotpath.inspections").increase()
    if report:
        from . import emit_findings
        emit_findings(report)
    return report


def sweep_serving_stack(surfaces=("engine", "disagg", "fleet",
                                  "encoder", "mpmd"),
                        drive=True) -> Dict[str, Report]:
    """Build + briefly drive a tiny instance of each serving surface
    on the local (CPU is fine) backend and lint it warm — the CLI's
    ``--hotpath`` sweep and the tier-1 zero-false-positive gate.

    ``drive=False`` skips the warm-up requests and lints each surface
    cold: the inventories fall back to their default variant/bucket
    sets, so every rule still runs over every executable body — only
    the runtime-populated cache-key sets shrink. Used by
    ``paddle_lint --self-check`` where the sweep rides along a much
    larger package walk."""
    import numpy as np

    import paddle_tpu as paddle
    reports: Dict[str, Report] = {}
    prompts = [np.arange(1, 6, dtype=np.int64),
               np.arange(2, 9, dtype=np.int64)]

    def llama():
        from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM
        paddle.seed(0)
        cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=2)
        cfg.use_flash_attention = False
        net = LlamaForCausalLM(cfg)
        net.eval()
        return net

    if "engine" in surfaces:
        from paddle_tpu.inference import Engine, SamplingParams
        eng = Engine(llama(), max_slots=2, page_size=8, pool_pages=32,
                     max_context=64)
        if drive:
            eng.run([(p, SamplingParams(max_new_tokens=3))
                     for p in prompts])
        reports["engine"] = lint_surface(eng)
    if "disagg" in surfaces:
        from paddle_tpu.inference import DisaggEngine, SamplingParams
        eng = DisaggEngine(llama(), prefill_workers=1, decode_workers=1,
                           max_slots=2, page_size=8, pool_pages=32,
                           max_context=64)
        if drive:
            eng.run([(p, SamplingParams(max_new_tokens=3))
                     for p in prompts])
        reports["disagg"] = lint_surface(eng)
    if "fleet" in surfaces:
        from paddle_tpu.inference import SamplingParams, ServingFleet
        eng = ServingFleet(llama(), replicas=2, max_slots=2,
                           page_size=8, pool_pages=32, max_context=64)
        if drive:
            eng.run([(p, SamplingParams(max_new_tokens=3))
                     for p in prompts])
        reports["fleet"] = lint_surface(eng)
    if "encoder" in surfaces:
        from paddle_tpu.inference import BatchEncoder
        from paddle_tpu.text.models import BertConfig, BertModel
        paddle.seed(0)
        cfg = BertConfig.tiny(vocab=64, hidden=32, layers=2, heads=2)
        bert = BertModel(cfg)
        bert.eval()
        svc = BatchEncoder(bert, max_batch=2, bucket=16, max_seq=32)
        if drive:
            svc.run([p.tolist() for p in prompts])
        reports["encoder"] = lint_surface(svc)
    if "mpmd" in surfaces:
        import jax.numpy as jnp

        from paddle_tpu.distributed.mpmd_runtime import MpmdRingExecutor
        ex = MpmdRingExecutor(2, causal=True)
        if drive:
            rng = np.random.default_rng(0)
            q = jnp.asarray(rng.standard_normal((1, 2, 8, 4)),
                            jnp.float32)
            numel = float(q.size)
            ex.run(q, q, q,
                   dout_fn=lambda r, ob: ob * (2.0 / numel))
        reports["mpmd"] = lint_surface(ex)
    return reports


__all__ = ["ExecutableSpec", "HotpathInventory", "HOTPATH_RULES",
           "POOL_BYTES_FLOOR", "FETCH_BYTES_FLOOR", "emit_hotpath",
           "lint_inventory", "lint_surface", "struct_of",
           "sweep_serving_stack"]
