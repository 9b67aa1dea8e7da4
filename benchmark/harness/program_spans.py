"""The program's own host spans and its XLA programs by name, read from
the trace a `--trace 1` run has just written, and device-idle time
attributed to the span the host was in.

`trace_reduce.py` reads the benchmark loop's `bench.*` spans and the
`XLA Ops` line and stays as it is. This file reads the same xplane a
second time (once a run) for what the program names itself:

* host spans `engine.*` (`Engine.step()` and its phases, PERF.md section
  3), `trainstep.*` (`TrainStep.__call__`) and `bench.*`, each with the
  thread line it lies on and the keyword arguments the program gave it
  (`event.stats`: `ctx_tokens`, `slots`, `bucket`, `req`, ...);
* the `XLA Modules` line of the first chip: one event per executed
  program, named `jit_<function name>(<fingerprint>)`. The engine names
  its bodies `serve_decode_<variant>`, `serve_prefill_<bucket>`,
  `serve_multi_<k>`, `serve_verify_<variant>`, so a substring tells the
  decode program from each prefill bucket.

The device-busy intervals come from `ctx.trace` (the `Reduced` the
runner made). Attribution rule: each instant inside `bench.window` at
which nothing runs on the first chip goes to the INNERMOST span open at
that instant on the thread that holds `bench.window`; an instant under
no span goes to `NO_SPAN`. `bench.window` itself is the window, not a
span. The buckets of the `serve.idle_*_ms` metrics merge span names
(`BUCKETS`); `idle_by_span` keeps every name, and one
`{"info": "idle_by_span", ...}` line a run prints it.

The two planes do NOT quite share a clock: on the v5e machine the device
plane runs 0.9-1.8 ms ahead of the host plane (a steady decode program
begins 0.8 ms before the call that dispatches it; PERF.md section 6, PR 26).
The rule above is applied to the times as recorded; the sum of the buckets is
exact, their split is good to that error, and `device_clock_early_ms`
(printed in the same info line) gives its bounds for the run at hand.

A program without these spans (the parent of the PR that added them)
gives `for_ctx(ctx) -> None` and every metric that reads this file is
left out of the line; a `ProgramSpans` built on such a trace raises
`TraceError`, never a 0.

Where a Pallas kernel's `name=` lands on a v5e trace (seen in PR 26's traced
chip run of the serve cell, JAX 0.9.0): it becomes the NAME of the kernel's
HLO instruction, so the op event on `XLA Ops` reads
`%paged_decode.8 = bf16[48,8,4,128]{...} custom-call(...),
custom_call_target="tpu_custom_call", ..., frontend_attributes={kernel_metadata={}}`
(before, `%jvp__.N` or `%custom-call.N`); under `jax.grad` the
transformations wrap it: the train step's kernels read `%jvp_flash_fwd_.N`,
`%transpose_jvp_flash_dq__.N`, `%transpose_jvp_flash_dkv__.N`. It is in no `stats` entry (those are
`device_offset_ps`, `device_duration_ps`, `Time Scale Multiplier`) and not in
`kernel_metadata`. An operation that CONSUMES the kernel's output names it
among its operands (`reshape(... %paged_decode.8)`), so a substring of the
whole text over-counts (by 0.07% there, by more where the consumer is a
large fusion): `kernel_s` here takes the Pallas calls and looks in the
instruction's own name.
"""
from __future__ import annotations

import bisect
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from . import stats
from .trace_reduce import (DEVICE_PLANE_PREFIX, HOST_PLANE, OPS_LINE,
                           WINDOW_SPAN, Event, TraceError, clip, find_xplane,
                           total, union)

MODULES_LINE = "XLA Modules"
PALLAS_CALL = 'custom_call_target="tpu_custom_call"'
SPAN_PREFIXES = ("engine.", "trainstep.", "bench.")
PROGRAM_PREFIXES = ("engine.", "trainstep.")
NO_SPAN = "(no span)"
DECODE_PROGRAMS = ("serve_decode_", "serve_multi_", "serve_verify_")
SCRATCH = "benchmark_out"       # where run.py puts trace-<cell name>

# span name -> bucket of the serve.idle_<bucket>_ms metrics. A span's
# OWN time counts (its children have their own rows). Any other
# `engine.*` name lies inside `engine.step` and falls to `schedule`;
# everything else (the load generator's `bench.*`, no span) is outside.
BUCKETS = {
    "engine.decode.dispatch": "dispatch",
    "engine.flush_state": "dispatch",
    "engine.prefill": "prefill_host",
    "engine.decode.wait": "wait",
    "engine.prefill.wait": "wait",
    "engine.harvest": "harvest",
    "engine.ensure_pages": "bookkeeping",
    "engine.bookkeeping": "bookkeeping",
    "engine.add_request": "schedule",
    "engine.expire": "schedule",
    "engine.admit": "schedule",
    "engine.step": "schedule",
}
BUCKET_NAMES = ("dispatch", "prefill_host", "wait", "harvest",
                "bookkeeping", "schedule", "outside_step")


def bucket_of(span_name: str) -> str:
    if span_name in BUCKETS:
        return BUCKETS[span_name]
    return "schedule" if span_name.startswith("engine.") else "outside_step"


@dataclass(frozen=True)
class Span(Event):
    """A host span: an event (its `line` is the thread) with the keyword
    arguments the program gave it."""
    stats: dict = field(default_factory=dict)


# -- reading -----------------------------------------------------------------

def read_xplane(path: str) -> Tuple[List[Span], List[Event], List[Event]]:
    """(host spans, program executions, Pallas kernel calls) of a trace;
    the device events are the first chip's."""
    from jax.profiler import ProfileData
    spans: List[Span] = []
    device: Dict[str, Tuple[List[Event], List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append(Span(*_event(plane, line, ev),
                                          dict(ev.stats)))
        elif plane.name.startswith(DEVICE_PLANE_PREFIX):
            modules, kernels = device.setdefault(plane.name, ([], []))
            for line in plane.lines:
                if line.name not in (MODULES_LINE, OPS_LINE):
                    continue
                for ev in line.events:
                    if line.name == MODULES_LINE:
                        modules.append(Event(*_event(plane, line, ev)))
                    elif PALLAS_CALL in ev.name:
                        kernels.append(Event(*_event(plane, line, ev)))
    modules, kernels = device[min(device)] if device else ([], [])
    return spans, modules, kernels


def _event(plane, line, ev) -> tuple:
    return (plane.name, line.name, ev.name, float(ev.start_ns),
            float(ev.duration_ns))


def load_recording(path: str):
    """(events for `trace_reduce.Reduced`, spans, modules, kernels) of a
    recording: one JSON list of rows `[plane, line, name, start_ns,
    dur_ns(, stats)]` (`trace_reduce.load_events` reads the same file; it
    takes the first five fields)."""
    with open(path, encoding="utf-8") as fh:
        rows = json.load(fh)
    events, spans, modules, kernels = [], [], [], []
    for row in rows:
        ev = Event(*row[:5])
        if ev.plane == HOST_PLANE:
            spans.append(Span(*row[:5], *row[5:6]))
            if ev.name.startswith("bench."):
                events.append(ev)
        elif ev.line == MODULES_LINE:
            modules.append(ev)
        else:
            events.append(ev)
            if PALLAS_CALL in ev.name:
                kernels.append(ev)
    return events, spans, modules, kernels


# -- attribution -------------------------------------------------------------

def innermost_segments(spans: Sequence[Span], lo: float, hi: float
                       ) -> List[Tuple[float, float, str]]:
    """Disjoint `(a, b, name)` covering `[lo, hi)`: the innermost of
    `spans` (one thread, properly nested) open at each instant, `NO_SPAN`
    where none is."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Span] = []
    t = lo

    def emit(until: float) -> None:
        nonlocal t
        until = min(until, hi)
        if until > t:
            out.append((t, until, stack[-1].name if stack else NO_SPAN))
            t = until

    for s in sorted(spans, key=lambda s: (s.start_ns, -s.dur_ns)):
        if s.end_ns <= lo or s.start_ns >= hi:
            continue
        while stack and stack[-1].end_ns <= s.start_ns:
            emit(stack[-1].end_ns)
            stack.pop()
        emit(s.start_ns)
        stack.append(s)
    while stack:
        emit(stack[-1].end_ns)
        stack.pop()
    emit(hi)
    return out


def overlap_ns(intervals: Sequence[Tuple[float, float]], starts, a: float,
               b: float) -> float:
    """Nanoseconds of the sorted disjoint `intervals` inside `[a, b)`;
    `starts` is the list of their starts (for the bisection)."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    got = 0.0
    while i < len(intervals) and intervals[i][0] < b:
        got += max(0.0, min(intervals[i][1], b) - max(intervals[i][0], a))
        i += 1
    return got


class ProgramSpans:
    """One traced window: device-idle time by host span, and the
    program executions by name. Seconds unless a name says ms."""

    def __init__(self, spans: Sequence[Span], modules: Sequence[Event],
                 busy: Sequence[Tuple[float, float]], lo: float, hi: float,
                 kernels: Sequence[Event] = ()):
        if not any(s.name.startswith(PROGRAM_PREFIXES) for s in spans):
            raise TraceError(
                "the trace holds no engine.* or trainstep.* host span: the "
                "program does not name its phases (or no trace was running "
                "when they ran)")
        win = [s for s in spans if s.name == WINDOW_SPAN]
        if len(win) != 1:
            raise TraceError(f"expected one {WINDOW_SPAN} span, found "
                             f"{len(win)}")
        self.lo, self.hi = lo, hi
        self.spans = [s for s in spans if s.name != WINDOW_SPAN
                      and s.end_ns > lo and s.start_ns < hi]
        self.thread = win[0].line
        self.modules = [m for m in modules if lo <= m.start_ns < hi]
        self.kernels = [k for k in kernels
                        if k.end_ns > lo and k.start_ns < hi]
        self.busy = clip(union(busy), lo, hi)
        self._busy_starts = [a for a, _ in self.busy]
        self._on_thread = [s for s in self.spans if s.line == self.thread]
        self._by_span: Optional[Dict[str, float]] = None

    # -- idle time by span ---------------------------------------------------

    @property
    def idle_s(self) -> float:
        return (self.hi - self.lo - total(self.busy)) / 1e9

    def idle_by_span(self) -> Dict[str, float]:
        """Seconds of device-idle time by innermost span name."""
        if self._by_span is None:
            out: Dict[str, float] = {}
            for a, b, name in innermost_segments(self._on_thread, self.lo,
                                                 self.hi):
                idle = (b - a) - overlap_ns(self.busy, self._busy_starts,
                                            a, b)
                if idle > 0.0:
                    out[name] = out.get(name, 0.0) + idle / 1e9
            self._by_span = out
        return dict(self._by_span)

    def idle_by_bucket(self) -> Dict[str, float]:
        out = {b: 0.0 for b in BUCKET_NAMES}
        for name, s in self.idle_by_span().items():
            out[bucket_of(name)] += s
        return out

    def longest_gaps(self, n: int = 5) -> List[list]:
        """[innermost span covering most of it, seconds] for the n
        longest stretches with nothing on the first chip."""
        edges = [self.lo] + [t for iv in self.busy for t in iv] + [self.hi]
        gaps = sorted(((edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]),
                      key=lambda g: g[0] - g[1])[:n]
        out = []
        for a, b in gaps:
            cover: Dict[str, float] = {}
            for x, y, name in innermost_segments(self._on_thread, a, b):
                cover[name] = cover.get(name, 0.0) + (y - x)
            out.append([max(cover, key=cover.get), (b - a) / 1e9])
        return out

    # -- programs ------------------------------------------------------------

    def programs(self, needle: str) -> List[Event]:
        """Executions, begun inside the window, of the programs whose
        name holds `needle`."""
        return [m for m in self.modules if needle in m.name]

    def program_names(self) -> List[str]:
        return sorted({m.name.split("(")[0] for m in self.modules})

    def program_ms_p50(self, needle: str) -> Optional[float]:
        hits = self.programs(needle)
        return stats.median([m.dur_ns / 1e6 for m in hits]) if hits else None

    def program_busy_s(self, needle: str) -> float:
        """Device-busy seconds inside the executions of those programs."""
        return sum(overlap_ns(self.busy, self._busy_starts, m.start_ns,
                              min(m.end_ns, self.hi))
                   for m in self.programs(needle)) / 1e9

    def kernel_s(self, name: str) -> float:
        """Summed device time inside the window of the Pallas calls whose
        HLO instruction's own name (the text before ` = `) holds `name`;
        of every Pallas call of the trace when none does (a program that
        gives `pl.pallas_call` no `name=`)."""
        hits = [k for k in self.kernels
                if name in k.name.partition(" = ")[0]] or self.kernels
        if not hits:
            raise TraceError("no Pallas kernel call on the device in the "
                             "traced window")
        return sum(min(k.end_ns, self.hi) - max(k.start_ns, self.lo)
                   for k in hits) / 1e9

    def device_clock_early_ms(self) -> Optional[Tuple[float, float]]:
        """(at least, at most) the milliseconds by which the device plane's
        clock runs ahead of the host plane's, from causality over the decode
        ticks of the window: a program cannot begin before the call that
        dispatches it (the end of `engine.flush_state`, the last thing
        before the call inside `engine.decode.dispatch`), and it has ended
        when `engine.decode.wait` returns. `trace_reduce.py` took the
        planes to share one clock; on the v5e machine they differ by about
        a millisecond (PERF.md section 6, PR 26), so idle time near a
        span's edge can land in its neighbour. The buckets are NOT shifted:
        this is the error bar on their split, not on their sum."""
        decode = sorted((m for m in self.modules if any(
            n in m.name for n in DECODE_PROGRAMS)), key=lambda m: m.end_ns)
        ends = [m.end_ns for m in decode]
        at_least, at_most = [], []
        flushes = self.named("engine.flush_state")
        for d in self.named("engine.decode.dispatch"):
            i = bisect.bisect_right(ends, d.end_ns)
            if "ctx_tokens" not in d.stats or i == len(decode):
                continue            # nothing dispatched / ran past the trace
            call = max([f.end_ns for f in flushes
                        if d.start_ns <= f.start_ns < d.end_ns],
                       default=d.start_ns)
            at_least.append(call - decode[i].start_ns)
        for w in self.named("engine.decode.wait"):
            i = bisect.bisect_right(ends, w.end_ns) - 1
            if i >= 0 and w.end_ns - ends[i] < w.dur_ns:
                at_most.append(w.end_ns - ends[i])
        if not at_least or not at_most:
            return None
        return max(at_least) / 1e6, min(at_most) / 1e6

    # -- span arguments ------------------------------------------------------

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name
                and self.lo <= s.start_ns < self.hi]

    def span_ms_p50(self, name: str) -> Optional[float]:
        hits = self.named(name)
        return stats.median([s.dur_ns / 1e6 for s in hits]) if hits else None


# -- from a metric -----------------------------------------------------------

def trace_dir(cell_name: str) -> str:
    from .load import REPO_ROOT
    return os.path.join(str(REPO_ROOT), SCRATCH, f"trace-{cell_name}")


def for_ctx(ctx) -> Optional[ProgramSpans]:
    """The `ProgramSpans` of the run `ctx` belongs to, read once a run
    (kept in the run's `samples`); `None` when the run was not traced or
    the program under test names no phase of its own (then the metric is
    left out of the line)."""
    if ctx.trace is None:
        return None
    if "program_spans" not in ctx.samples:
        ctx.samples["program_spans"] = _read(ctx)
    return ctx.samples["program_spans"]


def _read(ctx) -> Optional[ProgramSpans]:
    spans, modules, kernels = read_xplane(
        find_xplane(trace_dir(ctx.cell.name)))
    if not any(s.name.startswith(PROGRAM_PREFIXES) for s in spans):
        return None
    red = ctx.trace
    ps = ProgramSpans(spans, modules, red._busy(red.planes[0]), red.lo,
                      red.hi, kernels)
    print(json.dumps({
        "info": "idle_by_span", "idle_s": ps.idle_s,
        "window_s": (ps.hi - ps.lo) / 1e9,
        "by_span": dict(sorted(ps.idle_by_span().items(),
                               key=lambda kv: -kv[1])),
        "longest_gaps": ps.longest_gaps(5),
        "device_clock_early_ms": ps.device_clock_early_ms(),
        "programs": {n: len(ps.programs(n)) for n in ps.program_names()},
        "span_ms_p50": {n: ps.span_ms_p50(n)
                        for n in sorted({s.name for s in ps.spans})}}),
        flush=True)
    return ps


def decode_ticks(ps: ProgramSpans) -> int:
    """Decode-program executions begun in the window (a fused multi-tick
    or a verify program counts once: it is one dispatch)."""
    return sum(len(ps.programs(n)) for n in DECODE_PROGRAMS)


def serve_idle_ms_per_tick(ctx, bucket: Optional[str] = None
                           ) -> Optional[float]:
    """Device-idle milliseconds a decode tick, all of it or one bucket's:
    what the `serve.idle_*` metric files return."""
    ps = for_ctx(ctx)
    if ps is None:
        return None
    ticks = decode_ticks(ps)
    if not ticks:
        raise TraceError("no serve_decode_* program ran in the traced "
                         f"window; programs seen: {ps.program_names()}")
    idle_s = ps.idle_s if bucket is None else ps.idle_by_bucket()[bucket]
    return 1e3 * idle_s / ticks
