"""From a JAX profiler trace to numbers: device busy time, kernel time,
the costliest device operations and the longest idle gaps, each gap named
by what the benchmark's loop was doing.

The profiler writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`;
`jax.profiler.ProfileData` reads it. What was seen in a v5e trace by hand
(PERF.md, Findings, PR 25) and is relied on here:

* one plane per chip, named `/device:TPU:<n>`; its line `XLA Ops` holds
  one event per executed HLO operation, `XLA Modules` one per program;
* an op event's name is the operation's whole HLO text
  (`%fusion.7 = bf16[..] fusion(...), kind=kOutput, ...`); a `while` or
  `conditional` event spans the events of its body, which lie on the same
  line, so durations nest and only self time may be added up;
* a Pallas kernel is a `custom-call` whose text holds
  `custom_call_target="tpu_custom_call"`; its HLO name comes from the jax
  transformations around it (`%jvp__.4`, `%transpose_jvp___.9`), not
  from the kernel's body function;
* host threads are lines of the plane `/host:CPU`; a
  `jax.profiler.TraceAnnotation(name)` is an event called `name` on the
  line of the thread that opened it;
* all planes share one clock (nanoseconds from the start of the trace).

Everything below works on plain `Event` tuples, so the tests run it on a
few-KB recording (`benchmark/tests/data/`) without a profiler.
"""
from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass
from typing import Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class TraceError(RuntimeError):
    """The trace lacks what a metric needs. Never turned into a 0."""


# -- reading -----------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def read_xplane(path: str) -> List[Event]:
    """Device-op events and the benchmark's own host spans of a trace."""
    from jax.profiler import ProfileData
    events = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                events.append(Event(plane.name, line.name, ev.name,
                                    float(ev.start_ns),
                                    float(ev.duration_ns)))
    return events


def dump_events(events: Sequence[Event], path: str) -> None:
    with open(path, "w", encoding="utf-8") as fh:
        json.dump([[e.plane, e.line, e.name, e.start_ns, e.dur_ns]
                   for e in events], fh)


def load_events(path: str) -> List[Event]:
    with open(path, encoding="utf-8") as fh:
        return [Event(*row[:5]) for row in json.load(fh)]


# -- names ------------------------------------------------------------------

_OPCODE = re.compile(r"[ )]([a-z][a-z0-9\-]*)\(")


def short_name(hlo_text: str) -> str:
    """`%fusion.7 fusion bf16[2048,32000]` from an op's whole HLO text:
    its name, its opcode (a Pallas kernel reads `tpu_custom_call`) and
    the first array it produces."""
    name, sep, rest = hlo_text.partition(" = ")
    if not sep:
        return hlo_text[:80]
    if "tpu_custom_call" in rest:
        opcode = "tpu_custom_call"
    else:
        m = _OPCODE.search(rest)
        opcode = m.group(1) if m else "?"
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    return f"{name} {opcode} {shape.group(0) if shape else ''}".strip()


def self_times(ops: Sequence[Event]) -> List[float]:
    """Each op's own nanoseconds: its duration less that of the ops
    nested inside it (same line, contained interval)."""
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].start_ns, -ops[i].dur_ns))
    own = [e.dur_ns for e in ops]
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]].end_ns <= ops[i].start_ns:
            stack.pop()
        if stack and ops[i].end_ns <= ops[stack[-1]].end_ns:
            own[stack[-1]] -= ops[i].dur_ns
        stack.append(i)
    return own


# -- interval arithmetic -----------------------------------------------------

def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


# -- the reduced trace -------------------------------------------------------

class Reduced:
    """One traced window, reduced. Seconds throughout."""

    def __init__(self, events: Sequence[Event], chips: int = 1):
        spans = [e for e in events if e.plane == HOST_PLANE]
        win = [e for e in spans if e.name == WINDOW_SPAN]
        if len(win) != 1:
            raise TraceError(f"expected one {WINDOW_SPAN} span, found "
                             f"{len(win)}")
        self.lo, self.hi = win[0].start_ns, win[0].end_ns
        self.spans = [e for e in spans if e.name != WINDOW_SPAN]
        planes = sorted({e.plane for e in events
                         if e.plane.startswith(DEVICE_PLANE_PREFIX)})
        if len(planes) < chips:
            raise TraceError(f"the trace holds device planes {planes}; the "
                             f"cell runs on {chips} chip(s)")
        self.planes = planes[:chips]
        self._ops = {p: [e for e in events if e.plane == p
                         and e.end_ns > self.lo and e.start_ns < self.hi]
                     for p in self.planes}
        if not any(self._ops.values()):
            raise TraceError("no operation ran on the device inside the "
                             "traced window")

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def _busy(self, plane):
        return clip(union((e.start_ns, e.end_ns) for e in self._ops[plane]),
                    self.lo, self.hi)

    @property
    def busy_s(self) -> float:
        """Union of device-op intervals inside the window, averaged over
        the chips used."""
        return sum(total(self._busy(p)) for p in self.planes) \
            / len(self.planes) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, needles: Sequence[str]) -> float:
        """Summed device time (averaged over chips) of the operations
        whose HLO text holds one of `needles`. Raises when no
        event matches: a kernel that is not in the trace is a fault of
        the program or of the match, not a 0."""
        hits = [e for p in self.planes for e in self._ops[p]
                if any(n in e.name for n in needles)]
        if not hits:
            raise TraceError(f"no device operation matches {list(needles)}")
        return sum(min(e.end_ns, self.hi) - max(e.start_ns, self.lo)
                   for e in hits) / len(self.planes) / 1e9

    def top_ops(self, n: int = 10) -> List[list]:
        """[name, seconds] of the n operations with most SELF device
        time (first chip), executions of one HLO operation added up. A
        loop's time is its body's operations', not the loop's."""
        ops = self._ops[self.planes[0]]
        sums = {}
        for e, own in zip(ops, self_times(ops)):
            key = short_name(e.name)
            sums[key] = sums.get(key, 0.0) + own / 1e9
        return [[k, v] for k, v in sorted(sums.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 5) -> List[list]:
        """[what the host was doing, seconds] for the n longest stretches
        of the window in which nothing ran on the first chip. A gap is
        named after the benchmark span that covers most of it."""
        busy = self._busy(self.planes[0])
        edges = [self.lo] + [t for iv in busy for t in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._host_activity(a, b), (b - a) / 1e9]
                for a, b in gaps[:n]]

    def _host_activity(self, a: float, b: float) -> str:
        best, best_cover = "no benchmark span", 0.0
        for s in self.spans:
            cover = min(s.end_ns, b) - max(s.start_ns, a)
            if cover > best_cover:
                best, best_cover = s.name, cover
        return best

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(10), "idle_gaps": self.idle_gaps(5)}


def reduce_dir(trace_dir: str, chips: int = 1,
               keep_events: Optional[str] = None) -> Reduced:
    events = read_xplane(find_xplane(trace_dir))
    if keep_events:
        dump_events(events, keep_events)
    return Reduced(events, chips)
