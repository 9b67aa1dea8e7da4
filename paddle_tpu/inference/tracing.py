"""Per-request span timelines for the serving stack.

Every request the serving layers touch accumulates a host-truth span
log: QUEUED, each PREFILL slice, MIGRATING (disagg page migration and
fleet live-migration/failover), PREEMPTED, DECODE (tick-aggregated),
and a terminal FINISHED / FAILED(reason) marker. Spans are recorded on
the owning engine's injectable clock, so a replay on the virtual clock
produces bit-identical timelines run over run; span context is plain
serializable host state (a list of dicts on ``Request.spans``), so it
rides ``snapshot()/restore()``, ``Engine.extract_request``, and
worker/replica kills for free — a migrated or failed-over request
stitches into ONE contiguous timeline with the origin replica/worker
labeled per span.

The timeline contract (what ``validate_timeline`` checks):

* the first span is QUEUED (every request enters through a queue);
* spans are CONTIGUOUS — each span's ``t0_ms`` equals the previous
  span's ``t1_ms`` (no gaps, no overlaps; zero-length spans are legal,
  the virtual clock is constant within one tick);
* exactly one terminal span (FINISHED or FAILED) and it is last;
* a FAILED terminal span carries the failure reason in its detail.

Export reuses the chrome-trace conventions of
``profiler/chrome_trace.py`` — pid per origin (replica/worker) with
rank info via ``process_label()``, tid = slot lane — so serving
timelines open in perfetto next to op traces.

The tick-level sibling of those timelines is the STEP RECORD
(``StepLog``, at the end of this module): one row an ``Engine.step()``
with its phases, lanes and programs, kept in a ring for the life of
the engine whether a profiler runs or not. A timeline says how long a
request queued; the rows of the same seconds say what the engine was
doing meanwhile. It reads the wall clock (``time.perf_counter``, the
clock of the profiler's host store), never the injectable one, and is
no part of a timeline, a snapshot or a replay.
"""
from __future__ import annotations

import gc
import json
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from ..profiler.profiler import RecordEvent

# span phase vocabulary — mirrors the Request lifecycle states
QUEUED = "QUEUED"
PREFILL = "PREFILL"
DECODE = "DECODE"
PREEMPTED = "PREEMPTED"
MIGRATING = "MIGRATING"
FINISHED = "FINISHED"
FAILED = "FAILED"

TERMINAL = (FINISHED, FAILED)
PHASES = (QUEUED, PREFILL, DECODE, PREEMPTED, MIGRATING,
          FINISHED, FAILED)

#: ts/dur rounding (decimal places of a microsecond) for export —
#: fixed so the same virtual-clock replay emits the same bytes
_US_DP = 3


# -- span log primitives -----------------------------------------------------


def close_open(spans: List[dict], t_ms: float) -> Optional[dict]:
    """Close the trailing open span (``t1_ms is None``) at ``t_ms``.
    Returns the closed span, or None when nothing was open. A clock
    that did not advance closes a zero-length span; time never runs
    backwards within a timeline (clamped to the span's own start)."""
    if spans and spans[-1].get("t1_ms") is None:
        sp = spans[-1]
        sp["t1_ms"] = max(float(t_ms), sp["t0_ms"])
        return sp
    return None


def open_span(spans: List[dict], phase: str, t_ms: float, origin: str,
              slot: Optional[int] = None, **detail) -> dict:
    """Append a new OPEN span at ``t_ms``, closing any prior open span
    at the same instant — contiguity is structural, not checked after
    the fact."""
    closed = close_open(spans, t_ms)
    t0 = float(t_ms)
    if closed is not None:
        t0 = closed["t1_ms"]
    sp: dict = {"phase": phase, "t0_ms": t0, "t1_ms": None,
                "origin": str(origin)}
    if slot is not None:
        sp["slot"] = int(slot)
    if detail:
        sp["detail"] = {k: v for k, v in detail.items() if v is not None}
    spans.append(sp)
    return sp


def seal(spans: List[dict], phase: str, t_ms: float, origin: str,
         reason: Optional[str] = None) -> None:
    """Terminate a timeline: close the open span at ``t_ms`` and
    append the zero-length FINISHED/FAILED marker (with the failure
    reason in its detail). Idempotent — a timeline that already ends
    terminal is left alone, so a driver-level output path can seal
    defensively after an engine-level retire already did."""
    if spans and spans[-1].get("phase") in TERMINAL \
            and spans[-1].get("t1_ms") is not None:
        return
    closed = close_open(spans, t_ms)
    t = closed["t1_ms"] if closed is not None else float(t_ms)
    sp: dict = {"phase": phase, "t0_ms": t, "t1_ms": t,
                "origin": str(origin)}
    if reason:
        sp["detail"] = {"reason": str(reason)}
    spans.append(sp)


def mark_admitted(spans: List[dict], t_ms: float) -> None:
    """On an open QUEUED span, ``detail.admitted_ms``: the engine-clock
    instant the request was given its slot. The span still closes at
    the request's first prefill slice, so what lies between the two is
    its wait for the step's prefill budget (``StepLog``'s ``starved``
    counts the same wait by step)."""
    if current_phase(spans) == QUEUED:
        spans[-1].setdefault("detail", {})["admitted_ms"] = float(t_ms)


def current_phase(spans: List[dict]) -> Optional[str]:
    """Phase of the trailing OPEN span (None when nothing is open)."""
    if spans and spans[-1].get("t1_ms") is None:
        return spans[-1]["phase"]
    return None


def copy_spans(spans: List[dict]) -> List[dict]:
    """JSON-safe deep copy (snapshot serialization / Output attach —
    the live Request keeps mutating its own list)."""
    out = []
    for sp in spans:
        c = dict(sp)
        if "detail" in c:
            c["detail"] = dict(c["detail"])
        out.append(c)
    return out


def shift_spans(spans: List[dict], delta_ms: float) -> List[dict]:
    """Translate a timeline by ``delta_ms`` in place (restore onto a
    new clock epoch: durations and contiguity are preserved, absolute
    times re-anchor to the restoring process's clock)."""
    if delta_ms:
        for sp in spans:
            sp["t0_ms"] += delta_ms
            if sp.get("t1_ms") is not None:
                sp["t1_ms"] += delta_ms
    return spans


def restore_spans(spans: Optional[List[dict]], arrival_ms: float,
                  now_ms: float, origin: str,
                  resumed: bool) -> List[dict]:
    """Rebuild a snapshotted timeline on the restoring process's
    clock: shift so the timeline starts at the restored arrival time
    (durations and contiguity preserved; an in-process replay restore
    shifts by zero, keeping byte-identical timelines), close the span
    left open at snapshot time, and open the restored wait — PREEMPTED
    for a has-progress resume, QUEUED for an untouched request. A
    legacy entry with no spans starts a fresh QUEUED timeline."""
    spans = copy_spans(spans or [])
    if not spans:
        open_span(spans, QUEUED, now_ms, origin, kind="restore")
        return spans
    shift_spans(spans, arrival_ms - spans[0]["t0_ms"])
    open_span(spans, PREEMPTED if resumed else QUEUED, now_ms, origin,
              kind="restore")
    return spans


# -- validation --------------------------------------------------------------


def validate_timeline(spans: List[dict], tol_ms: float = 0.0
                      ) -> List[str]:
    """Check one request's span log against the timeline contract.
    Returns a list of human-readable problems — empty means the
    timeline is complete and contiguous. ``tol_ms`` loosens the
    contiguity equality for timelines reconstructed from a rounded
    export (0.0 for live span logs — the same floats propagate)."""
    problems: List[str] = []
    if not spans:
        return ["empty timeline"]
    if spans[0].get("phase") != QUEUED:
        problems.append(
            f"timeline starts {spans[0].get('phase')!r}, not QUEUED")
    last = spans[-1]
    if last.get("phase") not in TERMINAL:
        problems.append(
            f"no terminal span (ends {last.get('phase')!r})")
    elif last.get("phase") == FAILED and \
            not (last.get("detail") or {}).get("reason"):
        problems.append("FAILED terminal span carries no reason")
    prev_end = spans[0].get("t0_ms", 0.0)
    for k, sp in enumerate(spans):
        phase = sp.get("phase")
        if phase not in PHASES:
            problems.append(f"span {k}: unknown phase {phase!r}")
        t0, t1 = sp.get("t0_ms"), sp.get("t1_ms")
        if t1 is None:
            problems.append(f"span {k} ({phase}) left open")
            t1 = t0
        elif t1 < t0:
            problems.append(
                f"span {k} ({phase}) runs backwards ({t0}..{t1})")
        if abs(t0 - prev_end) > tol_ms:
            kind = "gap" if t0 > prev_end else "overlap"
            problems.append(
                f"span {k} ({phase}) {kind}: starts {t0}, previous "
                f"span ended {prev_end}")
        if phase in TERMINAL and k != len(spans) - 1:
            problems.append(
                f"span {k} ({phase}) is terminal but not last")
        prev_end = t1
    return problems


def phase_shares(spans: List[dict]) -> Dict[str, float]:
    """Total time (ms) per phase over one timeline — the per-request
    'where did the time go' summary the trace-summary tool tabulates
    fleet-wide."""
    out: Dict[str, float] = {}
    for sp in spans:
        t1 = sp.get("t1_ms")
        if t1 is None:
            continue
        dur = t1 - sp["t0_ms"]
        out[sp["phase"]] = out.get(sp["phase"], 0.0) + dur
    return out


# -- chrome-trace export -----------------------------------------------------


def build_serving_trace(timelines: Dict[int, List[dict]]) -> dict:
    """Chrome-trace dict for a set of stitched request timelines
    (``{req_id: spans}``). Follows profiler/chrome_trace.py's
    conventions: one pid per origin (replica/worker) carrying rank
    info from ``distributed.env.process_label()``, tid = slot lane
    (lane 0 is the queued/parked/migrating lane — spans with no slot),
    "X" complete events in microseconds off a common origin. Output is
    deterministic: origins, requests, and events are emitted in sorted
    order, times rounded to fixed precision — the same virtual-clock
    replay produces byte-identical bytes."""
    from ..profiler.chrome_trace import _rank_info
    rank, world = _rank_info()

    origins: List[str] = sorted(
        {sp["origin"] for spans in timelines.values() for sp in spans})
    pid_of = {o: i for i, o in enumerate(origins)}
    starts = [sp["t0_ms"] for spans in timelines.values()
              for sp in spans]
    t0 = min(starts) if starts else 0.0

    def us(t_ms: float) -> float:
        return round((t_ms - t0) * 1e3, _US_DP)

    events: List[dict] = []
    lanes = set()
    xevents: List[dict] = []
    for rid in sorted(timelines):
        for seq, sp in enumerate(timelines[rid]):
            t1 = sp.get("t1_ms")
            if t1 is None:       # defensive: export never drops a span
                t1 = sp["t0_ms"]
            pid = pid_of[sp["origin"]]
            lane = sp.get("slot")
            tid = 0 if lane is None else int(lane) + 1
            lanes.add((pid, tid))
            # seq preserves timeline order through the global event
            # sort (zero-length spans share one ts within a tick)
            args = {"req": int(rid), "seq": seq}
            args.update(sp.get("detail") or {})
            xevents.append({
                "ph": "X", "cat": "span", "name": sp["phase"],
                "pid": pid, "tid": tid, "ts": us(sp["t0_ms"]),
                "dur": round((t1 - sp["t0_ms"]) * 1e3, _US_DP),
                "args": args})
    for o in origins:
        pid = pid_of[o]
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": f"{o} (serving)"}})
        events.append({"ph": "M", "name": "process_sort_index",
                       "pid": pid, "tid": 0,
                       "args": {"sort_index": pid}})
    for pid, tid in sorted(lanes):
        name = "queue" if tid == 0 else f"slot {tid - 1}"
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": tid, "args": {"name": name}})
    xevents.sort(key=lambda e: (e["ts"], e["args"]["req"],
                                e["args"]["seq"]))
    events.extend(xevents)
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "metadata": {"tool": "paddle_tpu.serving_timeline",
                         "rank": rank, "world_size": world,
                         "requests": len(timelines)}}


def export_serving_trace(timelines: Dict[int, List[dict]],
                         path: str) -> str:
    """Write the stitched timelines as chrome-trace JSON. sort_keys +
    fixed separators: the byte stream is a pure function of the
    timelines, so two replays of one seed diff empty."""
    trace = build_serving_trace(timelines)
    with open(path, "w") as f:
        json.dump(trace, f, sort_keys=True, separators=(",", ":"))
    return path


def timelines_from_trace(trace: dict) -> Dict[int, List[dict]]:
    """Inverse of ``build_serving_trace`` (modulo ts rounding): the
    per-request span logs reconstructed from an export, for round-trip
    tests and the completeness gate's assert-via-the-artifact check.
    Validate reconstructed timelines with a small ``tol_ms`` — export
    rounds to 1e-3 us."""
    names = {}
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            label = str(ev.get("args", {}).get("name", ev["pid"]))
            if label.endswith(" (serving)"):
                label = label[:-len(" (serving)")]
            names[ev["pid"]] = label
    out: Dict[int, List[dict]] = {}
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "X" or ev.get("cat") != "span":
            continue
        rid = int(ev.get("args", {}).get("req", -1))
        seq = int(ev.get("args", {}).get("seq", 0))
        t0 = float(ev["ts"]) / 1e3
        sp = {"phase": ev["name"], "t0_ms": t0,
              "t1_ms": t0 + float(ev.get("dur", 0.0)) / 1e3,
              "origin": names.get(ev["pid"], str(ev["pid"])),
              "_seq": seq}
        if ev.get("tid", 0) > 0:
            sp["slot"] = int(ev["tid"]) - 1
        detail = {k: v for k, v in ev.get("args", {}).items()
                  if k not in ("req", "seq")}
        if detail:
            sp["detail"] = detail
        out.setdefault(rid, []).append(sp)
    for spans in out.values():
        spans.sort(key=lambda s: s.pop("_seq"))
    return out


# -- step record -------------------------------------------------------------

#: the spans a row times, by name: the engine's phases inside step()
#: and, last, the one span the CALLER opens in the gap before it
STEP_SPANS = (
    "engine.decode.dispatch", "engine.flush_state", "engine.decode.wait",
    "engine.harvest", "engine.prefill.harvest", "engine.prefill.wait",
    "engine.expire", "engine.admit", "engine.prefill",
    "engine.ensure_pages", "engine.bookkeeping", "engine.add_request")
GAP_SPAN = "engine.add_request"
#: time BLOCKED on the device: serving.device_ms_per_tick is their sum
WAIT_SPANS = ("engine.decode.wait", "engine.prefill.wait")

_FLOATS = ("t0_s", "wall_ms", "gap_ms", "cpu_ms") + STEP_SPANS + (
    "gap_spans_ms", "other_ms")
_INTS = ("step", "decoding", "prefilling", "waiting", "admitted",
         "finished", "preempted", "starved", "chunks", "chunk_tokens",
         "largest_bucket", "inflight", "compiles", "slow")
#: every key of a row, in order (``variant`` is the one string)
STEP_FIELDS = _FLOATS + _INTS + ("variant",)
_WALL = _FLOATS.index("wall_ms")

#: rows a log keeps before the oldest is overwritten: two and a half
#: minutes at a 9.3 ms step
STEP_LOG_ROWS = 16_384
#: a step is SLOW when its wall_ms exceeds
#: max(SLOW_STEP_FLOOR_MS, SLOW_STEP_FACTOR x the median wall_ms of the
#: SLOW_STEP_MEDIAN_ROWS rows before it)
SLOW_STEP_FLOOR_MS = 250.0
SLOW_STEP_FACTOR = 8.0
SLOW_STEP_MEDIAN_ROWS = 256
#: slow entries kept (the newest), and the rows kept around each
SLOW_STEPS_KEPT = 16
SLOW_ROWS_BEFORE = 8
SLOW_ROWS_AFTER = 4
#: a garbage collection at least this long is kept for the slow entries
SLOW_GC_MS = 10.0
_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use",
                "largest_free_block_bytes")

# (perf_counter at its end, generation, ms) of the process's long
# collections; one callback a process, installed by the first StepLog
_slow_gcs: "deque[tuple]" = deque(maxlen=64)
_gc_t0 = [0.0]


def _on_gc(phase: str, info: dict) -> None:
    now = time.perf_counter()
    if phase == "start":
        _gc_t0[0] = now
    elif (now - _gc_t0[0]) * 1e3 >= SLOW_GC_MS:
        _slow_gcs.append((now, int(info["generation"]),
                          (now - _gc_t0[0]) * 1e3))


_logs: Dict[str, "StepLog"] = {}


def step_logs() -> Dict[str, "StepLog"]:
    """Every engine's step log by the engine's ``label`` (a plain
    engine's is "engine"), the newest engine's where two shared one.
    A log stays here after its engine's ``close()``, so a driver reads
    the run that has just ended."""
    return dict(_logs)


class StepSpan(RecordEvent):
    """The engine's span primitive: a ``RecordEvent`` (same name, same
    arguments, recorded by a trace or a ``Profiler`` exactly as one)
    that, when it closes, also adds its duration to the open row of a
    ``StepLog`` — one site, two sinks. The duration runs from the
    ``perf_counter`` reading the ``RecordEvent`` takes on entry."""

    def __init__(self, log: "StepLog", name: str, **args):
        super().__init__(name, **args)
        self._log = log

    def __enter__(self):
        self._log._depth += 1
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self._log._close(self.name, time.perf_counter() - self._start)
        return False


class StepLog:
    """One row an ``Engine.step()``, in a ring of ``STEP_LOG_ROWS``.

    A row (``STEP_FIELDS``): ``step`` (the ``step`` argument of that
    step's ``engine.step`` span: what joins a row to a profiler trace
    of the same run); ``t0_s`` / ``wall_ms`` on ``time.perf_counter``;
    ``gap_ms``, from the previous step's return to this entry (the
    caller's time); ``cpu_ms``, ``time.thread_time()`` across the
    step; one field of milliseconds a span of ``STEP_SPANS`` (a span
    opened twice adds up; ``engine.add_request`` is the caller's, in
    the gap); ``gap_spans_ms``, every engine span closed in the gap
    (``add_request``, and the wait and harvest of a drain that
    ``cancel()`` or ``close()`` forced); ``other_ms`` = ``wall_ms``
    less the step's top-level spans; the lanes on entry (``decoding``,
    ``prefilling``, ``waiting``) and what the step did (``admitted``,
    ``finished``, ``preempted``, ``starved``, ``chunks``,
    ``chunk_tokens``, ``largest_bucket``, ``variant``, ``inflight``,
    ``compiles``); ``slow`` 0/1.

    The engine drives it: ``begin()`` / ``end()`` around a step,
    ``StepSpan`` for the phases, and plain attribute writes for the
    counts (``log.starved += 1``). A step that raises leaves no row."""

    def __init__(self, label: str):
        self.label = label
        self._cap = STEP_LOG_ROWS
        self._f = np.zeros((self._cap, len(_FLOATS)))
        self._i = np.zeros((self._cap, len(_INTS)), np.int64)
        self._variant = [""] * self._cap
        self._n = 0                   # rows written since construction
        self._slow: "deque[dict]" = deque(maxlen=SLOW_STEPS_KEPT)
        self._wants_after: List[dict] = []
        self._acc = dict.fromkeys(STEP_SPANS, 0.0)
        self._open = False
        self._depth = 0
        self._top = self._gap_spans = 0.0
        self._t0 = self._cpu0 = 0.0
        self._last_end = 0.0
        self._entry = (0, 0, 0, 0)
        self._reset_counts()
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        _logs[label] = self

    def _reset_counts(self) -> None:
        self.admitted = self.preempted = self.starved = 0
        self.chunks = self.chunk_tokens = self.largest_bucket = 0
        self.inflight = self.compiles = 0
        self.variant = ""

    # -- written by the engine -----------------------------------------------

    def begin(self, step: int, decoding: int, prefilling: int,
              waiting: int) -> None:
        self._entry = (step, decoding, prefilling, waiting)
        self._reset_counts()
        asked = self._acc[GAP_SPAN]
        self._acc = dict.fromkeys(STEP_SPANS, 0.0)
        self._acc[GAP_SPAN] = asked
        self._top = 0.0
        self._depth = 0
        self._open = True
        self._cpu0 = time.thread_time()
        self._t0 = time.perf_counter()

    def _close(self, name: str, seconds: float) -> None:
        self._depth -= 1
        if self._open:
            self._acc[name] += seconds
            if self._depth == 0:
                self._top += seconds
        elif self._depth == 0:
            # between steps: the caller's add_request(), or a drain
            self._gap_spans += seconds
            if name == GAP_SPAN:
                self._acc[name] += seconds

    def end(self, finished: int) -> tuple:
        """Close the open row. Returns ``(wall_ms, wait_ms, slow)``:
        the step's wall time, the part of it blocked on the device,
        and whether the step was slow (``slow()`` then holds it)."""
        t1 = time.perf_counter()
        cpu_ms = (time.thread_time() - self._cpu0) * 1e3
        self._open = False
        acc, t0 = self._acc, self._t0
        wall_ms = (t1 - t0) * 1e3
        wait_ms = sum(acc[w] for w in WAIT_SPANS) * 1e3
        slow = wall_ms > SLOW_STEP_FLOOR_MS and \
            wall_ms > SLOW_STEP_FACTOR * self._median_wall_ms()
        at = self._n % self._cap
        self._f[at] = (
            t0, wall_ms, (t0 - self._last_end) * 1e3 if self._n else 0.0,
            cpu_ms, *(s * 1e3 for s in acc.values()),
            self._gap_spans * 1e3, wall_ms - self._top * 1e3)
        self._i[at] = (
            *self._entry, self.admitted, finished, self.preempted,
            self.starved, self.chunks, self.chunk_tokens,
            self.largest_bucket, self.inflight, self.compiles, int(slow))
        self._variant[at] = self.variant
        self._n += 1
        self._last_end = t1
        self._gap_spans = 0.0
        acc[GAP_SPAN] = 0.0
        if self._wants_after:
            row = self._row(self._n - 1)
            for entry in self._wants_after:
                entry["after"].append(row)
            self._wants_after = [e for e in self._wants_after
                                 if len(e["after"]) < SLOW_ROWS_AFTER]
        if slow:
            self._keep_slow(t0, t1)
        return wall_ms, wait_ms, slow

    def _median_wall_ms(self) -> float:
        k = min(self._n, SLOW_STEP_MEDIAN_ROWS, self._cap)
        if not k:
            return 0.0
        last = np.arange(self._n - k, self._n) % self._cap
        return float(np.median(self._f[last, _WALL]))

    def _keep_slow(self, t0: float, t1: float) -> None:
        """The slow row with the rows around it, the long collections
        that ran inside it and, read now that the step is over (and
        only for a slow step), the first device's memory."""
        from ..device.monitor import _device_stats
        n = self._n - 1
        stats = _device_stats(0)
        entry = {
            "row": self._row(n),
            "before": [self._row(k) for k in range(
                max(n - SLOW_ROWS_BEFORE, self._first()), n)],
            "after": [],
            "collections": [{"generation": g, "ms": ms}
                            for at, g, ms in _slow_gcs if t0 <= at <= t1],
            "memory": {k: int(stats[k]) for k in _MEMORY_KEYS
                       if k in stats}}
        self._slow.append(entry)
        self._wants_after.append(entry)

    # -- read ----------------------------------------------------------------

    def _first(self) -> int:
        return max(0, self._n - self._cap)

    def _row(self, n: int) -> dict:
        at = n % self._cap
        row = dict(zip(_FLOATS, self._f[at].tolist()))
        row.update(zip(_INTS, self._i[at].tolist()))
        row["variant"] = self._variant[at]
        return row

    def __len__(self) -> int:
        return self._n - self._first()

    def rows(self, t0: Optional[float] = None,
             t1: Optional[float] = None) -> List[dict]:
        """The rows kept, oldest first, as plain dicts; with ``t0`` /
        ``t1`` (``time.perf_counter`` seconds) those whose ``t0_s``
        lies between them."""
        lo = float("-inf") if t0 is None else t0
        hi = float("inf") if t1 is None else t1
        began = self._f[:, 0]
        return [self._row(n) for n in range(self._first(), self._n)
                if lo <= began[n % self._cap] <= hi]

    def slow(self) -> List[dict]:
        """The newest ``SLOW_STEPS_KEPT`` slow steps, oldest first:
        ``row``, the ``before`` and ``after`` rows, ``collections``
        and ``memory``."""
        return [dict(e, before=list(e["before"]), after=list(e["after"]))
                for e in self._slow]
