"""Profiler core (reference python/paddle/profiler/profiler.py:358)."""
from __future__ import annotations

import enum
import os
import time
from collections import defaultdict
from typing import Callable, Iterable, Optional

import jax


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Step-keyed state schedule (reference profiler.py make_scheduler)."""
    period = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def _default_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


class _HostEventStore:
    """In-process host event aggregation (reference host_tracer role)."""

    def __init__(self):
        self.events = []  # (name, start, end, args)

    def add(self, name, start, end, args=None):
        self.events.append((name, start, end, dict(args or ())))

    def aggregate(self):
        agg = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])
        for name, s, e, _ in self.events:
            d = (e - s) * 1e3  # ms
            a = agg[name]
            a[0] += 1
            a[1] += d
            a[2] = min(a[2], d)
            a[3] = max(a[3], d)
        return {k: dict(calls=v[0], total_ms=v[1], min_ms=v[2],
                        max_ms=v[3], avg_ms=v[1] / max(v[0], 1))
                for k, v in agg.items()}


_current_store: Optional[_HostEventStore] = None


class RecordEvent:
    """User annotation (reference utils.py:47): shows on the device trace
    via jax.profiler.TraceAnnotation and in host summaries.

    Keyword ``args`` (small ints/strings read from host state) ride
    along: they come back as the event's ``stats`` in a
    ``jax.profiler`` trace and as the row's args in the Profiler's host
    store. ``set(**args)`` adds the ones only known once the work is
    done (tokens harvested, pages allocated). A span is recorded while
    a ``jax.profiler`` trace or a ``Profiler`` is running and is inert
    (under a microsecond) otherwise."""

    def __init__(self, name: str, event_type=None, **args):
        self.name = name
        self.args = args
        self._ann = None
        self._start = None

    def begin(self):
        self.__enter__()

    def end(self):
        self.__exit__(None, None, None)

    def set(self, **args):
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*(exc or (None, None, None)))
            self._ann = None
        if _current_store is not None and self._start is not None:
            _current_store.add(self.name, self._start, time.perf_counter(),
                               self.args)
        return False


class Profiler:
    """Reference-shaped Profiler.

        with paddle.profiler.Profiler(on_trace_ready=...) as p:
            for batch in loader:
                train_step(...)
                p.step()
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready=None, record_shapes=False,
                 profile_memory=False, timer_only=False, log_dir=None,
                 **kw):
        if callable(scheduler):
            self._scheduler = scheduler
        elif isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            lo, hi = scheduler
            self._scheduler = make_scheduler(
                closed=max(lo, 0), ready=0, record=hi - lo, repeat=1)
        else:
            self._scheduler = _default_scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._record_shapes = record_shapes
        self._log_dir = log_dir or os.environ.get(
            "PADDLE_PROFILER_LOG_DIR", "./profiler_log")
        self.step_num = 0
        self._state = ProfilerState.CLOSED
        self._tracing = False
        self._recording = False
        self._fired_in_step = False
        self._store = _HostEventStore()
        from .stats import RuntimeStats
        self._runtime_stats = RuntimeStats(record_timeline=True,
                                           profile_memory=profile_memory)
        self.last_trace_path = None  # set by export_chrome_tracing

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        global _current_store
        _current_store = self._store
        self._state = self._scheduler(self.step_num)
        self._transit()

    def stop(self):
        global _current_store
        # batched NaN checking must not leave queued flags unreported
        # past the end of a profiled run — but a raised NaN report must
        # not leak an open device trace either
        from ..core.dispatch import flush_nan_checks
        try:
            flush_nan_checks()
        finally:
            had_trace = self._tracing
            if self._tracing:
                self._stop_trace()
            self._runtime_stats.stop()
            self._recording = False
            # fire only for a cycle still open at stop(); completed
            # cycles already fired in step()
            if self._on_trace_ready is not None and (
                    had_trace or (self._timer_only
                                  and not self._fired_in_step)):
                self._on_trace_ready(self)
            _current_store = None

    def step(self, num_samples: Optional[int] = None):
        prev = self._state
        # step boundary housekeeping BEFORE the state transition so the
        # closing step's compiles/memory land in its own bucket — and
        # queued batched NaN flags (FLAGS_check_nan_inf_batch > 1) are
        # reported against the step that produced them
        from ..core.dispatch import flush_nan_checks
        flush_nan_checks()
        if self._recording:
            self._runtime_stats.on_step(self.step_num)
        self.step_num += 1
        new_state = self._scheduler(self.step_num)
        if prev == ProfilerState.RECORD_AND_RETURN:
            # end of a recording cycle: close the trace (even if the next
            # cycle records again — cycles must not merge) and hand the
            # result to on_trace_ready, per the reference contract
            if self._tracing:
                self._stop_trace()
            if self._on_trace_ready is not None:
                self._on_trace_ready(self)
                self._fired_in_step = True
            # host telemetry must not merge across cycles either: the
            # next cycle starts with fresh collectors and a fresh host
            # event store (the exported trace above owns this window)
            self._runtime_stats.reset_window()
            self._recording = False
            self._store = _HostEventStore()
            global _current_store
            _current_store = self._store
        if new_state != self._state or prev == \
                ProfilerState.RECORD_AND_RETURN:
            self._state = new_state
            self._transit()

    def _transit(self):
        recording = self._state in (ProfilerState.RECORD,
                                    ProfilerState.RECORD_AND_RETURN)
        # host-side telemetry (op dispatch, XLA compiles, memory) runs
        # whenever the schedule says RECORD — including timer_only mode,
        # which skips only the heavyweight device tracer below
        if recording and not self._recording:
            self._runtime_stats.start()
            self._recording = True
        elif not recording and self._recording:
            self._runtime_stats.stop()
            self._recording = False
        want_trace = recording and not self._timer_only
        if want_trace and not self._tracing:
            self._start_trace()
        elif not want_trace and self._tracing:
            self._stop_trace()

    def _start_trace(self):
        os.makedirs(self._log_dir, exist_ok=True)
        try:
            jax.profiler.start_trace(self._log_dir)
            self._tracing = True
        except Exception:
            self._tracing = False  # tracing unavailable (e.g. nested)

    def _stop_trace(self):
        try:
            jax.profiler.stop_trace()
        finally:
            self._tracing = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- reporting -----------------------------------------------------------
    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None, row_limit=100):
        """Multi-view report (reference profiler_statistic _build_table):
        OverView + OperatorView + MemoryView + UDFView by default, any
        subset via ``views=SummaryView.* | [SummaryView.*, ...]``, rows
        ordered by ``sorted_by`` (a SortedKeys member)."""
        from .profiler_statistic import StatisticData
        return StatisticData(self).build_table(
            sorted_by=sorted_by, views=views, row_limit=row_limit,
            time_unit=time_unit)

    @property
    def statistic_data(self):
        from .profiler_statistic import StatisticData
        return StatisticData(self)

    @property
    def runtime_stats(self):
        """The window's RuntimeStats (op tracer, compile tracker,
        memory samples) — see profiler/stats.py."""
        return self._runtime_stats

    def shape_churn_report(self, min_signatures: int = 8):
        return self._runtime_stats.ops.shape_churn_report(min_signatures)

    @property
    def profiler_result_dir(self):
        return self._log_dir


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready factory (reference profiler.py:227): writes a
    chrome://tracing / perfetto-loadable JSON of the HOST events
    (RecordEvent annotations, eager op-dispatch spans, memory counters,
    per-rank pid tagging) that load_profiler_result round-trips. The
    XPlane files jax.profiler writes under log_dir carry the DEVICE
    timeline for TensorBoard; TRACE_LOCATION.txt records where those
    landed, as before."""
    def handler(prof: Profiler):
        from . import chrome_trace
        os.makedirs(dir_name, exist_ok=True)
        marker = os.path.join(dir_name, "TRACE_LOCATION.txt")
        with open(marker, "w") as f:
            f.write(prof.profiler_result_dir + "\n")
        rank, _ = chrome_trace._rank_info()
        name = worker_name or f"rank{rank}"
        path = os.path.join(dir_name,
                            f"{name}_step{prof.step_num}.json")
        prof.last_trace_path = chrome_trace.export_chrome_trace(
            prof, path, worker_name=name)
    return handler


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    return export_chrome_tracing(dir_name, worker_name)


def load_profiler_result(filename: str):
    """Load an exported chrome-trace JSON back into its dict (reference:
    profiler.load_profiler_result over the protobuf dump; ours exports
    chrome-trace JSON, so that's what loads). Raises ValueError for a
    file that isn't a chrome trace."""
    import json
    with open(filename) as f:
        data = json.load(f)
    if not isinstance(data, dict) or "traceEvents" not in data:
        raise ValueError(
            f"{filename} is not a chrome-trace export (no traceEvents)")
    return data
