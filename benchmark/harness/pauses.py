"""Where a run lost its seconds: the longest `step()` calls of the window
and the longest gaps between them, beside what the process can see of
its own pauses while they lasted. One run in ten of a serving cell holds
a pause of 2-5 s (PERF.md section 7); a trace cannot be asked for after
the fact, so a runner that wants to know keeps this on in every run.

`Watch` costs one thread that wakes ten times a second to read the
process's CPU time and, where `/proc/<pid>/task/<tid>/schedstat` exists
(the chip's sandbox has none), the driving thread's nanoseconds on a CPU
and runnable but waiting for one; and a `gc` callback that keeps
collections of 10 ms or more. `report` says of the longest step and the
longest gap what the process did meanwhile:

* the watch woke on time and the process used little CPU: the driving
  thread was ASLEEP, waiting for the device or the runtime;
* the watch woke late (`longest_watch_gap_ms`) and the process's CPU time
  grew with the clock: host code held the interpreter (a collection, a
  long call);
* the watch woke late and the CPU time stood still: the process did not
  run at all (its machine gave the cores to someone else).
"""
from __future__ import annotations

import gc
import statistics
import threading
import time

PERIOD_S = 0.1
SLOW_GC_MS = 10.0
KEPT = 3


def _sched(tid: int):
    """(ms on a CPU, ms runnable without one) of a thread, or ()."""
    try:
        with open(f"/proc/self/task/{tid}/schedstat") as f:
            on_cpu, waiting = f.read().split()[:2]
        return int(on_cpu) / 1e6, int(waiting) / 1e6
    except (OSError, ValueError):
        return ()


class Watch:
    """Samples of the thread that enters it, until it leaves."""

    def __enter__(self):
        self.tid = threading.get_native_id()
        self.samples = []             # (perf_counter, process_time[, on_cpu_ms, waiting_ms])
        self.collections = []         # (perf_counter at end, generation, ms)
        self._gc_t0 = 0.0
        self._stop = threading.Event()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._sample, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._gc_t0 = now
        elif (now - self._gc_t0) * 1e3 >= SLOW_GC_MS:
            self.collections.append(
                (now, int(info["generation"]), (now - self._gc_t0) * 1e3))

    def _sample(self):
        # one sample at once, one every period, one at the stop
        stopped = False
        while not stopped:
            self.samples.append((time.perf_counter(), time.process_time())
                                + _sched(self.tid))
            stopped = self._stop.wait(PERIOD_S)
        self.samples.append((time.perf_counter(), time.process_time())
                            + _sched(self.tid))

    def during(self, t0: float, t1: float) -> dict:
        """The process between the last sample at or before t0 and the
        first at or after t1 (so up to two periods more than t1 - t0)."""
        before = [s for s in self.samples if s[0] <= t0]
        after = [s for s in self.samples if s[0] >= t1]
        out = {"collections": [
            {"generation": g, "ms": ms} for at, g, ms in self.collections
            if t0 <= at <= t1 + PERIOD_S]}
        if before and after:
            a, b = before[-1], after[0]
            inside = [s[0] for s in self.samples if a[0] <= s[0] <= b[0]]
            out.update(
                sampled_ms=(b[0] - a[0]) * 1e3,
                process_cpu_ms=(b[1] - a[1]) * 1e3,
                longest_watch_gap_ms=max(
                    y - x for x, y in zip(inside, inside[1:])) * 1e3)
            if len(a) == 4 and len(b) == 4:
                out.update(on_cpu_ms=b[2] - a[2],
                           runnable_waiting_ms=b[3] - a[3])
        return out


def _top(rows, origin):
    rows = sorted(rows, key=lambda r: -r[1])[:KEPT]
    return [{"at_s": t0 - origin, "ms": s * 1e3, "active": a, "waiting": w,
             "prefilling": p} for t0, s, a, w, p in rows]


def report(ticks, watch: Watch) -> dict:
    """`ticks`: the window's (start, seconds, active, waiting,
    prefilling) of every `step()`, as `runners/serve.py` keeps them."""
    if len(ticks) < 2:
        return {"steps": len(ticks)}
    origin = ticks[0][0]
    steps = [t[1] * 1e3 for t in ticks]
    # the gap BEFORE a step: from the end of the one before it
    gaps = [(b[0], b[0] - (a[0] + a[1])) + tuple(b[2:])
            for a, b in zip(ticks, ticks[1:])]
    worst_step = max(ticks, key=lambda t: t[1])
    worst_gap = max(gaps, key=lambda g: g[1])
    return {
        "steps": len(ticks),
        "step_ms_p50": statistics.median(steps),
        "step_ms_p99": statistics.quantiles(steps, n=100)[98],
        "gap_ms_p50": statistics.median(g[1] * 1e3 for g in gaps),
        "gap_ms_total": sum(g[1] for g in gaps) * 1e3,
        "longest_steps": _top(ticks, origin),
        "longest_gaps": _top(gaps, origin),
        "during_longest_step": watch.during(
            worst_step[0], worst_step[0] + worst_step[1]),
        "during_longest_gap": watch.during(
            worst_gap[0] - worst_gap[1], worst_gap[0]),
        "slow_collections": len(watch.collections),
    }
