"""Chrome-trace (trace_event) JSON export of host-side profiler events.

Reference: the C++ ChromeTracingLogger
(paddle/fluid/platform/profiler/dump/serialization_logger.cc analog)
that export_chrome_tracing drives. TPU-native split: DEVICE timelines
are jax.profiler's XPlane dumps (TensorBoard/perfetto); this module
covers the HOST side — RecordEvent annotations, eager op dispatch
spans, and memory counter tracks — as plain chrome://tracing /
perfetto-loadable JSON that load_profiler_result round-trips.

pid tagging: one process per rank. When paddle_tpu.distributed is
initialized the rank/world size come from there, so merged multi-host
traces interleave cleanly; single-process falls back to rank 0 of 1.
"""
from __future__ import annotations

import json
from typing import List, Optional


def _rank_info():
    """(rank, world_size) — sourced from paddle_tpu.distributed when it
    is importable/initialized, else the single-process fallback."""
    try:
        from ..distributed import env
        label = env.process_label()
        return int(label["rank"]), int(label["world_size"])
    except Exception:  # noqa: BLE001 — distributed stack unavailable
        return 0, 1


# thread lanes within a rank's process row
TID_USER = 0      # RecordEvent annotations
TID_DISPATCH = 1  # eager op dispatch spans


def build_trace(profiler, worker_name: Optional[str] = None) -> dict:
    """Chrome trace dict for one Profiler's recorded host events."""
    rank, world = _rank_info()
    pid = rank
    name = worker_name or f"rank{rank}"

    store_events = list(getattr(profiler._store, "events", []))
    rt = getattr(profiler, "_runtime_stats", None)
    spans = list(rt.ops.spans) if rt is not None else []
    mem = list(rt.memory.samples) if rt is not None else []

    # one common origin so user events, op spans, and memory counters
    # line up; chrome-trace wants microseconds
    starts = ([s for _, s, _, _ in store_events] + [s for _, s, _ in spans]
              + [m["t"] for m in mem if "t" in m])
    t0 = min(starts) if starts else 0.0

    def us(t):
        return round((t - t0) * 1e6, 3)

    events: List[dict] = [
        {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
         "args": {"name": f"{name} (host, {world} rank"
                          f"{'s' if world != 1 else ''})"}},
        {"ph": "M", "name": "process_sort_index", "pid": pid, "tid": 0,
         "args": {"sort_index": rank}},
        {"ph": "M", "name": "thread_name", "pid": pid, "tid": TID_USER,
         "args": {"name": "user annotations"}},
        {"ph": "M", "name": "thread_name", "pid": pid,
         "tid": TID_DISPATCH, "args": {"name": "op dispatch"}},
    ]
    for ev_name, s, e, ev_args in store_events:
        ev = {"ph": "X", "cat": "user", "name": ev_name,
              "pid": pid, "tid": TID_USER, "ts": us(s),
              "dur": round((e - s) * 1e6, 3)}
        if ev_args:
            ev["args"] = ev_args
        events.append(ev)
    for op_name, s, e in spans:
        events.append({"ph": "X", "cat": "op", "name": op_name,
                       "pid": pid, "tid": TID_DISPATCH, "ts": us(s),
                       "dur": round((e - s) * 1e6, 3)})
    for m in mem:
        if "t" not in m:
            continue
        events.append({"ph": "C", "cat": "memory",
                       "name": f"memory ({m.get('source', '?')})",
                       "pid": pid, "tid": 0, "ts": us(m["t"]),
                       "args": {"bytes_in_use": m["bytes_in_use"]}})

    meta = {"rank": rank, "world_size": world,
            "step_num": getattr(profiler, "step_num", 0),
            "tool": "paddle_tpu.profiler"}
    if rt is not None:
        meta["xla_compiles"] = rt.compiles.compiles
        meta["xla_compile_secs"] = round(rt.compiles.compile_secs, 4)
        if rt.ops.timeline_dropped:
            meta["op_spans_dropped"] = rt.ops.timeline_dropped
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "metadata": meta}


def export_chrome_trace(profiler, path: str,
                        worker_name: Optional[str] = None) -> str:
    trace = build_trace(profiler, worker_name)
    with open(path, "w") as f:
        json.dump(trace, f)
    return path
