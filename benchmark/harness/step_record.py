"""The engine's own step record, read after a run: one row an
`Engine.step()` with its phases, lanes and programs
(`paddle_tpu/inference/tracing.py` `StepLog`; docs/OBSERVABILITY.md
"Step record"). Unlike a trace it covers the whole 45 s window, and it
is there whether the run was traced or not: the engine keeps it on
`time.perf_counter`, the clock of the runner's `ticks`, and
`tracing.step_logs()` still holds it after the runner has closed the
engine.

`for_ctx(ctx)` gives the rows of the measured window: those whose `t0_s`
lies between the start of the first and the end of the last of
`ctx.samples["ticks"]` (every serving runner keeps them through
`runners/serve.py`). A program without a step log (the parent of the PR
that added it) gives `None`, and every metric that reads this file is
then left out of the line. Once a run it prints one
`{"info": "step_record", ...}` line.
"""
from __future__ import annotations

import json
from typing import List, Optional

from . import stats

ENGINE_LABEL = "engine"       # a plain `Engine`'s label: the runners build one
WAITS = ("engine.decode.wait", "engine.prefill.wait")
KEPT = 3
_META = ("t0_s", "step", "slow")


def window_rows(log, ticks) -> List[dict]:
    """The rows of `log` that began inside the window `ticks` spans."""
    if not ticks:
        return []
    return log.rows(ticks[0][0], ticks[-1][0] + ticks[-1][1])


def host_ms(row: dict) -> float:
    """What the step cost the host: its wall time less the time it was
    blocked on the device."""
    return row["wall_ms"] - sum(row[w] for w in WAITS)


def backlog(row: dict) -> int:
    """Requests that held no decoding lane when the step began."""
    return row["waiting"] + row["prefilling"]


def _brief(row: dict, origin: float) -> dict:
    out = {"at_s": row["t0_s"] - origin}
    out.update((k, v) for k, v in row.items() if k not in _META and v)
    return out


def summary(rows: List[dict], ticks, slow: List[dict]) -> dict:
    """The info line: what the window's rows say in one object."""
    origin = ticks[0][0]
    end = ticks[-1][0] + ticks[-1][1]
    walls = [r["wall_ms"] for r in rows]
    phases = sorted(k for k in rows[0] if k.startswith("engine."))
    deepest = max(rows, key=backlog)

    def longest(key):
        return [_brief(r, origin)
                for r in sorted(rows, key=lambda r: -r[key])[:KEPT]]

    return {
        "rows": len(rows), "ticks": len(ticks),
        "rows_match_ticks": len(rows) == len(ticks),
        "wall_ms": {"p50": stats.median(walls),
                    "p99": stats.quantile(walls, 0.99), "max": max(walls)},
        "host_ms_p50": stats.median([host_ms(r) for r in rows]),
        "cpu_ms_p50": stats.median([r["cpu_ms"] for r in rows]),
        "gap_ms_p50": stats.median([r["gap_ms"] for r in rows]),
        "phase_ms_mean": {p: sum(r[p] for r in rows) / len(rows)
                          for p in phases},
        "other_ms_mean": sum(r["other_ms"] for r in rows) / len(rows),
        "starved_steps": sum(1 for r in rows if r["starved"]),
        "compiles": sum(r["compiles"] for r in rows),
        "longest_steps": longest("wall_ms"),
        "longest_gaps": longest("gap_ms"),
        "deepest_backlog": {"lanes": backlog(deepest),
                            "at_s": deepest["t0_s"] - origin},
        "slow": [dict(e, row=_brief(e["row"], origin),
                      before=[_brief(r, origin) for r in e["before"]],
                      after=[_brief(r, origin) for r in e["after"]])
                 for e in slow if origin <= e["row"]["t0_s"] <= end],
    }


def _read(ctx) -> Optional[List[dict]]:
    from paddle_tpu.inference import tracing
    logs = getattr(tracing, "step_logs", None)
    log = logs().get(ENGINE_LABEL) if logs is not None else None
    if log is None:
        return None
    ticks = ctx.samples["ticks"]
    rows = window_rows(log, ticks)
    if rows:
        print(json.dumps({"info": "step_record",
                          **summary(rows, ticks, log.slow())}), flush=True)
    return rows


def for_ctx(ctx) -> Optional[List[dict]]:
    """The window's rows of the run `ctx` belongs to, read once a run
    (kept in the run's `samples`); `None` when the program under test
    keeps no step log."""
    if "step_record" not in ctx.samples:
        ctx.samples["step_record"] = _read(ctx)
    return ctx.samples["step_record"]
