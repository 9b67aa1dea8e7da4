"""What `run.py` hands a runner, what a runner hands back, and what a
per-layer metric's `compute(ctx)` may read."""
from __future__ import annotations

import contextlib
import json
import os
import time
from dataclasses import dataclass, field
from typing import Optional

from .load import Cell
from .trace_reduce import WINDOW_SPAN, Reduced, reduce_dir


def say(what: str, **facts) -> None:
    """An information line on stdout, before the result line."""
    print(json.dumps({"info": what, **facts}), flush=True)


@dataclass
class Job:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    trace_dir: str            # where a traced run may write; inside the checkout
    process_start: float      # time.perf_counter() at the top of run.py
    device: dict              # platform, kind, count


@dataclass
class Measured:
    """A runner's account of one run. `end_to_end` holds every
    end-to-end value the runner can compute (run.py prints those the cell
    lists); `samples` is whatever its per-layer metrics read."""
    checks: dict                          # name -> True | explanation
    attempted: int
    failed: int
    end_to_end: dict
    samples: dict = field(default_factory=dict)
    trace: Optional[Reduced] = None

    @property
    def correct(self) -> bool:
        return all(v is True for v in self.checks.values())


@dataclass
class MetricContext:
    cell: Cell
    measured: Measured
    device: dict
    peak: dict

    @property
    def samples(self) -> dict:
        return self.measured.samples

    @property
    def trace(self) -> Optional[Reduced]:
        return self.measured.trace


def span(name: str):
    """A host span on the profiler's clock (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def traced_window(job: Job, out: dict):
    """Profile what runs inside; leaves the reduced trace in
    out["trace"]. The python tracer stays off: it slows the host loop
    whose gaps the trace is there to show."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(job.trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        say("trace_written", seconds=time.perf_counter() - t0)
    # the events the reduction read stay beside the trace, for a reader
    out["trace"] = reduce_dir(job.trace_dir, job.cell.chips,
                              keep_events=os.path.join(job.trace_dir,
                                                       "events.json"))
