"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` on the machine it is started on and
prints, as the last line of stdout, one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` and, when traced, `breakdown`.
`--trace 0` reports the cell's end-to-end metrics with the profiler off;
`--trace 1` reports its per-layer metrics and profiles a stretch of the
same steady loop after the measured window.

This file names no cell, configuration, traffic mix, runner or metric:
`benchmark/harness/load.py` finds each by the name `BENCHMARK.json` gives
(see benchmark/README.md). With no TPU behind JAX, or fewer chips than
the cell asks for, it exits nonzero and prints no result line.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from benchmark.harness import device, load  # noqa: E402
from benchmark.harness.job import Job, MetricContext, say  # noqa: E402

SCRATCH = "benchmark_out"       # inside the checkout, git-ignored


def result_line(job: Job, measured, repo_root=REPO_ROOT) -> dict:
    """The result object for one finished run."""
    cell = job.cell
    metrics = {}
    if job.trace:
        ctx = MetricContext(cell=cell, measured=measured, device=job.device,
                            peak=device.peak(job.device["kind"]))
        for name in cell.per_layer:
            value = load.load_metric(name, repo_root).compute(ctx)
            if value is not None:
                metrics[name] = value
    else:
        for name in cell.end_to_end:
            if name in measured.end_to_end:
                metrics[name] = measured.end_to_end[name]
            else:       # e.g. a tail the window's requests do not support
                measured.checks[f"reports_{name}"] = "not measured"
    dev = dict(job.device)
    dev["memory_peak_bytes"] = device.memory_peak_bytes(cell.chips)
    line = {
        "correct": measured.correct, "attempted": measured.attempted,
        "failed": measured.failed,
        "metrics": {k: {"value": float(v), "unit": cell.units[k]}
                    for k, v in metrics.items()},
        "device": dev,
        "checks": measured.checks,
    }
    if job.trace and measured.trace is not None:
        dev["busy_s"] = measured.trace.busy_s
        dev["window_s"] = measured.trace.window_s
        line["breakdown"] = measured.trace.breakdown()
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load.load_cell(args.workload)
    dev = device.require(cell.chips)      # raises with no TPU: no result line
    say("start", cell=cell.name, config=cell.config_name,
        traffic=cell.traffic_name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=dev,
        compile_cache=device.enable_compile_cache())

    trace_dir = REPO_ROOT / SCRATCH / f"trace-{cell.name}"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    job = Job(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), trace_dir=str(trace_dir),
              process_start=PROCESS_START, device=dev)
    measured = load.load_runner(cell.traffic["runner"]).run(job)
    print(json.dumps(result_line(job, measured)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
