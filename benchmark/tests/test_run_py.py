"""run.py as a process: it refuses to print a result on a CPU, and the
two runners go end to end at a tiny size."""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmark import run as runpy
from benchmark.harness import load
from benchmark.tests import tiny


REAL_CELLS = [w["name"] for w in json.loads(
    (load.REPO_ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", REAL_CELLS)
def test_no_result_line_without_a_tpu(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=load.REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "NoAccelerator" in proc.stderr


@pytest.mark.parametrize("runner", ["train", "serve"])
def test_tiny_cell_end_to_end(runner, tmp_path):
    root = tiny.tiny_tree(tmp_path)
    job, measured = tiny.run_tiny(root, f"tiny-{runner}", seconds=1.0,
                                  seed=2 ** 31 + 12345)
    assert measured.correct, measured.checks
    line = runpy.result_line(job, measured)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    json.dumps(line)
    traced = runpy.result_line(
        dataclasses.replace(job, trace=True,
                            device=dict(job.device, kind="TPU v5 lite")),
        measured)
    assert traced["metrics"] and not set(traced["metrics"]) & set(
        line["metrics"])
