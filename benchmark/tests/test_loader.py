"""A later PR's files are found by name: a new configuration, traffic mix
and per-layer metric dropped into a copy of the tree, with entries in
BENCHMARK.json, reach the runner and the result line with no edit to
run.py or to any file that was there."""
import dataclasses
import json
import shutil

import pytest

from benchmark import run as runpy
from benchmark.harness import load
from benchmark.tests import tiny


def test_the_real_cells_load_and_name_files_that_exist(root=load.REPO_ROOT):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for entry in bench["workloads"]:
        cell = load.load_cell(entry["name"], root)
        assert hasattr(load.load_runner(cell.traffic["runner"]), "run")
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) > 1
        for name in cell.per_layer:
            metric = load.load_metric(name, root)
            listed = next(m for m in bench["per_layer"] if m["name"] == name)
            assert (metric.NAME, metric.UNIT, metric.LAYER, metric.MOVES,
                    metric.SOURCE, metric.BETTER) == tuple(
                listed[k] for k in ("name", "unit", "layer", "moves",
                                    "source", "better"))
            assert listed["moves"] in cell.end_to_end


def test_run_py_names_no_cell_config_runner_or_metric(root=load.REPO_ROOT):
    text = (load.BENCH_DIR / "run.py").read_text()
    bench = json.loads((root / "BENCHMARK.json").read_text())
    names = [e["name"] for k in ("workloads", "configs", "per_layer")
             for e in bench[k]] + [w["traffic"] for w in bench["workloads"]]
    names += [p.stem for p in (load.BENCH_DIR / "runners").glob("*.py")]
    assert not [n for n in names if n in text]


def test_unknown_names_are_errors():
    with pytest.raises(load.NotFound):
        load.load_cell("no-such-cell")
    with pytest.raises(load.NotFound):
        load.load_metric("no.such_metric")


def test_new_files_are_found_without_touching_run_py(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(load.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    tiny.tiny_tree(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    serve = "tiny-serve"
    old = next(w for w in bench["workloads"] if w["name"] == serve)
    # the later PR: one config, one traffic mix, one metric, three entries
    (root / "benchmark/configs/new-config.json").write_text(
        json.dumps(dict(tiny.SERVE_CFG, num_hidden_layers=1)))
    (root / "benchmark/traffic/new-mix.json").write_text(json.dumps(dict(
        tiny.SERVE_TRAFFIC, arrival=dict(kind="poisson", rate_per_s=200.0))))
    (root / "benchmark/metrics/serve.lateness_ms_p50.py").write_text(
        "from benchmark.harness import stats\n"
        "def compute(ctx):\n"
        "    return stats.median(ctx.samples['lateness_ms'])\n")
    bench["configs"].append(dict(name="new-config", source="test",
                                 file="benchmark/configs/new-config.json",
                                 reduced=[], why="test"))
    bench["workloads"].append(dict(old, name="new-cell", config="new-config",
                                   traffic="new-mix"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if serve in m.get("workloads", []):
            m["workloads"].append("new-cell")
    bench["per_layer"].append(dict(
        name="serve.lateness_ms_p50", unit="ms", better="lower",
        source="host_clock", layer="load generator", moves="tpot_p90_ms",
        workloads=["new-cell"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = load.load_cell("new-cell", root)
    assert cell.config["num_hidden_layers"] == 1
    assert "serve.lateness_ms_p50" in cell.per_layer
    job, measured = tiny.run_tiny(root, "new-cell", seconds=1.0)
    assert measured.correct, measured.checks
    assert measured.attempted > 0 and measured.failed == 0
    line = runpy.result_line(
        dataclasses.replace(job, trace=True,
                            device=dict(job.device, kind="TPU v5 lite")),
        measured, repo_root=root)
    assert line["metrics"]["serve.lateness_ms_p50"]["unit"] == "ms"
    # metrics that read a device trace found none and were left out
    assert "serve.device_idle_share" not in line["metrics"]
    assert "breakdown" not in line
