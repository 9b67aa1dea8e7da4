"""Find a cell's files by the names `BENCHMARK.json` gives.

Nothing here lists a cell, a configuration, a traffic mix, a runner or a
metric: each is a file of its own under `benchmark/`, found by name.

    cell     BENCHMARK.json "workloads" entry   -> config, traffic, chips
    config   benchmark/configs/<config>.json    -> model + engine/optimizer
    traffic  benchmark/traffic/<traffic>.json   -> "runner" + its parameters
    runner   benchmark/runners/<runner>.py      -> run(job) -> Measured
    metric   benchmark/metrics/<metric>.py      -> compute(ctx) -> number|None
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from dataclasses import dataclass

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
REPO_ROOT = BENCH_DIR.parent


class NotFound(LookupError):
    """A name `BENCHMARK.json` or a data file gives has no file."""


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: tuple     # names this cell reports with --trace 0
    per_layer: tuple      # names this cell reports with --trace 1
    units: dict           # metric name -> unit, as BENCHMARK.json has it


def _read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise NotFound(f"{path} does not exist")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _import_file(path: pathlib.Path, modname: str):
    if not path.is_file():
        raise NotFound(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def _reported(entries, cell_name):
    return tuple(m["name"] for m in entries
                 if "workloads" not in m or cell_name in m["workloads"])


def load_cell(name: str, repo_root: pathlib.Path = REPO_ROOT) -> Cell:
    """The cell `name` of `<repo_root>/BENCHMARK.json` with its files."""
    repo_root = pathlib.Path(repo_root)
    bench = _read_json(repo_root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise NotFound(f"BENCHMARK.json has no workload named {name!r}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    bdir = repo_root / "benchmark"
    both = bench["end_to_end"] + bench["per_layer"]
    return Cell(
        name=name, chips=int(entry["chips"]),
        config_name=entry["config"], traffic_name=entry["traffic"],
        config=_read_json(repo_root / cfg_entry["file"]),
        traffic=_read_json(bdir / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=_reported(bench["end_to_end"], name),
        per_layer=_reported(bench["per_layer"], name),
        units={m["name"]: m["unit"] for m in both})


def load_runner(name: str, repo_root: pathlib.Path = REPO_ROOT):
    path = pathlib.Path(repo_root) / "benchmark" / "runners" / f"{name}.py"
    return _import_file(path, f"benchmark_runner_{name}")


def load_metric(name: str, repo_root: pathlib.Path = REPO_ROOT):
    path = pathlib.Path(repo_root) / "benchmark" / "metrics" / f"{name}.py"
    return _import_file(path, "benchmark_metric_" + name.replace(".", "_")
                        .replace("-", "_"))
