"""BENCHMARK.json against the contract's limits that a file can show:
keys, names, units, bounds, files that exist, `moves` that is reported
where the metric is, the run-time budget at 24 cells; and that a cell
appended as `benchmark/README.md` says passes every such check."""
import importlib
import inspect
import json
import re
import shutil

from benchmark.harness import load

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_KEYS = re.compile(r"(hidden|intermediate|latent|state|head)_?(size|dim)|"
                        r"_dim$|_rank$|experts_per_tok")
SERVING = {"serve_tokens_per_s", "tpot_p90_ms"}


def _bench(root=load.REPO_ROOT):
    return json.loads((root / "BENCHMARK.json").read_text())


def test_keys_names_units(root=load.REPO_ROOT):
    b = _bench(root)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert len((root / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in b[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        assert len({e["name"] for e in b[k]}) == len(b[k])
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["name"].endswith("_roofline") <= (m["unit"] == "%")
    for e in b["configs"] + b["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]


def test_files_cells_and_moves(root=load.REPO_ROOT):
    b = _bench(root)
    cells = {w["name"] for w in b["workloads"]}
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == \
        len(cells)
    assert {w["config"] for w in b["workloads"]} == \
        {c["name"] for c in b["configs"]}
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= \
        max(1, len(cells) // 4)
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)
    for c in b["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        body = json.loads((root / c["file"]).read_text())
        assert set(c["reduced"]) == set(body["reduced"])
        assert not [k for k in c["reduced"] if WIDTH_KEYS.search(k)]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "workloads" not in e2e["setup_s"]
    for name in cells:
        cell = load.load_cell(name, root)
        assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
    for m in b["per_layer"]:
        where = m.get("workloads", cells)
        assert set(where) <= cells
        moved = e2e[m["moves"]]
        assert set(where) <= set(moved.get("workloads", cells)), m["name"]


def test_a_full_check_fits_with_24_cells(root=load.REPO_ROOT):
    rs = _bench(root)["run_seconds"]
    assert 1 <= rs <= 51 and rs == int(rs)
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def _cases(fn):
    """The keyword arguments of each case of `fn`'s one `parametrize`."""
    marks = [m.args for m in getattr(fn, "pytestmark", [])
             if m.name == "parametrize"]
    if not marks:
        return [{}]
    (name, values), = marks
    return [{name: v} for v in values]


def test_an_appended_cell_breaks_no_cell_test(tmp_path):
    """A serving cell added by new files alone: a configuration file and a
    metric file, their `configs`, `workloads` and `per_layer` entries
    appended, and the cell's name at the end of the list of every serving
    metric. Every test here that takes a `root` (each one that reads
    `BENCHMARK.json`'s lists) passes on that copy as on the real file."""
    root = tmp_path / "repo"
    shutil.copytree(load.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = _bench()
    config = next(c for c in b["configs"]
                  if c["name"] == "mistral-7b-serve-8l")
    new = dict(config, name="appended-serve",
               file="benchmark/configs/appended-serve.json")
    shutil.copy(load.REPO_ROOT / config["file"], root / new["file"])
    b["configs"].append(new)
    b["workloads"].append(dict(name="serve-appended-chat48",
                               config=new["name"], traffic="chat48",
                               chips=1, why="test"))
    for m in b["end_to_end"] + b["per_layer"]:
        if {m["name"], m.get("moves")} & SERVING:
            m["workloads"].append("serve-appended-chat48")
    (root / "benchmark/metrics/serve.appended_share.py").write_text(
        'NAME, UNIT, BETTER, SOURCE = "serve.appended_share", "%", "higher", '
        '"program_counter"\n'
        'LAYER, MOVES = "serving scheduler", "serve_tokens_per_s"\n'
        "def compute(ctx):\n    return None\n")
    b["per_layer"].append(dict(
        name="serve.appended_share", unit="%", better="higher",
        source="program_counter", layer="serving scheduler",
        moves="serve_tokens_per_s", workloads=["serve-appended-chat48"]))
    (root / "BENCHMARK.json").write_text(json.dumps(b, indent=1))
    cell = load.load_cell("serve-appended-chat48", root)
    assert "serve.host_ms_per_step" in cell.per_layer
    assert set(cell.end_to_end) == SERVING | {"setup_s"}

    ran = set()
    for path in sorted(load.BENCH_DIR.glob("tests/test_*.py")):
        module = importlib.import_module(f"benchmark.tests.{path.stem}")
        for name, fn in vars(module).items():
            if name.startswith("test_") and callable(fn) and \
                    "root" in inspect.signature(fn).parameters:
                for kwargs in _cases(fn):
                    fn(root=root, **kwargs)
                ran.add(f"{path.stem}.{name}")
    assert {"test_ssm.test_the_cells_entries_in_benchmark_json",
            "test_step_record."
            "test_benchmark_json_lists_the_metric_as_its_file_has_it",
            "test_ssm.test_the_cell_is_the_one_the_issue_names",
            "test_hybrid.test_the_cell_is_the_one_the_issue_names",
            "test_latent.test_the_cell_is_the_one_the_issue_names",
            "test_window.test_the_cell_is_the_one_the_issue_names",
            "test_benchmark_json.test_files_cells_and_moves",
            "test_loader.test_the_real_cells_load_and_name_files_that_exist",
            } <= ran
