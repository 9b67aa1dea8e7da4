"""BENCHMARK.json against the contract's limits that a file can show:
keys, names, units, bounds, files that exist, `moves` that is reported
where the metric is, the run-time budget at 24 cells."""
import json
import re

from benchmark.harness import load

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_KEYS = re.compile(r"(hidden|intermediate|latent|state|head)_?(size|dim)|"
                        r"_dim$|_rank$|experts_per_tok")


def _bench():
    return json.loads((load.REPO_ROOT / "BENCHMARK.json").read_text())


def test_keys_names_units():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert len((load.REPO_ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
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


def test_files_cells_and_moves():
    b = _bench()
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
        body = json.loads((load.REPO_ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(body["reduced"])
        assert not [k for k in c["reduced"] if WIDTH_KEYS.search(k)]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "workloads" not in e2e["setup_s"]
    for name in cells:
        cell = load.load_cell(name)
        assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
    for m in b["per_layer"]:
        where = m.get("workloads", cells)
        assert set(where) <= cells
        moved = e2e[m["moves"]]
        assert set(where) <= set(moved.get("workloads", cells)), m["name"]


def test_a_full_check_fits_with_24_cells():
    rs = _bench()["run_seconds"]
    assert 1 <= rs <= 51 and rs == int(rs)
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
