"""`harness/step_record.py` and the four metrics on it: the window's
bounds, a program without a step log, the info line, rows with a known
answer, and BENCHMARK.json's entries against their files."""
import json
import types

import pytest

from benchmark.harness import load, step_record
from benchmark.harness.load import load_metric

METRICS = ("serve.host_ms_per_step", "serve.step_ms_p99",
           "serve.slow_step_ms", "serve.backlog_lanes_p90")
SERVE_CELLS = ["serve-mistral7b-8l-chat48", "serve-dots3-5l-notes48",
               "serve-solar2-4l-chat96", "serve-kexaone-5l-mixed128"]
PHASES = ("engine.decode.dispatch", "engine.decode.wait",
          "engine.prefill.wait", "engine.harvest", "engine.add_request")


def row(step, t0_s, wall_ms, wait_ms=0.0, waiting=0, prefilling=0, slow=0,
        **more):
    out = dict.fromkeys(PHASES, 0.0)
    out.update(step=step, t0_s=t0_s, wall_ms=wall_ms, gap_ms=0.1,
               cpu_ms=min(wall_ms, 2.0), other_ms=0.05, decoding=48,
               waiting=waiting, prefilling=prefilling, starved=0,
               compiles=0, slow=slow, variant="greedy")
    out["engine.decode.wait"] = wait_ms
    out.update(more)
    return out


class FakeLog:
    def __init__(self, rows, slow=()):
        self._rows, self._slow = rows, list(slow)

    def rows(self, t0=None, t1=None):
        return [r for r in self._rows
                if (t0 is None or r["t0_s"] >= t0)
                and (t1 is None or r["t0_s"] <= t1)]

    def slow(self):
        return self._slow


def steady(n, t0=100.0, wall_ms=15.0, wait_ms=12.0):
    """`n` back-to-back steps from `t0`, and the runner's ticks of the
    same steps (each begun 5 us before its row)."""
    rows = [row(k, t0 + k * wall_ms / 1e3, wall_ms - 0.02, wait_ms)
            for k in range(n)]
    ticks = [(r["t0_s"] - 5e-6, wall_ms / 1e3, 48, 0, 0) for r in rows]
    return rows, ticks


def ctx_for(monkeypatch, log, ticks):
    from paddle_tpu.inference import tracing
    if log is None:
        monkeypatch.delattr(tracing, "step_logs")
    else:
        monkeypatch.setattr(tracing, "step_logs", lambda: {"engine": log})
    return types.SimpleNamespace(samples={"ticks": ticks}, trace=None)


def test_the_window_is_the_ticks_first_start_to_last_end(monkeypatch):
    rows, ticks = steady(100)
    ctx = ctx_for(monkeypatch, FakeLog(rows), ticks[10:60])
    got = step_record.for_ctx(ctx)
    assert [r["step"] for r in got] == list(range(10, 60))
    assert ctx.samples["step_record"] is got        # read once a run
    assert step_record.window_rows(FakeLog(rows), []) == []


def test_a_program_without_a_step_log_leaves_every_metric_out(
        monkeypatch, capsys):
    _, ticks = steady(20)
    ctx = ctx_for(monkeypatch, None, ticks)
    assert step_record.for_ctx(ctx) is None
    assert [load_metric(m).compute(ctx) for m in METRICS] == [None] * 4
    assert capsys.readouterr().out == ""


def test_the_info_line(monkeypatch, capsys):
    rows, ticks = steady(200)
    rows[150].update(wall_ms=400.0, slow=1, waiting=7, prefilling=2)
    rows[150]["engine.decode.wait"] = 390.0
    rows[30]["gap_ms"] = 9.0
    slow = [{"row": rows[150], "before": rows[142:150],
             "after": rows[151:155], "collections": [], "memory": {}},
            {"row": row(9, 1.0, 999.0, slow=1), "before": [], "after": [],
             "collections": [], "memory": {}}]      # before the window
    ctx = ctx_for(monkeypatch, FakeLog(rows, slow), ticks)
    for m in METRICS:
        load_metric(m).compute(ctx)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(lines) == 1                          # once a run
    info, = lines
    assert info["info"] == "step_record"
    assert set(info) >= {
        "rows", "ticks", "rows_match_ticks", "wall_ms", "host_ms_p50",
        "phase_ms_mean", "other_ms_mean", "longest_steps", "longest_gaps",
        "deepest_backlog", "slow"}
    assert info["rows"] == info["ticks"] == 200 and info["rows_match_ticks"]
    assert info["wall_ms"]["max"] == 400.0
    assert info["wall_ms"]["p50"] == pytest.approx(14.98)
    assert set(info["phase_ms_mean"]) == set(PHASES)
    assert len(info["longest_steps"]) == len(info["longest_gaps"]) == 3
    worst = info["longest_steps"][0]
    assert worst["wall_ms"] == 400.0 and worst["waiting"] == 7
    assert worst["engine.decode.wait"] == 390.0
    assert worst["at_s"] == pytest.approx(150 * 0.015 + 5e-6)
    assert info["longest_gaps"][0]["gap_ms"] == 9.0
    assert info["deepest_backlog"]["lanes"] == 9
    assert [e["row"]["wall_ms"] for e in info["slow"]] == [400.0]
    assert len(info["slow"][0]["before"]) == 8


def test_a_four_second_step_among_three_thousand(monkeypatch):
    """It moves `serve.slow_step_ms` and `serve.step_ms_p99`'s
    neighbourhood, not `serve.host_ms_per_step`."""
    rows, ticks = steady(3000)
    clean = ctx_for(monkeypatch, FakeLog(rows), ticks)
    values = {m: load_metric(m).compute(clean) for m in METRICS}
    assert values["serve.host_ms_per_step"] == pytest.approx(2.98)
    assert values["serve.step_ms_p99"] == pytest.approx(14.98)
    assert values["serve.slow_step_ms"] == 0.0
    assert values["serve.backlog_lanes_p90"] == 0.0

    stalled = [dict(r) for r in rows]
    stalled[1500].update(wall_ms=4000.0, slow=1)
    stalled[1500]["engine.decode.wait"] = 3997.0
    # the cohort it parked: forty steps with a queue behind them
    for r in stalled[1501:1541]:
        r.update(waiting=20, prefilling=4, wall_ms=29.98)
    ctx = ctx_for(monkeypatch, FakeLog(stalled), ticks)
    values = {m: load_metric(m).compute(ctx) for m in METRICS}
    assert values["serve.slow_step_ms"] == 4000.0
    assert values["serve.step_ms_p99"] == pytest.approx(29.98)
    assert values["serve.host_ms_per_step"] == pytest.approx(2.98)
    assert values["serve.backlog_lanes_p90"] == 0.0     # a parked cohort
    # a standing backlog reads in the p90
    for r in stalled[:600]:
        r.update(waiting=11, prefilling=1)
    ctx = ctx_for(monkeypatch, FakeLog(stalled), ticks)
    assert load_metric("serve.backlog_lanes_p90").compute(ctx) == 12.0


@pytest.mark.parametrize("name", METRICS)
def test_benchmark_json_lists_the_metric_as_its_file_has_it(name):
    bench = json.loads((load.REPO_ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    metric = load_metric(name)
    assert entry == {
        "name": metric.NAME, "unit": metric.UNIT, "better": metric.BETTER,
        "source": metric.SOURCE, "layer": metric.LAYER,
        "moves": metric.MOVES, "workloads": SERVE_CELLS}
    assert (entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("lower", "program_span", "serving scheduler",
                                "serve_tokens_per_s")
    assert bench["per_layer"].index(entry) >= 38        # appended
    text = (load.BENCH_DIR / "metrics" / f"{name}.py").read_text()
    assert "step_record" in text and "program_" + "spans" not in text
