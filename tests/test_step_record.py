"""The step record (`Engine.step_log`, `tracing.StepLog`): one row a
`step()` with its phases timed at the spans' own sites, lanes and
programs, kept in a ring whether a profiler runs or not; slow steps
kept whole; the host/device gauges fall out of the row
(docs/OBSERVABILITY.md "Step record")."""
import statistics
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference import tracing
from paddle_tpu.inference.engine import Engine, SamplingParams
from paddle_tpu.profiler import Profiler
from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

KINDS = ("plain", "chunked", "speculative")
IN_STEP = [n for n in tracing.STEP_SPANS if n != tracing.GAP_SPAN]


def _net(seed=0, layers=2):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab=64, hidden=64, layers=layers, heads=4)
    cfg.use_flash_attention = False
    net = LlamaForCausalLM(cfg)
    net.eval()
    return net


def _engine(kind="plain", **kw):
    opts = dict(max_slots=3, page_size=8, pool_pages=96, max_context=128,
                prefill_bucket=16)
    if kind == "chunked":
        opts["max_prefill_tokens_per_step"] = 16
    if kind == "speculative":
        opts.update(draft_model=_net(seed=1, layers=1), spec_k=2)
    opts.update(kw)
    return Engine(_net(), **opts)


def _prompt(n, lo=1):
    return (np.arange(lo, lo + n) % 60 + 1).astype(np.int64)


def _drain(eng):
    outs = []
    while not eng.idle:
        outs.extend(eng.step())
    return outs


@pytest.fixture(scope="module", params=KINDS)
def recorded(request):
    """A run of each kind of engine under a `Profiler(timer_only=True)`:
    (the host store's events, the log's rows, per step the two gauges
    as read after it)."""
    eng = _engine(request.param)
    gauges = []
    with Profiler(timer_only=True) as prof:
        eng.add_request(_prompt(40), SamplingParams(max_new_tokens=8))
        eng.add_request(_prompt(9, 3), SamplingParams(max_new_tokens=6))
        for k in range(200):
            if k == 4:
                eng.add_request(_prompt(21, 5),
                                SamplingParams(max_new_tokens=5))
            if eng.idle:
                break
            eng.step()
            gauges.append((monitor.gauge("serving.host_ms_per_tick").get(),
                           monitor.gauge("serving.device_ms_per_tick").get()))
    events = list(prof._store.events)
    rows = eng.step_log.rows()
    eng.close()
    return events, rows, gauges


def _store_ms_by_step(events):
    """Per `engine.step` event of the host store: its `step` argument,
    and per span name the milliseconds of the store's events inside it
    (for `engine.add_request`: in the gap before it)."""
    steps = sorted((e for e in events if e[0] == "engine.step"),
                   key=lambda e: e[1])
    out, prev_end = [], float("-inf")
    for _, s0, s1, args in steps:
        ms = dict.fromkeys(tracing.STEP_SPANS, 0.0)
        for name, t0, t1, _ in events:
            if name == tracing.GAP_SPAN:
                inside = prev_end <= t0 and t1 <= s0
            else:
                inside = name in ms and s0 <= t0 and t1 <= s1
            if inside:
                ms[name] += (t1 - t0) * 1e3
        out.append((args["step"], (s1 - s0) * 1e3, ms))
        prev_end = s1
    return out


def test_one_row_a_step_with_consecutive_step_numbers(recorded):
    events, rows, gauges = recorded
    assert len(rows) == len(gauges) == sum(
        1 for e in events if e[0] == "engine.step") > 8
    assert [r["step"] for r in rows] == list(range(len(rows)))
    assert set(rows[0]) == set(tracing.STEP_FIELDS)
    assert all(a["t0_s"] + a["wall_ms"] / 1e3 <= b["t0_s"]
               for a, b in zip(rows, rows[1:]))
    # the gap is the caller's time: from one return to the next entry
    for a, b in zip(rows, rows[1:]):
        assert b["gap_ms"] == pytest.approx(
            (b["t0_s"] - a["t0_s"]) * 1e3 - a["wall_ms"], abs=1e-6)


def test_every_phase_field_is_the_span_the_profiler_stored(recorded):
    """One site, two sinks: the row's milliseconds under a span's name
    are the durations the same spans left in the host store."""
    events, rows, _ = recorded
    by_step = _store_ms_by_step(events)
    assert len(by_step) == len(rows)
    seen = set()
    for row, (step, step_ms, ms) in zip(rows, by_step):
        assert row["step"] == step
        # (the `engine.step` span also holds what follows the row's end:
        # the gauges, and the keeping of a slow step)
        assert row["wall_ms"] <= step_ms
        for name in tracing.STEP_SPANS:
            assert row[name] == pytest.approx(ms[name], abs=0.2), \
                (step, name)
            if ms[name]:
                seen.add(name)
        assert row["gap_spans_ms"] == pytest.approx(
            row[tracing.GAP_SPAN], abs=1e-9)
    assert seen == set(tracing.STEP_SPANS)


def test_a_row_accounts_for_its_step(recorded):
    _, rows, _ = recorded
    assert all(r["other_ms"] >= 0.0 for r in rows)
    assert statistics.median(r["other_ms"] for r in rows) < 0.2
    for r in rows:
        # children lie inside their parents, everything inside the step
        assert r["engine.flush_state"] <= r["engine.decode.dispatch"]
        assert r["engine.prefill.wait"] <= r["engine.prefill.harvest"]
        assert 0.0 <= r["cpu_ms"] and r["wall_ms"] > 0.0


def test_lanes_and_programs_of_the_run(recorded):
    _, rows, _ = recorded
    assert rows[0]["waiting"] == 2 and rows[0]["decoding"] == 0
    assert sum(r["admitted"] for r in rows) == 3
    assert sum(r["finished"] for r in rows) == 3
    assert sum(r["chunk_tokens"] for r in rows) == 40 + 9 + 21
    assert sum(r["chunks"] for r in rows) >= 3
    assert max(r["largest_bucket"] for r in rows) in (16, 48)
    assert {r["variant"] for r in rows} == {"", "greedy"}
    assert rows[0]["compiles"] > 0 and rows[-1]["compiles"] == 0
    assert max(r["decoding"] for r in rows) >= 2


def test_host_plus_device_gauges_are_the_rows_wall_time(recorded):
    _, rows, gauges = recorded
    for row, (host_ms, dev_ms) in zip(rows, gauges):
        waits = sum(row[w] for w in tracing.WAIT_SPANS)
        assert dev_ms == pytest.approx(waits, abs=1e-9)
        assert host_ms + dev_ms == pytest.approx(row["wall_ms"], abs=1e-9)


def test_starved_exactly_when_the_budget_passed_a_slot_over():
    eng = _engine("chunked")
    passed_over = []
    run_prefills = eng._run_prefills

    def spy():
        # what _run_prefills finds, and what it leaves untouched
        before = {r.req_id: r.written for r in eng._slots
                  if r is not None and r.state == "PREFILL"}
        chunks0 = len(eng._prefilled)
        outs = run_prefills()
        served = {p.req.req_id for p in eng._prefilled[chunks0:]}
        passed_over.append(len(set(before) - served))
        return outs

    eng._run_prefills = spy
    for n in (40, 40, 40):
        eng.add_request(_prompt(n), SamplingParams(max_new_tokens=3))
    _drain(eng)
    rows = eng.step_log.rows()
    assert [r["starved"] for r in rows] == passed_over
    assert any(passed_over) and not all(passed_over)
    # a starved step spent its budget (one bucket) on another slot
    assert all(r["chunks"] >= 1 and r["largest_bucket"] == 16
               for r in rows if r["starved"])
    eng.close()


def test_ring_stays_at_its_capacity(monkeypatch):
    monkeypatch.setattr(tracing, "STEP_LOG_ROWS", 16)
    eng = _engine()
    eng.add_request(_prompt(5), SamplingParams(max_new_tokens=40))
    _drain(eng)
    log = eng.step_log
    assert len(log) == 16 == len(log.rows()) and log._f.shape[0] == 16
    assert len(log.rows(t0=log.rows()[3]["t0_s"])) == 13
    steps = [r["step"] for r in log.rows()]
    assert steps == list(range(eng._steps - 16, eng._steps))
    eng.close()


def test_a_wait_that_sleeps_is_one_slow_entry(monkeypatch):
    import jax
    eng = _engine()
    eng.add_request(_prompt(5), SamplingParams(max_new_tokens=40))
    for _ in range(14):
        eng.step()                       # compiles, then steady ticks
    kept = len(eng.step_log.slow())
    steps0 = monitor.counter("serving.slow_steps").get()
    ms0 = monitor.counter("serving.slow_step_ms").get()
    real = jax.block_until_ready
    naps = [0.4]

    def sleepy(x):
        if naps:
            time.sleep(naps.pop())
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", sleepy)
    for _ in range(6):
        eng.step()
    slow = eng.step_log.slow()
    assert len(slow) == kept + 1
    assert monitor.counter("serving.slow_steps").get() - steps0 == 1
    entry = slow[-1]
    row = entry["row"]
    assert row["slow"] == 1 and row["step"] == 14
    assert 400.0 <= row["wall_ms"] < 600.0
    assert monitor.counter("serving.slow_step_ms").get() - ms0 == \
        pytest.approx(row["wall_ms"])
    assert max(IN_STEP, key=row.get) == "engine.decode.wait"
    assert row["engine.decode.wait"] >= 400.0
    assert row["cpu_ms"] < 50.0
    assert [r["step"] for r in entry["before"]] == list(range(6, 14))
    assert [r["step"] for r in entry["after"]] == [15, 16, 17, 18]
    assert entry["collections"] == [] and isinstance(entry["memory"], dict)
    assert sum(r["slow"] for r in eng.step_log.rows()) == kept + 1
    eng.close()


def test_step_logs_keeps_the_rows_after_close():
    eng = _engine(label="replica7")
    other = _engine()
    assert tracing.step_logs()["replica7"] is eng.step_log
    assert tracing.step_logs()["engine"] is other.step_log
    eng.add_request(_prompt(5), SamplingParams(max_new_tokens=4))
    _drain(eng)
    n = eng._steps
    eng.close()
    other.close()
    del eng
    log = tracing.step_logs()["replica7"]
    assert len(log.rows()) == n > 0 and len(other.step_log) == 0
    assert log.rows(t0=log.rows()[2]["t0_s"])[0]["step"] == 2
    assert log.rows(t1=log.rows()[2]["t0_s"])[-1]["step"] == 2


def test_the_callers_calls_lie_in_the_gap():
    """`engine.add_request` and a drain forced from outside a step are
    the gap's, not the step's."""
    eng = _engine()
    eng.add_request(_prompt(5), SamplingParams(max_new_tokens=30))
    for _ in range(4):
        eng.step()
    rid = eng.add_request(_prompt(6), SamplingParams(max_new_tokens=30))
    eng.cancel(rid)                      # drains the tick in flight
    eng.step()
    before, row = eng.step_log.rows()[-2:]
    assert before["engine.add_request"] == 0.0 == before["gap_spans_ms"]
    assert 0.0 < row["engine.add_request"] < row["gap_spans_ms"] \
        < row["gap_ms"]
    # the drained tick's wait is no part of this step's device time
    assert row["wall_ms"] >= sum(row[n] for n in tracing.WAIT_SPANS)
    eng.close()


def _replay(tmp_path, tag):
    vt = [0.0]
    eng = _engine("chunked", clock=lambda: vt[0])
    outs = []
    for k, n in enumerate((40, 9, 21)):
        eng.add_request(_prompt(n, k), SamplingParams(max_new_tokens=5))
    while not eng.idle:
        vt[0] += 0.010
        outs.extend(eng.step())
    path = tracing.export_serving_trace(
        {o.req_id: o.spans for o in outs}, str(tmp_path / f"{tag}.json"))
    rows = eng.step_log.rows()
    snap = eng.snapshot()
    eng.close()
    return open(path, "rb").read(), rows, outs, snap


def test_a_virtual_clock_replay_is_byte_identical_with_the_record_on(
        tmp_path):
    a, rows_a, outs, snap = _replay(tmp_path, "a")
    b, rows_b, _, _ = _replay(tmp_path, "b")
    assert a == b and len(rows_a) == len(rows_b) > 0
    # the record is on the wall clock: no two runs read the same
    assert rows_a[0]["t0_s"] != rows_b[0]["t0_s"]
    # and it is no part of a timeline or a snapshot
    assert "step_log" not in snap and b"wall_ms" not in a
    for o in outs:
        assert tracing.validate_timeline(o.spans) == []
