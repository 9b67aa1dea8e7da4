"""Serving observability plane (docs/OBSERVABILITY.md "Serving
timelines & histograms").

Three contracts under test:

* ``monitor.Histogram`` — fixed log2 buckets, O(1) record, EXACT merge
  (a merged histogram is indistinguishable from one that recorded both
  streams), JSON-safe serialization, and percentile resolution within
  5% relative error of the exact nearest-rank answer — the bound the
  replay p99-TTFT gate (exit 7) leans on now that the unbounded
  latency lists are gone.
* Per-request span timelines — every request the engine retires
  carries a structurally contiguous QUEUED -> ... -> FINISHED/FAILED
  span log that survives snapshot/restore, and the chrome-trace export
  round-trips it (tools/trace_summary.py serving mode included).
* Host/device tick attribution — every ``step()`` splits its wall
  time into ``serving.host_ms_per_tick`` / ``serving.device_ms_per_tick``
  gauges plus histograms, and labeled scopes dual-write
  ``serving.<label>.…`` twins next to the unlabeled aggregate.

The chaos completeness matrix (fleet replica kill + disagg worker
kill, each under fault injection) asserts through the stitched
--trace-out export, not the in-process objects: what an operator
loads in Perfetto is the artifact under test.
"""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference import tracing
from paddle_tpu.inference.engine import Engine, SamplingParams
from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_net(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab=64, hidden=64, layers=2, heads=4)
    cfg.use_flash_attention = False
    net = LlamaForCausalLM(cfg)
    net.eval()
    return net


def _prompts(rng, lens, vocab=64):
    return [rng.integers(0, vocab, (n,)).astype(np.int64) for n in lens]


def _replay():
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import serving_replay
    finally:
        sys.path.pop(0)
    return serving_replay


def _nearest_rank(sorted_vals, q):
    """The exact percentile the old full-list _percentiles computed:
    nearest-rank on the sorted samples."""
    import math
    rank = max(1, math.ceil(q / 100.0 * len(sorted_vals)))
    return sorted_vals[rank - 1]


# ---------------------------------------------------------------------------
# Histogram: exactness, merge, resolution, serialization
# ---------------------------------------------------------------------------

def test_histogram_merge_is_exact():
    """merge() folds bucket counts: the merged histogram is
    indistinguishable (count/sum/min/max/every percentile) from one
    that recorded both streams directly."""
    rng = np.random.default_rng(7)
    a_vals = rng.lognormal(2.0, 1.0, 500)
    b_vals = rng.lognormal(4.0, 0.5, 300)
    ha = monitor.Histogram("a")
    hb = monitor.Histogram("b")
    hboth = monitor.Histogram("both")
    for v in a_vals:
        ha.record(v)
        hboth.record(v)
    for v in b_vals:
        hb.record(v)
        hboth.record(v)
    merged = monitor.Histogram("m").merge(ha).merge(hb)
    assert merged.count == hboth.count == 800
    assert merged.sum == pytest.approx(hboth.sum)
    for q in (1, 25, 50, 90, 99, 100):
        assert merged.percentile(q) == hboth.percentile(q)
    # bucket counts are exactly equal; sums only up to float
    # summation order
    for k, v in hboth.stats().items():
        assert merged.stats()[k] == pytest.approx(v), k


def test_histogram_resolution_within_5pct():
    """Bucket-midpoint percentiles stay within 5% relative error of
    the exact nearest-rank percentile — the resolution contract the
    serving_replay p99 gates (exit 7) rely on after dropping the
    full latency lists (see tools/serving_replay.py _percentiles)."""
    rng = np.random.default_rng(0)
    for dist in (rng.lognormal(3.0, 1.2, 4000),
                 rng.exponential(40.0, 4000) + 0.5,
                 rng.uniform(1.0, 900.0, 4000)):
        h = monitor.Histogram("res")
        for v in dist:
            h.record(float(v))
        exact = np.sort(dist)
        for q in (50, 90, 95, 99):
            want = _nearest_rank(exact, q)
            got = h.percentile(q)
            assert abs(got - want) / want <= 0.05, (q, got, want)


def test_histogram_zero_bucket_and_clamp():
    """Non-positive samples (virtual-clock granularity yields 0.0
    latencies) land in the zero bucket; percentiles stay inside the
    exact observed [min, max]."""
    h = monitor.Histogram("z")
    for v in (0.0, 0.0, -1.0, 5.0):
        h.record(v)
    assert h.count == 4
    assert h.percentile(50) == 0.0      # zero bucket reports 0
    assert h.percentile(100) == 5.0
    st = h.stats()
    assert st["min"] == -1.0 and st["max"] == 5.0


def test_histogram_serialization_round_trip():
    """to_dict/from_dict is lossless (snapshot files, cross-process
    merge) and JSON-safe."""
    rng = np.random.default_rng(3)
    h = monitor.Histogram("ser")
    for v in rng.lognormal(2.0, 1.0, 250):
        h.record(float(v))
    wire = json.loads(json.dumps(h.to_dict()))
    back = monitor.Histogram.from_dict(wire, "ser")
    assert back.stats() == h.stats()
    # a deserialized histogram keeps merging exactly
    other = monitor.Histogram("o")
    other.record(1.0)
    combined = monitor.Histogram("c").merge(back).merge(other)
    assert combined.count == h.count + 1


def test_scope_dual_write_and_fleet_merge():
    """A labeled scope writes BOTH the unlabeled aggregate and its
    serving.<label>. twin; merging the per-replica twins reproduces
    the aggregate exactly — per-replica histograms merge fleet-wide
    without losing resolution."""
    agg = monitor.histogram("serving.hist.obs_scope_test_ms")
    agg.reset()
    labeled = []
    for i, n in ((0, 40), (1, 25)):
        sc = monitor.scope(f"replica{i}")
        pair = sc.histogram("serving.hist.obs_scope_test_ms")
        rng = np.random.default_rng(i)
        for v in rng.lognormal(2.0, 0.8, n):
            pair.record(float(v))
        tw = monitor.histogram(
            f"serving.replica{i}.hist.obs_scope_test_ms")
        assert tw.count == n
        labeled.append(tw)
    assert agg.count == 65
    remerged = monitor.Histogram("fleetwide")
    for tw in labeled:
        remerged.merge(tw)
    for k, v in agg.stats().items():
        assert remerged.stats()[k] == pytest.approx(v), k
    for h in labeled + [agg]:
        h.reset()


# ---------------------------------------------------------------------------
# Engine timelines: lifecycle, preemption, snapshot/restore, host/device
# ---------------------------------------------------------------------------

def test_engine_timeline_lifecycle(rng):
    """Every retired Output carries a contiguous timeline: first span
    QUEUED, exactly one terminal span last, validate_timeline clean,
    and phase_shares covers the whole span of the request."""
    net = _tiny_net()
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                 max_context=64)
    outs = eng.run([(p, SamplingParams(max_new_tokens=6))
                    for p in _prompts(rng, (5, 9, 3))])
    assert len(outs) == 3
    for o in outs:
        assert o.ok and o.spans
        assert tracing.validate_timeline(o.spans) == []
        assert o.spans[0]["phase"] == tracing.QUEUED
        assert o.spans[-1]["phase"] == tracing.FINISHED
        phases = [s["phase"] for s in o.spans]
        assert tracing.PREFILL in phases and tracing.DECODE in phases
        shares = tracing.phase_shares(o.spans)
        total = o.spans[-1]["t0_ms"] - o.spans[0]["t0_ms"]
        assert sum(shares.values()) == pytest.approx(total, abs=0.01)
    eng.close()


def test_queued_span_says_when_the_slot_was_given(rng):
    """`QUEUED.detail.admitted_ms` is the engine-clock instant `_admit`
    gave the request its slot: before it the request waited for a SLOT,
    after it (to the span's end, its first slice) for prefill BUDGET."""
    vt = [0.0]

    def queued_spans(max_slots):
        eng = Engine(_tiny_net(), max_slots=max_slots, page_size=8,
                     pool_pages=64, max_context=64, prefill_bucket=16,
                     max_prefill_tokens_per_step=16, clock=lambda: vt[0])
        outs = {}
        for p in _prompts(rng, (12, 9, 5)):
            eng.add_request(p, SamplingParams(max_new_tokens=3))
        while not eng.idle:
            vt[0] += 0.010
            outs.update((o.req_id, o) for o in eng.step())
        eng.close()
        queued = [outs[i].spans[0] for i in sorted(outs)]
        for i, q in enumerate(queued):
            assert q["phase"] == tracing.QUEUED
            assert q["t0_ms"] <= q["detail"]["admitted_ms"] <= q["t1_ms"]
            assert tracing.validate_timeline(outs[i].spans) == []
        return queued, eng.step_log.rows()

    # one slot: each request waits for the SLOT and is prefilled in the
    # step that admits it
    queued, rows = queued_spans(1)
    assert [q["detail"]["admitted_ms"] for q in queued] == \
        [q["t1_ms"] for q in queued]
    assert queued[0]["t1_ms"] < queued[1]["t1_ms"] < queued[2]["t1_ms"]
    assert not any(r["starved"] for r in rows)
    # three slots, a budget of one bucket a step: all are admitted at
    # once; the shortest is served, the oldest is never passed over, and
    # the one between them waits a step for BUDGET
    queued, rows = queued_spans(3)
    admitted = {q["detail"]["admitted_ms"] for q in queued}
    assert admitted == {queued[0]["t1_ms"]} == {queued[2]["t1_ms"]}
    assert queued[1]["t1_ms"] == queued[1]["detail"]["admitted_ms"] + 10.0
    assert [r["starved"] for r in rows][:2] == [1, 0]


def test_engine_timeline_preemption_spans(rng):
    """A pool-pressure preemption shows up as a PREEMPTED span between
    two decode stints, and the timeline stays contiguous through the
    resume."""
    net = _tiny_net()
    eng = Engine(net, max_slots=2, page_size=4, pool_pages=4,
                 max_context=16, prefill_bucket=4, watermark_pages=0)
    outs = eng.run([(p, SamplingParams(max_new_tokens=10))
                    for p in _prompts(rng, (4, 3))])
    preempted = [o for o in outs if o.preemptions > 0]
    assert preempted
    for o in preempted:
        phases = [s["phase"] for s in o.spans]
        assert tracing.PREEMPTED in phases
        assert tracing.validate_timeline(o.spans) == []
    eng.close()


def test_engine_snapshot_restore_stitches_timeline(rng):
    """Span context is host state that rides snapshot()/restore(): a
    request suspended mid-decode resumes in a NEW engine process and
    still retires ONE contiguous timeline whose restore seam is a
    PREEMPTED span tagged kind=restore."""
    net = _tiny_net()
    prompts = _prompts(rng, (5, 7))
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                 max_context=64)
    for p in prompts:
        eng.add_request(p, SamplingParams(max_new_tokens=8))
    done = {}
    for _ in range(3):
        for o in eng.step():
            done[o.req_id] = o
    snap = eng.snapshot()
    eng.close()

    eng2 = Engine(_tiny_net(), max_slots=2, page_size=8, pool_pages=64,
                  max_context=64)
    assert eng2.restore(snap) > 0
    for _ in range(60):
        for o in eng2.step():
            done[o.req_id] = o
        if len(done) == 2:
            break
    assert len(done) == 2
    restored = [o for o in done.values()
                if any(s.get("detail", {}).get("kind") == "restore"
                       for s in o.spans)]
    assert restored
    for o in done.values():
        assert tracing.validate_timeline(o.spans) == []
        assert o.spans[0]["phase"] == tracing.QUEUED
        assert o.spans[-1]["phase"] == tracing.FINISHED
    eng2.close()


def test_host_device_tick_attribution(rng):
    """step() publishes the host/device wall-time split: gauges carry
    the last tick, histograms the per-tick distribution, and
    host + device never exceeds the recorded tick wall time."""
    for name in ("serving.hist.host_ms_per_tick",
                 "serving.hist.device_ms_per_tick",
                 "serving.hist.tick_ms"):
        monitor.histogram(name).reset()
    for name in ("serving.host_ms_per_tick",
                 "serving.device_ms_per_tick"):
        monitor.gauge(name).reset()
    net = _tiny_net()
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                 max_context=64)
    eng.run([(p, SamplingParams(max_new_tokens=4))
             for p in _prompts(rng, (5, 3))])
    host = monitor.histogram("serving.hist.host_ms_per_tick")
    dev = monitor.histogram("serving.hist.device_ms_per_tick")
    tick = monitor.histogram("serving.hist.tick_ms")
    assert host.count == dev.count == tick.count > 0
    assert host.sum >= 0.0 and dev.sum >= 0.0
    assert host.sum + dev.sum == pytest.approx(tick.sum, rel=1e-6)
    detail = monitor.snapshot(detail=True)
    assert detail["serving.host_ms_per_tick"]["count"] == host.count
    assert detail["serving.device_ms_per_tick"]["count"] == dev.count
    eng.close()


# ---------------------------------------------------------------------------
# Chaos completeness matrix + deterministic export (through the replay tool)
# ---------------------------------------------------------------------------

def _assert_complete_stitched(trace_path, expect_failed=False):
    """The operator-facing artifact check: reload the exported trace
    and re-assert every request reconstructs to exactly one contiguous
    timeline with one terminal span."""
    with open(trace_path) as f:
        trace = json.load(f)
    assert trace["metadata"]["tool"] == "paddle_tpu.serving_timeline"
    timelines = tracing.timelines_from_trace(trace)
    assert len(timelines) == trace["metadata"]["requests"] > 0
    saw_failed = False
    for rid, spans in timelines.items():
        assert tracing.validate_timeline(spans, tol_ms=0.01) == [], rid
        assert spans[0]["phase"] == tracing.QUEUED, rid
        assert spans[-1]["phase"] in (tracing.FINISHED,
                                      tracing.FAILED), rid
        saw_failed |= spans[-1]["phase"] == tracing.FAILED
    if expect_failed:
        assert saw_failed
    return timelines


def test_fleet_chaos_timeline_completeness(rng, capsys, tmp_path):
    """Fleet chaos matrix: replica kill + fault injection on the
    session-heavy fixture — every request (survivor, re-admitted,
    failed) yields exactly ONE contiguous stitched timeline in the
    --trace-out export, live-migrated/failed-over requests included,
    and the exit-12 gate agrees."""
    serving_replay = _replay()
    trace = os.path.join(_REPO, "tests", "fixtures",
                         "serving_trace_fleet.jsonl")
    out_path = str(tmp_path / "fleet_spans.json")
    rc = serving_replay.main([
        trace, "--replicas", "2", "--kill-replica", "1:12",
        "--chaos", "--fault-seed", "3", "--fault-rate", "0.03",
        "--trace-out", out_path, "--expect-complete-timelines",
        "--json"])
    report = json.loads(capsys.readouterr().out.strip()
                        .splitlines()[-1])
    assert rc == 0
    timelines = _assert_complete_stitched(out_path,
                                          expect_failed=True)
    # failover stitches into the same timeline: killed-replica
    # requests carry a failover-tagged span, not a fresh timeline
    failover = [spans for spans in timelines.values()
                if any(s.get("detail", {}).get("kind") == "failover"
                       for s in spans)]
    assert failover
    assert report["steady_state_recompiles"] == 0
    assert report["histograms"]["serving.hist.ttft_ms"]["count"] > 0
    assert "replica0" in report["fleet"]["ttft_by_replica"]


def test_disagg_chaos_timeline_completeness(rng, capsys, tmp_path):
    """Disagg chaos matrix: decode-worker kill + fault injection —
    page-migrated requests (prefill -> decode pool) and failed-over
    ones stitch into single contiguous timelines across workers."""
    serving_replay = _replay()
    trace = os.path.join(_REPO, "tests", "fixtures",
                         "serving_trace.jsonl")
    out_path = str(tmp_path / "disagg_spans.json")
    rc = serving_replay.main([
        trace, "--disagg", "--prefill-workers", "2",
        "--decode-workers", "2", "--kill-worker", "decode:1:10",
        "--chaos", "--fault-seed", "3", "--fault-rate", "0.03",
        "--trace-out", out_path, "--expect-complete-timelines",
        "--json"])
    capsys.readouterr()
    assert rc == 0
    timelines = _assert_complete_stitched(out_path)
    # every finished request crossed the prefill->decode boundary:
    # a MIGRATING span tagged kind=pages, origins spanning workers
    migrated = [spans for spans in timelines.values()
                if any(s["phase"] == tracing.MIGRATING and
                       s.get("detail", {}).get("kind") == "pages"
                       for s in spans)]
    assert migrated
    origins = {s["origin"] for spans in timelines.values()
               for s in spans}
    assert any(o.startswith("prefill") for o in origins)
    assert any(o.startswith("decode") for o in origins)


def test_double_replay_trace_byte_identical(rng, capsys, tmp_path):
    """Two same-seed replays on the virtual clock export byte-identical
    timeline files — the determinism the acceptance gate pins."""
    serving_replay = _replay()
    trace = os.path.join(_REPO, "tests", "fixtures",
                         "serving_trace.jsonl")
    args = [trace, "--layers", "1", "--hidden", "32", "--heads", "2",
            "--vocab", "32", "--max-slots", "2", "--page-size", "8",
            "--pool-pages", "24", "--json"]
    paths = []
    for tag in ("a", "b"):
        p = str(tmp_path / f"spans_{tag}.json")
        rc = serving_replay.main(args + ["--trace-out", p])
        capsys.readouterr()
        assert rc == 0
        paths.append(p)
    with open(paths[0], "rb") as fa, open(paths[1], "rb") as fb:
        assert fa.read() == fb.read()


def test_trace_summary_serving_mode_round_trip(rng, capsys, tmp_path):
    """tools/trace_summary.py detects a serving-timeline export and
    prints the per-phase time-share table; its aggregation matches
    tracing.phase_shares over the reconstructed timelines."""
    serving_replay = _replay()
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import trace_summary
    finally:
        sys.path.pop(0)
    trace = os.path.join(_REPO, "tests", "fixtures",
                         "serving_trace.jsonl")
    out_path = str(tmp_path / "spans.json")
    rc = serving_replay.main([
        trace, "--layers", "1", "--hidden", "32", "--heads", "2",
        "--vocab", "32", "--max-slots", "2", "--page-size", "8",
        "--pool-pages", "24", "--json", "--trace-out", out_path])
    capsys.readouterr()
    assert rc == 0
    assert trace_summary.main([out_path]) == 0
    text = capsys.readouterr().out
    assert "serving timeline" in text
    assert "QUEUED" in text and "DECODE" in text
    # the table's per-phase totals == phase_shares over the round-trip
    with open(out_path) as f:
        exported = json.load(f)
    summary = trace_summary.summarize_serving(exported)
    want = {}
    for spans in tracing.timelines_from_trace(exported).values():
        for phase, ms in tracing.phase_shares(spans).items():
            want[phase] = want.get(phase, 0.0) + ms
    for phase, a in summary["phases"].items():
        assert a["total_ms"] == pytest.approx(
            want.get(phase, 0.0), abs=0.01), phase
    assert summary["requests"] == exported["metadata"]["requests"]
