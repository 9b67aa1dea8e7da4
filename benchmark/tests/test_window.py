"""The sliding-window cell's files: the configuration and the traffic
mix load and say what the issue asked for, `gqa_window_bytes.py` matches
a hand count, the new metric files compute on a synthetic span set and
find nothing in a run that lacks them, the reference's switches move it,
and the runner `serve_window` goes end to end at a tiny size on the
CPU."""
import dataclasses
import gc
import json
import os
import time

import numpy as np
import pytest

from benchmark import run as runpy
from benchmark.harness import device, gqa_window_bytes, load, pauses
from benchmark.harness import program_spans as ps
from benchmark.harness.job import Job, Measured, MetricContext
from benchmark.harness.trace_reduce import Event
from benchmark.tests import tiny

CELL = "serve-kexaone-5l-mixed128"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("serve.gqa_decode_roofline", "serve.gqa_decode_time_share")
REDUCED = {"num_hidden_layers", "layer_types", "sliding_windows",
           "mlp_layer_types", "num_experts", "vocab_size",
           "num_nextn_predict_layers"}

TINY_WINDOW = dict(
    vocab_size=96, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=5,
    layer_types=["sliding_attention"] * 3 + ["full_attention",
                                             "sliding_attention"],
    sliding_windows=[8, 8, 8, 0, 8],
    mlp_layer_types=["dense"] + ["sparse"] * 4, sliding_window=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    max_position_embeddings=256, rms_norm_eps=1e-5,
    rope_parameters=dict(rope_theta=1000000, rope_type="default"),
    num_experts=4, num_experts_per_tok=2, num_shared_experts=1,
    norm_topk_prob=True, routed_scaling_factor=2.5,
    num_nextn_predict_layers=0, prefill_query_block=8,
    expert_share=dict(index=1, of=2),
    serving=dict(weight_dtype="float32"),
    engine=dict(max_slots=4, page_size=8, prefill_bucket=4, max_context=64,
                cache_dtype="auto", max_prefill_tokens_per_step=20,
                keep_logits=True))
TINY_TRAFFIC = dict(
    runner="serve_window", arrival=dict(kind="closed", clients=4),
    prompt_tokens=[12, 40], output_tokens=[4, 12], shared_prefix_tokens=0,
    block=16, ramp_seconds=0.2, steady_seconds=0.5, traced_seconds=0.5,
    reference_prompt_tokens=30, reference_new_tokens=4)


def test_the_cell_is_the_one_the_issue_names():
    cell = load.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == \
        ("k-exaone-236b-serve-5l", "mixed128", 1)
    t, c = cell.traffic, cell.config
    assert t["runner"] == "serve_window"
    assert t["arrival"] == {"kind": "closed", "clients": 128}
    assert t["prompt_tokens"] == [256, 1792]
    assert t["output_tokens"] == [128, 384]
    assert t["shared_prefix_tokens"] == 0
    assert (t["block"], t["ramp_seconds"], t["steady_seconds"],
            t["traced_seconds"]) == (128, 4, 10, 3)
    assert (t["reference_prompt_tokens"], t["reference_new_tokens"]) == \
        (2560, 8)
    e = c["engine"]
    assert e == dict(max_slots=128, page_size=128, prefill_bucket=512,
                     max_prefill_tokens_per_step=2048, max_context=2688,
                     cache_dtype="auto", keep_logits=True)
    # every timed prompt fits one prefill program, the reference request takes two
    assert max(t["prompt_tokens"]) <= e["max_prefill_tokens_per_step"] \
        < t["reference_prompt_tokens"]
    assert max(t["prompt_tokens"]) + max(t["output_tokens"]) <= \
        e["max_context"] >= \
        t["reference_prompt_tokens"] + t["reference_new_tokens"]
    # a ring has the layout of one page
    assert c["sliding_window"] == e["page_size"]
    assert set(cell.end_to_end) == {"serve_tokens_per_s", "setup_s"}
    assert set(NEW_METRICS) | {"serve.moe_held_pick_share",
                               "serve.moe_experts_touched_share",
                               "serve.state_gb", "serve.hbm_peak_gb",
                               "serve.device_idle_share"} \
        <= set(cell.per_layer)
    assert not {"serve.paged_decode_roofline", "serve.mla_decode_roofline",
                "serve.kda_decode_roofline", "serve.preemptions"} \
        & set(cell.per_layer)


def test_the_configuration_keeps_every_published_width():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    c = load.load_cell(CELL).config
    row = next(json.loads(line) for line in open(CATALOG)
               if '"K-EXAONE-236B-A23B"' in line)
    assert c["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if c.get(k) != v}
    assert changed == set(c["reduced"]) == REDUCED
    n = c["num_hidden_layers"]
    for key in ("layer_types", "sliding_windows", "mlp_layer_types"):
        assert c[key] == row["config"][key][:n]
    # the leading dense layer and one whole period, three sliding to one
    # full as published
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert sorted(c["layer_types"][1:]) == \
        sorted(row["config"]["layer_types"][:4])
    assert c["num_experts"] * c["expert_share"]["of"] == \
        row["config"]["num_experts"] == c["published"]["num_experts"]
    assert c["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert c["num_nextn_predict_layers"] == 0


def test_the_runner_builds_the_configuration_it_is_given():
    runner = load.load_runner("serve_window")
    cfg = runner.model_config(load.load_cell(CELL).config)
    assert (cfg.num_experts, cfg.num_experts_held, cfg.expert_share) == \
        (128, 16, (0, 8))
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.dtype,
            cfg.sliding_window, cfg.num_nextn_predict_layers) == \
        (5, 19200, "bfloat16", 128, 0)
    assert cfg.layer_types.count("full_attention") == 1


def test_gqa_window_bytes_match_a_hand_count():
    c = load.load_cell(CELL).config
    # K + V, 8 heads of 128, bfloat16
    assert gqa_window_bytes.kv_bytes_per_token_layer(c) == 4096
    # one slot at context 1000: the full layer reads 1001 rows, each of
    # the four sliding layers the 128 of its window
    assert gqa_window_bytes.decode_bytes(c, 1000, 128, 1) == \
        4096 * (1001 + 4 * 128)
    # a slot at context 50 has 51 rows in its rings
    assert gqa_window_bytes.decode_bytes(c, 50, 51, 1) == 4096 * 5 * 51
    assert gqa_window_bytes.decode_bytes(c, 0, 0, 0) == 0


def _span(name, t0, dur, **stats):
    return ps.Span("/host:CPU", "main", name, float(t0), float(dur), stats)


def test_the_kernel_metrics_compute_on_a_synthetic_span():
    cell = load.load_cell(CELL)
    ms = 1e6
    busy = [(0.0, 40 * ms)]
    kernels = [Event("/device:TPU:0", "XLA Ops",
                     "%paged_decode.7 = bf16[128,8,8,128] custom-call()",
                     i * 5 * ms, 1 * ms) for i in range(5)]
    spans = [_span("bench.window", 0, 50 * ms),
             _span("engine.decode.dispatch", 1 * ms, 1 * ms, slots=120,
                   ctx_tokens=120 * 1700, win_tokens=120 * 128, ticks=1,
                   state_slots=120)]
    traced = ps.ProgramSpans(spans, [], busy, 0.0, 50 * ms, kernels)

    class Trace:
        busy_s = 0.040
    measured = Measured(checks={}, attempted=1, failed=0, end_to_end={},
                        samples={}, trace=Trace())
    ctx = MetricContext(cell=cell, measured=measured,
                        device={"kind": "TPU v5 lite"},
                        peak=device.peak("TPU v5 lite"))
    orig = ps.for_ctx
    ps.for_ctx = lambda c: traced
    try:
        need = gqa_window_bytes.decode_bytes(cell.config, 120 * 1700,
                                             120 * 128, 120)
        assert need == 4096 * (120 * 1701 + 4 * 120 * 128)
        roof = load.load_metric(NEW_METRICS[0]).compute(ctx)
        assert roof == pytest.approx(100 * need / 819e9 / 0.005)
        assert 0 < roof < 100
        share = load.load_metric(NEW_METRICS[1]).compute(ctx)
        assert share == pytest.approx(100 * 0.005 / 0.040)
        # a program whose kernels carry another name, or whose spans lack
        # the argument: nothing to read
        traced.kernels = [dataclasses.replace(k, name="%kda_decode.8 = x")
                          for k in kernels]
        for name in NEW_METRICS:
            assert load.load_metric(name).compute(ctx) is None
        traced.kernels = kernels
        spans[1].stats.pop("win_tokens")
        assert load.load_metric(NEW_METRICS[0]).compute(ctx) is None
    finally:
        ps.for_ctx = orig


def test_the_new_metrics_find_nothing_in_a_run_that_lacks_them():
    cell = load.load_cell(CELL)
    measured = Measured(checks={}, attempted=0, failed=0, end_to_end={},
                        samples={}, trace=None)
    ctx = MetricContext(cell=cell, measured=measured, device={}, peak={})
    for name in NEW_METRICS:
        assert load.load_metric(name).compute(ctx) is None


def test_each_switch_moves_the_reference(monkeypatch):
    """The reference at the tiny size: the expert share as the runner
    hands it over, and each mechanism's switch changes the logits."""
    from benchmark.reference import k_exaone as ref
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    runner = load.load_runner("serve_window")

    class Cell:
        config = TINY_WINDOW
    cfg, net = runner.build_model(Cell, 5)
    assert (cfg.num_experts, cfg.num_experts_held) == (8, 4)
    weights = ref.model_weights(net)
    ids = np.random.default_rng(0).integers(0, 96, 40)
    want = np.asarray(ref.logits(weights, TINY_WINDOW, ids, (1, 2)))
    assert want.shape == (40, 96) and np.isfinite(want).all()
    for off in (dict(window=False), dict(rope=False), dict(qk_norm=False),
                dict(round_to="bfloat16")):
        other = np.asarray(ref.logits(weights, TINY_WINDOW, ids, (1, 2),
                                      **off))
        assert ref.errors(other, want)["rms"] > 1e-3, off
    # the first window's tokens see no window
    short = np.asarray(ref.logits(weights, TINY_WINDOW, ids[:8], (1, 2),
                                  window=False))
    assert ref.errors(short, want[:8])["max"] < 1e-5


def test_pauses_report_names_the_longest_step_and_gap():
    """A synthetic window with one long step and one long gap: the
    report finds both, and the watch splits a busy stretch of this
    thread into on-CPU time where /proc has the file."""
    with pauses.Watch() as watch:
        time.sleep(0.15)                   # the watch's first sample
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.35:
            pass
        t1 = time.perf_counter()
        gc.collect()
    ticks = [(t0 - 1.0, 0.016, 3, 0, 0), (t0, t1 - t0, 4, 1, 1),
             (t1 + 0.5, 0.016, 4, 0, 0), (t1 + 0.52, 0.016, 4, 0, 0)]
    rep = pauses.report(ticks, watch)
    assert rep["steps"] == 4
    assert rep["longest_steps"][0]["ms"] == pytest.approx((t1 - t0) * 1e3)
    assert rep["longest_steps"][0]["prefilling"] == 1
    assert rep["longest_gaps"][0]["ms"] == pytest.approx(1000 - 16)
    assert rep["gap_ms_total"] == pytest.approx(984 + 500 + 4)
    busy = rep["during_longest_step"]
    # this thread spun: the process's CPU time grew with the clock
    assert busy["sampled_ms"] >= 350 and busy["process_cpu_ms"] > 200
    assert busy["longest_watch_gap_ms"] >= 90
    if "on_cpu_ms" in busy:
        assert busy["on_cpu_ms"] > 200
    assert pauses.report(ticks[:1], watch) == {"steps": 1}
    json.dumps(rep)


def test_tiny_window_cell_end_to_end(tmp_path, capsys):
    root = tiny.tiny_tree(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="tiny-window", source="test",
                                 reduced=[], why="test",
                                 file="benchmark/configs/tiny-window.json"))
    bench["workloads"].append(dict(name="tiny-window", config="tiny-window",
                                   traffic="tiny-window", chips=1,
                                   why="test"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-serve" in m.get("workloads", []):
            m["workloads"].append("tiny-window")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "benchmark/configs/tiny-window.json").write_text(
        json.dumps(TINY_WINDOW))
    (root / "benchmark/traffic/tiny-window.json").write_text(
        json.dumps(TINY_TRAFFIC))
    cell = load.load_cell("tiny-window", root)
    job = Job(cell=cell, seed=2 ** 31 + 77, seconds=1.0, trace=False,
              trace_dir=str(root / "trace"),
              process_start=time.perf_counter(), device=device.describe())
    measured = load.load_runner("serve_window").run(job)
    assert measured.correct, measured.checks
    said = {line["info"]: line for line in map(
        json.loads, capsys.readouterr().out.splitlines())}
    # the readings the limits were set between, made again in every run
    assert {"reference", "reference_without_window",
            "reference_without_rope", "reference_without_qk_norm",
            "reference_in_bfloat16", "reference_in_float8_e4m3fn",
            "stalls", "rings"} <= set(said)
    assert said["reference_in_bfloat16"]["median_row"] \
        < said["reference_in_float8_e4m3fn"]["median_row"]
    assert not said["reference_without_window"]["passes"]
    assert said["stalls"]["steps"] > 2 and said["stalls"]["longest_steps"]
    assert set(measured.checks) == {
        "reference", "all_requests_ok", "paged_pallas_decode",
        "no_compile_in_window", "no_leaked_pages"}
    moe = measured.samples["moe"]
    assert 0 < moe["picks_held"] < moe["picks_total"] and moe["held"] == 4
    assert moe["slabs"] > 0
    state = measured.samples["state"]
    # 4 slots x 4 sliding layers x (k + v rings [2, 8, 16]) float32
    assert state["bytes"] == 4 * 4 * 2 * 4 * (2 * 8 * 16)
    assert state["resets"] > 0 and state["recomputes"] == 0
    line = runpy.result_line(job, measured)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"setup_s", "serve_tokens_per_s"} <= set(line["metrics"])
    traced = runpy.result_line(
        dataclasses.replace(job, trace=True,
                            device=dict(job.device, kind="TPU v5 lite")),
        measured)
    assert {"serve.moe_held_pick_share", "serve.moe_experts_touched_share",
            "serve.state_gb"} <= set(traced["metrics"])
