"""The linear-attention cell's files: the configuration and the traffic
mix load and say what the issue asked for, `kda_bytes.py` matches a hand
count (also on a recorded span), the new metric files compute on a
synthetic span set and find nothing in a run that lacks them, and the
runner `serve_hybrid` goes end to end at a tiny size on the CPU."""
import dataclasses
import json
import os
import time

import pytest

from benchmark import run as runpy
from benchmark.harness import device, kda_bytes, load
from benchmark.harness import program_spans as ps
from benchmark.harness.job import Job, Measured, MetricContext
from benchmark.harness.trace_reduce import Event
from benchmark.tests import tiny

CELL = "serve-solar2-4l-chat96"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("serve.kda_decode_roofline", "serve.kda_decode_time_share",
               "serve.state_gb")

TINY_HYBRID = dict(
    vocab_size=96, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=3, gqa_layers=[0],
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    max_position_embeddings=256, rms_norm_eps=1e-5,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                            num_heads=4, num_kv_heads=None),
    n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1,
    norm_topk_prob=True, routed_scaling_factor=1, prefill_query_block=8,
    expert_share=dict(index=1, of=2),
    serving=dict(weight_dtype="float32"),
    engine=dict(max_slots=4, page_size=8, prefill_bucket=8, max_context=64,
                cache_dtype="auto", max_prefill_tokens_per_step=16,
                keep_logits=True))
TINY_TRAFFIC = dict(
    runner="serve_hybrid", arrival=dict(kind="closed", clients=4),
    prompt_tokens=[12, 40], output_tokens=[4, 12], shared_prefix_tokens=0,
    block=16, ramp_seconds=0.2, steady_seconds=0.5, traced_seconds=0.5,
    reference_prompt_tokens=24, reference_new_tokens=4)


def test_the_cell_is_the_one_the_issue_names(root=load.REPO_ROOT):
    cell = load.load_cell(CELL, root)
    assert (cell.config_name, cell.traffic_name, cell.chips) == \
        ("solar-open2-serve-4l", "chat96", 1)
    t, c = cell.traffic, cell.config
    assert t["runner"] == "serve_hybrid"
    assert t["arrival"] == {"kind": "closed", "clients": 96}
    assert t["prompt_tokens"] == [256, 1792]
    assert t["output_tokens"] == [128, 384]
    assert t["shared_prefix_tokens"] == 0
    assert (t["block"], t["ramp_seconds"], t["steady_seconds"],
            t["traced_seconds"]) == (128, 4, 10, 3)
    assert (t["reference_prompt_tokens"], t["reference_new_tokens"]) == \
        (2560, 8)
    e = c["engine"]
    assert e["max_slots"] == t["arrival"]["clients"]
    # every timed prompt is one prefill program, the reference request two
    assert max(t["prompt_tokens"]) <= e["max_prefill_tokens_per_step"] \
        < t["reference_prompt_tokens"]
    assert t["reference_prompt_tokens"] + t["reference_new_tokens"] <= \
        e["max_context"]
    assert set(cell.end_to_end) == {"serve_tokens_per_s", "setup_s"}
    assert set(NEW_METRICS) | {"serve.moe_held_pick_share",
                               "serve.moe_experts_touched_share",
                               "serve.hbm_peak_gb"} <= set(cell.per_layer)
    assert not {"serve.paged_decode_roofline", "serve.mla_decode_roofline",
                "serve.preemptions"} & set(cell.per_layer)


def test_the_configuration_keeps_every_published_width():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    c = load.load_cell(CELL).config
    row = next(json.loads(line) for line in open(CATALOG)
               if '"Solar-Open2-250B"' in line)
    assert c["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if c.get(k) != v}
    assert changed == set(c["reduced"]) == {
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"}
    assert c["gqa_layers"] == [i for i in row["config"]["gqa_layers"]
                               if i < c["num_hidden_layers"]]
    assert c["n_routed_experts"] * c["expert_share"]["of"] == \
        row["config"]["n_routed_experts"] == c["published"]["n_routed_experts"]
    assert c["vocab_size"] * 8 == row["config"]["vocab_size"]


def test_kda_bytes_match_a_hand_count():
    c = load.load_cell(CELL).config
    assert kda_bytes.kda_layers(c) == 3
    assert kda_bytes.state_bytes_per_slot_layer(c) == 4 * 64 * 128 * 128
    # a slot a layer: S in and out, six float32 vectors of 128 a head
    assert kda_bytes.decode_bytes(c, 1) == \
        3 * (2 * 4194304 + 6 * 4 * 64 * 128)
    assert kda_bytes.decode_bytes(c, 96) == 96 * kda_bytes.decode_bytes(c, 1)
    assert kda_bytes.decode_bytes(c, 0) == 0


def _span(name, t0, dur, **stats):
    return ps.Span("/host:CPU", "main", name, float(t0), float(dur), stats)


def test_the_kernel_metrics_compute_on_a_recorded_span():
    cell = load.load_cell(CELL)
    ms = 1e6
    busy = [(0.0, 40 * ms)]
    kernels = [Event("/device:TPU:0", "XLA Ops",
                     "%kda_decode.7 = (f32[96,64,128], f32[96,64,128,128]) "
                     "custom-call()", i * 10 * ms, 2 * ms) for i in range(3)]
    spans = [_span("bench.window", 0, 50 * ms),
             _span("engine.decode.dispatch", 1 * ms, 1 * ms, slots=90,
                   ctx_tokens=90 * 1200, ticks=1, state_slots=90)]
    traced = ps.ProgramSpans(spans, [], busy, 0.0, 50 * ms, kernels)

    class Trace:
        busy_s = 0.040
    measured = Measured(checks={}, attempted=1, failed=0, end_to_end={},
                        samples={"state": dict(bytes=1250000000, resets=5,
                                               recomputes=0)},
                        trace=Trace())
    ctx = MetricContext(cell=cell, measured=measured,
                        device={"kind": "TPU v5 lite"},
                        peak=device.peak("TPU v5 lite"))
    orig = ps.for_ctx
    ps.for_ctx = lambda c: traced
    try:
        need = kda_bytes.decode_bytes(cell.config, 90)
        roof = load.load_metric("serve.kda_decode_roofline").compute(ctx)
        assert roof == pytest.approx(100 * need / 819e9 / 0.006)
        assert 0 < roof < 100
        share = load.load_metric("serve.kda_decode_time_share").compute(ctx)
        assert share == pytest.approx(100 * 0.006 / 0.040)
        # a program whose kernels carry another name, or whose spans lack
        # the argument: nothing to read
        traced.kernels = [dataclasses.replace(k, name="%paged_decode.8 = x")
                          for k in kernels]
        for name in NEW_METRICS[:2]:
            assert load.load_metric(name).compute(ctx) is None
        traced.kernels = kernels
        spans[1].stats.pop("state_slots")
        assert load.load_metric(NEW_METRICS[0]).compute(ctx) is None
    finally:
        ps.for_ctx = orig
    assert load.load_metric("serve.state_gb").compute(ctx) == 1.25


def test_the_new_metrics_find_nothing_in_a_run_that_lacks_them():
    cell = load.load_cell(CELL)
    measured = Measured(checks={}, attempted=0, failed=0, end_to_end={},
                        samples={}, trace=None)
    ctx = MetricContext(cell=cell, measured=measured, device={}, peak={})
    for name in NEW_METRICS:
        assert load.load_metric(name).compute(ctx) is None


def test_tiny_hybrid_cell_end_to_end(tmp_path):
    root = tiny.tiny_tree(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="tiny-hybrid", source="test",
                                 reduced=[], why="test",
                                 file="benchmark/configs/tiny-hybrid.json"))
    bench["workloads"].append(dict(name="tiny-hybrid", config="tiny-hybrid",
                                   traffic="tiny-hybrid", chips=1,
                                   why="test"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-serve" in m.get("workloads", []):
            m["workloads"].append("tiny-hybrid")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "benchmark/configs/tiny-hybrid.json").write_text(
        json.dumps(TINY_HYBRID))
    (root / "benchmark/traffic/tiny-hybrid.json").write_text(
        json.dumps(TINY_TRAFFIC))
    cell = load.load_cell("tiny-hybrid", root)
    job = Job(cell=cell, seed=2 ** 31 + 77, seconds=1.0, trace=False,
              trace_dir=str(root / "trace"),
              process_start=time.perf_counter(), device=device.describe())
    measured = load.load_runner("serve_hybrid").run(job)
    assert measured.correct, measured.checks
    assert set(measured.checks) == {
        "reference", "all_requests_ok", "paged_pallas_decode",
        "kda_pallas_decode", "no_compile_in_window", "no_leaked_pages"}
    moe = measured.samples["moe"]
    assert 0 < moe["picks_held"] < moe["picks_total"] and moe["held"] == 4
    state = measured.samples["state"]
    # 4 slots x 2 layers x (S [4, 16, 16] + tail [3, 192]) float32
    assert state["bytes"] == 4 * 2 * 4 * (4 * 16 * 16 + 3 * 192)
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
