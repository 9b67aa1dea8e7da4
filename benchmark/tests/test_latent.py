"""The latent-attention cell's files: the configuration and the traffic
mix load and say what the issue asked for, `mla_bytes.py` matches a hand
count, the new metric files compute on a synthetic span set, and the
runner `serve_latent` goes end to end at a tiny size on the CPU."""
import dataclasses
import json
import time

import pytest

from benchmark import run as runpy
from benchmark.harness import device, load, mla_bytes
from benchmark.harness.job import Job, Measured, MetricContext
from benchmark.harness.trace_reduce import Event
from benchmark.harness import program_spans as ps
from benchmark.tests import tiny

CELL = "serve-dots3-5l-notes48"

TINY_LATENT = dict(
    vocab_size=96, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=3,
    layer_types=["full_attention", "full_attention", "sliding_attention"],
    max_position_embeddings=256, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    index_n_heads=2, index_head_dim=16, index_topk=8,
    sliding_window_size=5, swa_num_attention_heads=2,
    swa_num_key_value_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=24,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
    swa_rope_theta=50000, rope_theta=80000000, rms_norm_eps=1e-5,
    first_k_dense_replace=1, n_routed_experts=4, num_experts_per_tok=2,
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=1,
    apply_mla_qkv_lora_rescale=True, prefill_query_block=8,
    expert_share=dict(index=1, of=2),
    serving=dict(weight_dtype="float32"),
    engine=dict(max_slots=4, page_size=8, prefill_bucket=8, max_context=64,
                cache_dtype="auto", max_prefill_tokens_per_step=16,
                keep_logits=True))
TINY_TRAFFIC = dict(
    runner="serve_latent", arrival=dict(kind="closed", clients=4),
    prompt_tokens=[12, 40], output_tokens=[4, 12], shared_prefix_tokens=0,
    block=16, ramp_seconds=0.2, steady_seconds=0.5, traced_seconds=0.5,
    reference_prompt_tokens=24, reference_new_tokens=4)


def test_the_cell_is_the_one_the_issue_names(root=load.REPO_ROOT):
    cell = load.load_cell(CELL, root)
    assert (cell.config_name, cell.traffic_name, cell.chips) == \
        ("dots3-note-serve-5l", "notes48", 1)
    t, c = cell.traffic, cell.config
    assert t["runner"] == "serve_latent"
    assert t["arrival"] == {"kind": "closed", "clients": 48}
    assert t["prompt_tokens"] == [2304, 4352]
    assert t["output_tokens"] == [128, 384]
    assert (t["block"], t["ramp_seconds"], t["steady_seconds"],
            t["traced_seconds"]) == (128, 4, 10, 3)
    assert (t["reference_prompt_tokens"], t["reference_new_tokens"]) == \
        (2560, 8)
    assert c["engine"]["max_slots"] == t["arrival"]["clients"]
    assert min(t["prompt_tokens"]) > c["index_topk"]
    # 111 requests finish in a window: under the issue's 130, no p90
    assert set(cell.end_to_end) == {"serve_tokens_per_s", "setup_s"}
    assert {"serve.mla_decode_roofline", "serve.mla_decode_time_share",
            "serve.moe_held_pick_share",
            "serve.moe_experts_touched_share"} <= set(cell.per_layer)
    assert not {"serve.paged_decode_roofline",
                "serve.paged_decode_time_share"} & set(cell.per_layer)


def test_the_configuration_keeps_every_published_width():
    c = load.load_cell(CELL).config
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"dots3-note-prev"' in line) \
        if __import__("os").path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is None:
        pytest.skip("no catalog here")
    assert c["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if c.get(k) != v}
    assert changed == set(c["reduced"]) == {
        "num_hidden_layers", "layer_types", "n_routed_experts",
        "vocab_size"}
    assert c["layer_types"] == row["config"]["layer_types"][:5]
    assert c["n_routed_experts"] * c["expert_share"]["of"] == \
        row["config"]["n_routed_experts"] == c["published"]["n_routed_experts"]
    assert c["vocab_size"] * 8 == row["config"]["vocab_size"]


def test_mla_bytes_match_a_hand_count():
    c = load.load_cell(CELL).config
    # full: 512 latent + 64 rotary key (+ 128 indexer key); sliding: 1024 + 64
    assert mla_bytes.row_values(c, "full_attention") == 576
    assert mla_bytes.row_values(c, "sliding_attention") == 1088
    assert mla_bytes.cache_bytes_per_token(c) == \
        2 * (2 * (576 + 128) + 3 * 1088) == 9344
    # 48 slots past both limits: 2 full layers x 2048 rows, 3 sliding x 513
    assert mla_bytes.decode_attention_bytes(c, 48 * 2048, 48 * 513) == \
        2 * 48 * (2 * 2048 * 576 + 3 * 513 * 1088)


def _span(name, t0, dur, **stats):
    return ps.Span("/host:CPU", "main", name, float(t0), float(dur), stats)


def test_the_kernel_metrics_compute_on_a_synthetic_span_set():
    cell = load.load_cell(CELL)
    ms = 1e6
    busy = [(0.0, 40 * ms)]
    kernels = [Event("/device:TPU:0", "XLA Ops",
                     "%paged_mla_decode.3 = bf16[48,1,128,512] custom-call()",
                     i * 10 * ms, 2 * ms) for i in range(4)]
    spans = [_span("bench.window", 0, 50 * ms),
             _span("engine.decode.dispatch", 1 * ms, 1 * ms, slots=48,
                   ctx_tokens=48 * 3000, sel_tokens=48 * 2048,
                   win_tokens=48 * 513, ticks=1)]
    traced = ps.ProgramSpans(spans, [], busy, 0.0, 50 * ms, kernels)

    class Trace:
        busy_s = 0.040
    measured = Measured(checks={}, attempted=1, failed=0, end_to_end={},
                        samples={"moe": dict(picks_held=50, picks_total=400,
                                             experts_touched=100,
                                             layer_ticks=4, held=32)},
                        trace=Trace())
    ctx = MetricContext(cell=cell, measured=measured,
                        device={"kind": "TPU v5 lite"},
                        peak=device.peak("TPU v5 lite"))
    orig = ps.for_ctx
    ps.for_ctx = lambda c: traced
    try:
        need = mla_bytes.decode_attention_bytes(cell.config, 48 * 2048,
                                                48 * 513)
        roof = load.load_metric("serve.mla_decode_roofline").compute(ctx)
        assert roof == pytest.approx(100 * need / 819e9 / 0.008)
        assert 0 < roof < 100
        share = load.load_metric("serve.mla_decode_time_share").compute(ctx)
        assert share == pytest.approx(100 * 0.008 / 0.040)
        # a program whose kernels carry another name: nothing to read
        traced.kernels = [dataclasses.replace(k, name="%paged_decode.8 = x")
                          for k in kernels]
        assert load.load_metric(
            "serve.mla_decode_time_share").compute(ctx) is None
    finally:
        ps.for_ctx = orig
    assert load.load_metric("serve.moe_held_pick_share").compute(ctx) == 12.5
    assert load.load_metric("serve.moe_experts_touched_share").compute(
        ctx) == pytest.approx(100 * 100 / (32 * 4))


def test_the_new_metrics_find_nothing_in_a_run_that_lacks_them():
    cell = load.load_cell(CELL)
    measured = Measured(checks={}, attempted=0, failed=0, end_to_end={},
                        samples={}, trace=None)
    ctx = MetricContext(cell=cell, measured=measured, device={}, peak={})
    for name in ("serve.mla_decode_roofline", "serve.mla_decode_time_share",
                 "serve.moe_held_pick_share",
                 "serve.moe_experts_touched_share"):
        assert load.load_metric(name).compute(ctx) is None


def test_tiny_latent_cell_end_to_end(tmp_path):
    root = tiny.tiny_tree(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="tiny-latent", source="test",
                                 reduced=[], why="test",
                                 file="benchmark/configs/tiny-latent.json"))
    bench["workloads"].append(dict(name="tiny-latent", config="tiny-latent",
                                   traffic="tiny-latent", chips=1,
                                   why="test"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-serve" in m.get("workloads", []):
            m["workloads"].append("tiny-latent")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "benchmark/configs/tiny-latent.json").write_text(
        json.dumps(TINY_LATENT))
    (root / "benchmark/traffic/tiny-latent.json").write_text(
        json.dumps(TINY_TRAFFIC))
    cell = load.load_cell("tiny-latent", root)
    job = Job(cell=cell, seed=2 ** 31 + 77, seconds=1.0, trace=False,
              trace_dir=str(root / "trace"),
              process_start=time.perf_counter(), device=device.describe())
    measured = load.load_runner("serve_latent").run(job)
    assert measured.correct, measured.checks
    assert set(measured.checks) == {
        "reference", "all_requests_ok", "paged_mla_pallas_decode",
        "no_compile_in_window", "no_leaked_pages"}
    moe = measured.samples["moe"]
    assert 0 < moe["picks_held"] < moe["picks_total"] and moe["held"] == 4
    line = runpy.result_line(job, measured)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"setup_s", "serve_tokens_per_s"} <= set(line["metrics"])
    traced = runpy.result_line(
        dataclasses.replace(job, trace=True,
                            device=dict(job.device, kind="TPU v5 lite")),
        measured)
    assert {"serve.moe_held_pick_share",
            "serve.moe_experts_touched_share"} <= set(traced["metrics"])
