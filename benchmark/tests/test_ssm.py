"""The state-space cell's files: the configuration keeps every published
width (only the `reduced` keys differ from the catalog row), the traffic
mix says what the issue asked for, `ssd_bytes.py` and
`paged_gqa_bytes.py` match a hand count, the three metric files compute
on made-up spans and op times and find nothing in a run that lacks them,
the cell's entries in `BENCHMARK.json`,
and the runner `serve_ssm` goes end to end at a tiny size on the CPU."""
import dataclasses
import json
import os
import time

import pytest

from benchmark import run as runpy
from benchmark.harness import device, load, paged_gqa_bytes, ssd_bytes
from benchmark.harness import program_spans as ps
from benchmark.harness.job import Job, Measured, MetricContext
from benchmark.harness.trace_reduce import Event
from benchmark.tests import tiny

CELL = "serve-falconh1-6l-reply96"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("serve.ssd_decode_roofline", "serve.ssd_decode_time_share",
               "serve.paged_gqa_decode_roofline")
STEP_RECORD = {"serve.host_ms_per_step", "serve.step_ms_p99",
               "serve.slow_step_ms", "serve.backlog_lanes_p90"}
FOUR = ["serve-mistral7b-8l-chat48", "serve-dots3-5l-notes48",
        "serve-solar2-4l-chat96", "serve-kexaone-5l-mixed128"]

TINY_SSM = dict(
    vocab_size=96, hidden_size=64, intermediate_size=96,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, max_position_embeddings=256, rms_norm_eps=1e-5,
    rope_theta=100000000000, mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
    mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8,
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    attention_in_multiplier=1, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
    prefill_query_block=8, serving=dict(weight_dtype="float32"),
    engine=dict(max_slots=4, page_size=8, prefill_bucket=8, max_context=64,
                cache_dtype="auto", max_prefill_tokens_per_step=16,
                keep_logits=True))
TINY_TRAFFIC = dict(
    runner="serve_ssm", arrival=dict(kind="closed", clients=4),
    prompt_tokens=[12, 40], output_tokens=[4, 12], shared_prefix_tokens=0,
    block=16, ramp_seconds=0.2, steady_seconds=0.5, traced_seconds=0.5,
    reference_prompt_tokens=24, reference_new_tokens=4)


def test_the_cell_is_the_one_the_issue_names():
    cell = load.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == \
        ("falcon-h1-34b-serve-6l", "reply96", 1)
    t, c = cell.traffic, cell.config
    assert t["runner"] == "serve_ssm"
    assert t["arrival"] == {"kind": "closed", "clients": 96}
    assert t["prompt_tokens"] == [128, 1024]
    assert t["output_tokens"] == [256, 768]
    assert t["shared_prefix_tokens"] == 0
    assert (t["block"], t["ramp_seconds"], t["steady_seconds"],
            t["traced_seconds"]) == (128, 4, 10, 3)
    assert (t["reference_prompt_tokens"], t["reference_new_tokens"]) == \
        (2560, 8)
    e = c["engine"]
    assert e == dict(max_slots=96, page_size=128, prefill_bucket=256,
                     max_context=2688, cache_dtype="auto",
                     max_prefill_tokens_per_step=2048, keep_logits=True)
    assert e["max_slots"] == t["arrival"]["clients"]
    # every timed prompt fits one prefill program, the reference request two
    assert max(t["prompt_tokens"]) <= e["max_prefill_tokens_per_step"] \
        < t["reference_prompt_tokens"]
    assert t["reference_prompt_tokens"] + t["reference_new_tokens"] <= \
        e["max_context"]
    assert c["serving"] == {"weight_dtype": "bfloat16"}


def test_the_cells_entries_in_benchmark_json():
    bench = json.loads((load.REPO_ROOT / "BENCHMARK.json").read_text())
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "falcon-h1-34b-serve-6l"
    assert bench["configs"][-1]["reduced"] == ["num_hidden_layers",
                                               "vocab_size"]
    cell = load.load_cell(CELL)
    assert set(cell.end_to_end) == {"serve_tokens_per_s", "setup_s"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert bench["per_layer"].index(by_name[name]) >= \
            len(bench["per_layer"]) - 3
        m = load.load_metric(name)
        assert {k: by_name[name][k] for k in
                ("unit", "better", "source", "layer", "moves")} == dict(
            unit=m.UNIT, better=m.BETTER, source=m.SOURCE, layer=m.LAYER,
            moves=m.MOVES)
    # appended to the lists of all four serving cells but the step
    # record's four (`test_step_record.py` holds those to exactly the four
    # older cells; PERF.md section 7), to the state's gauge and the paged
    # kernel's time share; to nothing else
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    all_four = {m["name"] for m in bench["per_layer"]
                if m.get("workloads", [])[:4] == FOUR}
    assert len(all_four) == 18
    assert listed == (all_four - STEP_RECORD) | {
        "serve.state_gb", "serve.gqa_decode_time_share"} | set(NEW_METRICS)
    assert listed == set(cell.per_layer)
    assert all(by_name[n]["moves"] == "serve_tokens_per_s" for n in listed)
    assert all(by_name[n]["workloads"][-1] == CELL for n in listed)


def test_the_configuration_keeps_every_published_width():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    c = load.load_cell(CELL).config
    row = next(json.loads(line) for line in open(CATALOG)
               if '"Falcon-H1-34B-Instruct"' in line)
    assert c["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if c.get(k) != v}
    assert changed == set(c["reduced"]) == {"num_hidden_layers",
                                            "vocab_size"}
    assert c["published"] == {k: row["config"][k] for k in changed}
    assert c["num_hidden_layers"] == 6
    assert c["vocab_size"] * 8 == row["config"]["vocab_size"]
    # and the model class takes them as they stand
    cfg = load.load_runner("serve_ssm").model_config(c)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.dtype) == (32, 128, 256, 2, 20, 4,
                                                    "bfloat16")
    assert cfg.ssm_multipliers == tuple(row["config"]["ssm_multipliers"])


def test_ssd_bytes_match_a_hand_count():
    c = load.load_cell(CELL).config
    assert ssd_bytes.state_bytes_per_slot_layer(c) == 4 * 32 * 128 * 256
    # a slot a block: S in and out; x and y rows of 128 a head, dt and the
    # decay a head, B and C rows of 256 a group: float32
    rows = 4 * (2 * 32 * 128 + 2 * 32 + 2 * 2 * 256)
    assert ssd_bytes.decode_bytes(c, 1) == 6 * (2 * 4194304 + rows)
    assert ssd_bytes.decode_bytes(c, 96) == 96 * ssd_bytes.decode_bytes(c, 1)
    assert ssd_bytes.decode_bytes(c, 0) == 0
    # the issue's arithmetic: 4.83 GB of state a tick at 96 lanes
    assert round(96 * 6 * 2 * 4194304 / 1e9, 2) == 4.83


def test_paged_gqa_bytes_match_a_hand_count():
    c = load.load_cell(CELL).config
    # K + V, 4 heads of 128 (the configuration's head_dim, not 5120 / 20),
    # bfloat16, six blocks
    assert paged_gqa_bytes.kv_bytes_per_token(c) == 6 * 2 * 4 * 128 * 2
    # 96 lanes at 830 tokens each, plus the token each has just written
    assert paged_gqa_bytes.decode_bytes(c, 96 * 830, 96) == \
        12288 * 96 * 831
    assert paged_gqa_bytes.decode_bytes(c, 0, 0) == 0


def _span(name, t0, dur, **stats):
    return ps.Span("/host:CPU", "main", name, float(t0), float(dur), stats)


STATE = "f32[96,32,128,256]{3,2,1,0:T(8,128)}"


def _op(name, t0, dur):
    return Event("/device:TPU:0", "XLA Ops", name, float(t0), float(dur))


def test_the_kernel_metrics_compute_on_made_up_spans_and_op_times():
    cell = load.load_cell(CELL)
    ms = 1e6
    busy = [(0.0, 40 * ms)]
    # six decode programs of 5 ms; in each a state update of 1.5 ms (a
    # fusion that gives S' and y), a paged_decode call of 0.5 ms that
    # names no state, and a fusion that only READS a state (a second
    # pass) of 0.1 ms; then a prefill program that writes a slot's rows
    ops, modules = [], []
    for i in range(6):
        t0 = i * 5 * ms
        modules.append(Event("/device:TPU:0", "XLA Modules",
                             "jit_serve_decode_greedy(1)", t0, 5 * ms))
        ops += [_op(f"%fusion.{i} = ({STATE}, f32[96,32,128]{{2,1,0}}) "
                    f"fusion({STATE} %caches_1__0_.1, f32[96,32,256] %b)",
                    t0 + 1 * ms, 1.5 * ms),
                _op(f"%paged_decode.{i} = bf16[96,4,5,128]{{3,2,1,0}} "
                    f"custom-call(s32[96,22] %c), custom_call_target="
                    f'"tpu_custom_call"', t0 + 3 * ms, 0.5 * ms),
                _op(f"%fusion.9{i} = f32[96,32,128]{{2,1,0}} "
                    f"fusion({STATE} %fusion.{i})", t0 + 4 * ms, 0.1 * ms)]
    modules.append(Event("/device:TPU:0", "XLA Modules",
                         "jit_serve_prefill_1024(2)", 30 * ms, 5 * ms))
    ops.append(_op(f"%dynamic-update-slice.3 = {STATE} "
                   f"dynamic-update-slice({STATE} %p, f32[1,32,128,256] %u)",
                   31 * ms, 0.7 * ms))
    kernels = [o for o in ops if ps.PALLAS_CALL in o.name]
    spans = [_span("bench.window", 0, 50 * ms),
             _span("engine.decode.dispatch", 1 * ms, 1 * ms, slots=90,
                   ctx_tokens=90 * 800, ticks=1, state_slots=90)]
    traced = ps.ProgramSpans(spans, modules, busy, 0.0, 50 * ms, kernels)

    class Trace:
        busy_s = 0.040
        planes = ["/device:TPU:0"]
        _ops = {"/device:TPU:0": ops}
    measured = Measured(checks={}, attempted=1, failed=0, end_to_end={},
                        samples={"state": dict(bytes=2433613824, resets=5,
                                               recomputes=0)},
                        trace=Trace())
    ctx = MetricContext(cell=cell, measured=measured,
                        device={"kind": "TPU v5 lite"},
                        peak=device.peak("TPU v5 lite"))
    orig = ps.for_ctx
    ps.for_ctx = lambda c: traced
    try:
        # every pass over a state inside a decode program, none outside
        took_s = 6 * (1.5 + 0.1) / 1e3
        assert ssd_bytes.state_update_s(ctx, traced) == pytest.approx(took_s)
        need = ssd_bytes.decode_bytes(cell.config, 90)
        roof = load.load_metric(NEW_METRICS[0]).compute(ctx)
        assert roof == pytest.approx(100 * need / 819e9 / took_s)
        assert 0 < roof < 100
        share = load.load_metric(NEW_METRICS[1]).compute(ctx)
        assert share == pytest.approx(100 * took_s / 0.040)
        paged = load.load_metric(NEW_METRICS[2]).compute(ctx)
        assert paged == pytest.approx(
            100 * 12288 * (90 * 800 + 90) / 819e9 / 0.003)
        # the solar cell's kernel metric does not take these ops for its own
        assert load.load_metric("serve.kda_decode_roofline").compute(ctx) \
            is None
        # a program whose ops name no whole state array, or whose spans
        # lack the argument: nothing to read
        Trace._ops = {"/device:TPU:0": [
            dataclasses.replace(o, name=o.name.replace("f32[96,", "f32[95,"))
            for o in ops]}
        for name in NEW_METRICS[:2]:
            assert load.load_metric(name).compute(ctx) is None
        Trace._ops = {"/device:TPU:0": ops}
        spans[1].stats.pop("state_slots")
        assert load.load_metric(NEW_METRICS[0]).compute(ctx) is None
        traced.kernels = []
        assert load.load_metric(NEW_METRICS[2]).compute(ctx) is None
    finally:
        ps.for_ctx = orig
    assert load.load_metric("serve.state_gb").compute(ctx) == \
        pytest.approx(2.43, abs=0.01)


def test_the_new_metrics_find_nothing_in_a_run_that_lacks_them():
    cell = load.load_cell(CELL)
    measured = Measured(checks={}, attempted=0, failed=0, end_to_end={},
                        samples={}, trace=None)
    ctx = MetricContext(cell=cell, measured=measured, device={}, peak={})
    for name in NEW_METRICS:
        assert load.load_metric(name).compute(ctx) is None


def test_tiny_ssm_cell_end_to_end(tmp_path):
    root = tiny.tiny_tree(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="tiny-ssm", source="test",
                                 reduced=[], why="test",
                                 file="benchmark/configs/tiny-ssm.json"))
    bench["workloads"].append(dict(name="tiny-ssm", config="tiny-ssm",
                                   traffic="tiny-ssm", chips=1, why="test"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-serve" in m.get("workloads", []):
            m["workloads"].append("tiny-ssm")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "benchmark/configs/tiny-ssm.json").write_text(
        json.dumps(TINY_SSM))
    (root / "benchmark/traffic/tiny-ssm.json").write_text(
        json.dumps(TINY_TRAFFIC))
    cell = load.load_cell("tiny-ssm", root)
    job = Job(cell=cell, seed=2 ** 31 + 77, seconds=1.0, trace=False,
              trace_dir=str(root / "trace"),
              process_start=time.perf_counter(), device=device.describe())
    measured = load.load_runner("serve_ssm").run(job)
    assert measured.correct, measured.checks
    assert set(measured.checks) == {
        "reference", "all_requests_ok", "paged_pallas_decode",
        "no_compile_in_window", "no_leaked_pages"}
    state = measured.samples["state"]
    # 4 slots x 2 blocks x (S [4, 8, 16] + tail [3, 96]) float32
    assert state["bytes"] == 4 * 2 * 4 * (4 * 8 * 16 + 3 * 96)
    assert state["resets"] > 0 and state["recomputes"] == 0
    line = runpy.result_line(job, measured)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"setup_s", "serve_tokens_per_s"} <= set(line["metrics"])
    traced = runpy.result_line(
        dataclasses.replace(job, trace=True,
                            device=dict(job.device, kind="TPU v5 lite")),
        measured)
    assert "serve.state_gb" in traced["metrics"]
    assert not set(NEW_METRICS) & set(traced["metrics"])
