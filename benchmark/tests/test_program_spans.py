"""`harness/program_spans.py`: device-idle time by the program's own host
spans and program time by name. The rule and the arithmetic on hand-made
events; the whole reduction on a recording of the serve cell on a TPU v5e:
`data/v5e_serve_spans.events.json`, cut from PR 26's traced chip run of
`serve-mistral7b-8l-chat48` to three `Engine.step()`s around one 768-token
prefill (157.7 ms; `bench.window` is cut to that stretch and times count from
its start). Every host span with its arguments and every program execution
is kept; the ~2,400 device operations are kept as the 11 stretches in which
they run back to back (gaps under 1 us closed: 8 us of busy time), plus the 24
Pallas calls and 8 operations that consume a kernel's output, HLO texts
shortened. So the idle arithmetic is the chip's; the sizes are not the whole
run's (a prefill in one of three ticks, where the cell has one in five)."""
import json
import pathlib
import types

import pytest

from benchmark.harness import paged_bytes, program_spans as ps
from benchmark.harness import trace_reduce as tr
from benchmark.harness.load import load_metric

DATA = pathlib.Path(__file__).parent / "data" / "v5e_serve_spans.events.json"
DEV = "/device:TPU:0"
T = "main"


def span(name, start, dur, line=T, **stats):
    return ps.Span(tr.HOST_PLANE, line, name, float(start), float(dur), stats)


def module(name, start, dur):
    return tr.Event(DEV, ps.MODULES_LINE, name, float(start), float(dur))


#   0        10        20        30        40        50        60   ...  100
#   |-------------------------- bench.window ---------------------------|
#      |----------------- bench.engine_step 5..65 -----------|
#        |-------------- engine.step 8..62 ----------------|
#          |- dispatch 10..30 -|  |prefill 32..50|  |wait 52..58|
#            |flush 12..18|          |p.wait 40..48|
NEST = [
    span(tr.WINDOW_SPAN, 0, 100),
    span("bench.engine_step", 5, 60),
    span("engine.step", 8, 54, step=1),
    span("engine.decode.dispatch", 10, 20, ctx_tokens=100, slots=2, ticks=1),
    span("engine.flush_state", 12, 6),
    span("engine.prefill", 32, 18, req=7, bucket=256),
    span("engine.prefill.wait", 40, 8),
    span("engine.decode.wait", 52, 6),
    span("engine.step", 70, 10, line="another thread"),
]


def test_innermost_span_rule_on_a_hand_made_nest():
    segs = ps.innermost_segments([s for s in NEST[1:] if s.line == T], 0, 100)
    assert segs == [
        (0, 5, ps.NO_SPAN), (5, 8, "bench.engine_step"),
        (8, 10, "engine.step"), (10, 12, "engine.decode.dispatch"),
        (12, 18, "engine.flush_state"), (18, 30, "engine.decode.dispatch"),
        (30, 32, "engine.step"), (32, 40, "engine.prefill"),
        (40, 48, "engine.prefill.wait"), (48, 50, "engine.prefill"),
        (50, 52, "engine.step"), (52, 58, "engine.decode.wait"),
        (58, 62, "engine.step"), (62, 65, "bench.engine_step"),
        (65, 100, ps.NO_SPAN)]
    # they tile the window
    assert sum(b - a for a, b, _ in segs) == 100
    # a sub-window clips, and a span that ends before it is skipped
    assert ps.innermost_segments(NEST[1:8], 45, 53) == [
        (45, 48, "engine.prefill.wait"), (48, 50, "engine.prefill"),
        (50, 52, "engine.step"), (52, 53, "engine.decode.wait")]


def test_idle_goes_to_the_innermost_span_on_the_window_thread():
    busy = [(14, 34), (44, 56), (56, 57), (80, 90)]     # touching: merged
    got = ps.ProgramSpans(NEST, [], busy, 0, 100)
    assert got.thread == T
    by = got.idle_by_span()
    ns = 1e-9
    assert by == pytest.approx({
        ps.NO_SPAN: (5 + 15 + 10) * ns,          # 0-5, 65-80, 90-100
        "bench.engine_step": (3 + 3) * ns,
        "engine.step": (2 + 0 + 0 + 4) * ns,     # 8-10 and 58-62
        "engine.decode.dispatch": 2 * ns,        # 10-12
        "engine.flush_state": 2 * ns,            # 12-14
        "engine.prefill": 6 * ns,                # 34-40
        "engine.prefill.wait": 4 * ns,           # 40-44
        "engine.decode.wait": 1 * ns,            # 57-58
    })
    # the other thread's engine.step (70-80) claims nothing
    assert sum(by.values()) == pytest.approx(got.idle_s)
    assert got.idle_s == pytest.approx((100 - 43) * ns)
    buckets = got.idle_by_bucket()
    assert set(buckets) == set(ps.BUCKET_NAMES)
    assert sum(buckets.values()) == pytest.approx(got.idle_s)
    assert buckets["dispatch"] == pytest.approx(4 * ns)
    assert buckets["wait"] == pytest.approx(5 * ns)
    assert buckets["outside_step"] == pytest.approx(36 * ns)
    assert buckets["schedule"] == pytest.approx(6 * ns)
    # 57-80 (15 of its 23 under no span), then 0-14; 34-44 lies under
    # engine.prefill (6) and its wait (4)
    assert got.longest_gaps(3) == [[ps.NO_SPAN, pytest.approx(23 * ns)],
                                   [ps.NO_SPAN, pytest.approx(14 * ns)],
                                   ["engine.prefill", pytest.approx(10 * ns)]]


def test_every_span_name_has_a_bucket():
    assert ps.bucket_of("engine.something_new") == "schedule"
    assert ps.bucket_of("bench.admit") == "outside_step"
    assert ps.bucket_of(ps.NO_SPAN) == "outside_step"
    assert set(ps.BUCKETS.values()) | {"outside_step"} == set(ps.BUCKET_NAMES)


def test_a_trace_without_program_spans_raises_never_a_zero():
    bench_only = [s for s in NEST if s.name.startswith("bench.")]
    with pytest.raises(tr.TraceError, match="engine"):
        ps.ProgramSpans(bench_only, [], [(10, 20)], 0, 100)
    with pytest.raises(tr.TraceError, match="bench.window"):
        ps.ProgramSpans(NEST[1:], [], [(10, 20)], 0, 100)


def test_program_medians_and_busy_time_by_name():
    mods = [module("jit_serve_decode_greedy(123)", 10, 30),
            module("jit_serve_decode_greedy(123)", 50, 32),
            module("jit_serve_decode_greedy(123)", 90, 40),   # runs past hi
            module("jit_serve_prefill_256(9)", 41, 8),
            module("jit_serve_prefill_1024(8)", 82, 6),
            module("jit__merge_rows(5)", 49, 1),
            module("jit_serve_decode_greedy(123)", 100, 30)]  # begins at hi
    busy = [(10, 40), (41, 49), (50, 82), (82, 88), (90, 100)]
    got = ps.ProgramSpans(NEST, mods, busy, 0, 100)
    assert got.program_ms_p50("serve_decode_") == pytest.approx(32e-6)
    assert got.program_ms_p50("serve_prefill_") == pytest.approx(7e-6)
    assert got.program_ms_p50("serve_multi_") is None
    assert got.program_names() == ["jit__merge_rows",
                                   "jit_serve_decode_greedy",
                                   "jit_serve_prefill_1024",
                                   "jit_serve_prefill_256"]
    assert ps.decode_ticks(got) == 3
    assert got.program_busy_s("serve_prefill_") == pytest.approx(14e-9)
    # clipped to the window's end
    assert got.program_busy_s("serve_decode_") == pytest.approx(72e-9)


def test_paged_bytes_from_the_configuration():
    cfg = dict(hidden_size=4096, num_attention_heads=32,
               num_key_value_heads=8, num_hidden_layers=8,
               serving=dict(weight_dtype="bfloat16"),
               engine=dict(cache_dtype="auto"))
    assert paged_bytes.kv_bytes_per_token(cfg) == 2 * 8 * 8 * 128 * 2
    cfg["engine"]["cache_dtype"] = "int8"
    assert paged_bytes.kv_bytes_per_token(cfg) == 2 * 8 * 8 * 128
    assert paged_bytes.decode_context_tokens(1000, 48) == 1048
    # 3 fused ticks: 1048 + 1096 + 1144
    assert paged_bytes.decode_context_tokens(1000, 48, 3) == 3288


def _ctx(trace, name="no-such-cell"):
    cell = types.SimpleNamespace(name=name, chips=1, config={})
    return types.SimpleNamespace(trace=trace, cell=cell, samples={}, peak={})


# the metric files that read this module
SPAN_METRICS = sorted(
    p.stem for p in (pathlib.Path(ps.__file__).parents[1] / "metrics").glob(
        "*.py") if "program_spans" in p.read_text())


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_an_untraced_run_leaves_the_metric_out(metric):
    assert load_metric(metric).compute(_ctx(None)) is None


# -- the recording ------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    events, spans, modules, kernels = ps.load_recording(str(DATA))
    red = tr.Reduced(events, chips=1)
    return red, ps.ProgramSpans(spans, modules, red._busy(red.planes[0]),
                                red.lo, red.hi, kernels)


def test_recorded_buckets_add_up_to_the_idle_total(recorded):
    red, got = recorded
    assert red.window_s == pytest.approx(0.157691635)
    assert got.idle_s == pytest.approx(red.window_s - red.busy_s)
    assert got.idle_s == pytest.approx(0.019228772)
    by_span = got.idle_by_span()
    assert sum(by_span.values()) == pytest.approx(got.idle_s, rel=1e-9)
    buckets = got.idle_by_bucket()
    assert sum(buckets.values()) == pytest.approx(got.idle_s, rel=1e-9)
    assert buckets == pytest.approx({
        "dispatch": 0.005024169, "prefill_host": 0.001728051,
        "wait": 0.006477966, "harvest": 0.003933585,
        "bookkeeping": 0.0002173, "schedule": 0.00150301,
        "outside_step": 0.000344691})
    # the gap has parts: under no span at all lies a quarter of a percent
    assert by_span[ps.NO_SPAN] < 0.005 * got.idle_s
    assert [g[0] for g in got.longest_gaps(3)] == [
        "engine.prefill.wait", "engine.flush_state", "engine.decode.wait"]


def test_recorded_programs_by_name(recorded):
    _, got = recorded
    assert got.program_names() == [
        "jit__merge_rows", "jit__threefry_seed", "jit_convert_element_type",
        "jit_serve_decode_greedy", "jit_serve_prefill_768"]
    assert not [n for n in got.program_names() if "body" in n]
    # two decode programs BEGIN in the stretch; a third began 0.85 ms before
    assert ps.decode_ticks(got) == 2
    assert got.program_ms_p50("serve_decode_") == pytest.approx(31.0391395)
    assert got.program_ms_p50("serve_prefill_") == pytest.approx(46.234894)
    assert got.program_busy_s("serve_prefill_") == pytest.approx(0.046232531)


def test_recorded_kernel_is_found_by_its_instruction_name(recorded):
    red, got = recorded
    assert len(got.kernels) == 24                   # 8 layers x 3 programs
    assert got.kernel_s("paged_decode") == pytest.approx(0.010123551)
    # no kernel of that name: every Pallas call of the trace
    assert got.kernel_s("flash_fwd") == got.kernel_s("paged_decode")
    # under jax.grad the transformations wrap the name (seen in the train cell)
    wrapped = [tr.Event(DEV, tr.OPS_LINE, f"%{n}.4 = bf16[2,4096,32,128] "
                        f"custom-call(...), {ps.PALLAS_CALL}", 10.0 * i, 5.0)
               for i, n in enumerate(("jvp_flash_fwd_",
                                      "transpose_jvp_flash_dq__",
                                      "transpose_jvp_flash_dkv__"))]
    train = ps.ProgramSpans(NEST, [], [(0, 30)], 0, 100, wrapped)
    assert train.kernel_s("flash_dq") == pytest.approx(5e-9)
    assert train.kernel_s("flash_d") == pytest.approx(10e-9)
    # the whole-text substring also counts `reshape(... %paged_decode.8)`
    assert red.kernel_s(("paged_decode",)) > got.kernel_s("paged_decode")
    assert red.kernel_s((ps.PALLAS_CALL,)) == pytest.approx(
        got.kernel_s("paged_decode"))


def test_recorded_span_arguments_and_the_roofline_arithmetic(recorded):
    _, got = recorded
    dispatches = got.named("engine.decode.dispatch")
    assert [s.stats["slots"] for s in dispatches] == [48, 47, 48]
    tokens = sum(paged_bytes.decode_context_tokens(
        s.stats["ctx_tokens"], s.stats["slots"]) for s in dispatches)
    assert tokens == 169636
    prefill, = got.named("engine.prefill")
    assert prefill.stats == {"req": 265, "bucket": 768, "tokens": 752,
                             "start": 0, "final": 1}
    # 32 KiB a token over 8 layers at 819 GB/s against the kernel's time
    share = tokens * 32768 / 819e9 / got.kernel_s("paged_decode")
    assert 0.6 < share < 0.75


def test_recorded_device_clock_runs_ahead_of_the_host_clock(recorded):
    _, got = recorded
    at_least, at_most = got.device_clock_early_ms()
    # a decode program begins 0.77 ms BEFORE the call that dispatches it
    assert at_least == pytest.approx(0.769163)
    assert at_most == pytest.approx(2.115834)


# -- from the xplane to the metric files --------------------------------------

class _FakeProfile:
    """What `jax.profiler.ProfileData.from_file` gives, rebuilt from the
    recording: planes > lines > events with name, start_ns, duration_ns and
    stats as (key, value) pairs."""

    def __init__(self, rows):
        planes = {}
        for row in rows:
            planes.setdefault(row[0], {}).setdefault(row[1], []).append(
                types.SimpleNamespace(
                    name=row[2], start_ns=row[3], duration_ns=row[4],
                    stats=list((row[5] if len(row) > 5 else {}).items())))
        self.planes = [types.SimpleNamespace(
            name=p, lines=[types.SimpleNamespace(name=ln, events=evs)
                           for ln, evs in lines.items()])
            for p, lines in planes.items()]


@pytest.fixture
def traced_ctx(monkeypatch):
    import jax.profiler
    rows = json.loads(DATA.read_text())
    # a second chip's plane and a foreign host event must change nothing
    rows.append(["/device:TPU:1", ps.MODULES_LINE, "jit_serve_decode_greedy(1)",
                 5.0, 7.0])
    rows.append([tr.HOST_PLANE, "python3", "PjitFunction(serve_decode)", 1, 2])
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: _FakeProfile(rows)))
    monkeypatch.setattr(ps, "find_xplane", lambda trace_dir: trace_dir)
    events, *_ = ps.load_recording(str(DATA))
    from benchmark.harness import device, load
    cell = load.load_cell("serve-mistral7b-8l-chat48")
    measured = types.SimpleNamespace(samples={}, trace=tr.Reduced(events, 1))
    from benchmark.harness.job import MetricContext
    return MetricContext(cell=cell, measured=measured,
                         device={"kind": "TPU v5 lite"},
                         peak=device.peak("TPU v5 lite"))


def test_the_metric_files_read_the_xplane_end_to_end(traced_ctx, capsys):
    values = {m: load_metric(m).compute(traced_ctx) for m in SPAN_METRICS
              if m.startswith("serve.")}
    assert len(values) == 12 and None not in values.values()
    total = values.pop("serve.idle_ms_per_tick")
    assert total == pytest.approx(19.228772 / 2)         # two decode ticks
    assert sum(v for k, v in values.items() if k.startswith("serve.idle_")) \
        == pytest.approx(total, rel=1e-9)
    assert values["serve.decode_program_ms_p50"] == pytest.approx(31.0391395)
    assert values["serve.prefill_program_ms_p50"] == pytest.approx(46.234894)
    assert values["serve.prefill_device_share"] == pytest.approx(
        100 * 0.046232531 / 0.138462863)
    assert values["serve.paged_decode_roofline"] == pytest.approx(
        100 * 169636 * 32768 / 819e9 / 0.010123551)
    # one info line a run, however many metrics read the trace
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert len(lines) == 1
    info = json.loads(lines[0])
    assert info["info"] == "idle_by_span" and len(info["longest_gaps"]) == 5
    assert info["programs"]["jit_serve_decode_greedy"] == 2
    assert info["device_clock_early_ms"] == pytest.approx([0.769163, 2.115834])


def test_a_program_without_spans_leaves_every_new_metric_out(traced_ctx,
                                                             monkeypatch):
    import jax.profiler
    rows = [r for r in json.loads(DATA.read_text())
            if r[0] != tr.HOST_PLANE or r[2].startswith("bench.")]
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: _FakeProfile(rows)))
    for m in SPAN_METRICS:
        assert load_metric(m).compute(traced_ctx) is None, m
