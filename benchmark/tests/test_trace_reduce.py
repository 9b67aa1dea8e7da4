"""The reduction on a recording: two training steps of
`train-mistral7b-4l-s4096` traced on a TPU v5e (PR 25's first traced chip
run), cut to the operations of 3.3 ms and more, the 24 Pallas kernel calls,
the loops and the small programs between the steps, with every HLO text
shortened to `%name = shape opcode(...)`. Dropping the short operations
leaves holes, so its idle share is (18%) far above the real run's 1%: the
test holds the arithmetic, not the chip."""
import pathlib

import pytest

from benchmark.harness import trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data" / \
    "v5e_train_two_steps.events.json"
PALLAS = ('custom_call_target="tpu_custom_call"',)


@pytest.fixture(scope="module")
def events():
    return tr.load_events(str(DATA))


@pytest.fixture(scope="module")
def reduced(events):
    return tr.Reduced(events, chips=1)


def _brute_busy_ns(events, lo, hi, step=20_000):
    """Busy time by sampling the window every 20 us: another algorithm
    than the interval union."""
    ops = [(e.start_ns, e.end_ns) for e in events
           if e.plane.startswith(tr.DEVICE_PLANE_PREFIX)]
    t, busy = lo + step / 2, 0
    while t < hi:
        busy += any(a <= t < b for a, b in ops)
        t += step
    return busy * step


def test_window_busy_and_idle(events, reduced):
    assert reduced.window_s == pytest.approx(0.864847919)
    assert reduced.busy_s == pytest.approx(0.709753166)
    assert reduced.busy_s * 1e9 == pytest.approx(
        _brute_busy_ns(events, reduced.lo, reduced.hi), rel=2e-3)
    assert reduced.idle_share == pytest.approx(
        1 - reduced.busy_s / reduced.window_s)
    assert 0 < reduced.idle_share < 1


def test_union_merges_nested_and_touching_intervals():
    assert tr.union([(0, 10), (2, 3), (10, 12), (20, 21), (5, 11)]) == \
        [(0, 12), (20, 21)]
    assert tr.total(tr.clip([(0, 12), (20, 21)], 5, 20.5)) == 7.5


def test_kernel_time_is_the_sum_of_the_pallas_calls(events, reduced):
    calls = [e for e in events if PALLAS[0] in e.name]
    assert len(calls) == 24                      # fwd + dq + dkv, 4 layers, 2 steps
    assert reduced.kernel_s(PALLAS) == pytest.approx(
        sum(e.dur_ns for e in calls) / 1e9)
    assert reduced.kernel_s(PALLAS) == pytest.approx(0.097851767)


def test_a_kernel_that_is_not_in_the_trace_is_an_error(reduced):
    with pytest.raises(tr.TraceError, match="no device operation matches"):
        reduced.kernel_s(("_no_such_kernel",))


def test_top_ops_use_self_time(events, reduced):
    top = dict(reduced.top_ops(200))
    assert sum(top.values()) == pytest.approx(reduced.busy_s)
    whole = sum(e.dur_ns for e in events
                if e.name.startswith("%while.16 ")) / 1e9
    inside = top["%while.16 while u32[]"]
    assert 0 < inside < whole                    # its body's ops took the rest
    assert reduced.top_ops(3)[0][1] >= reduced.top_ops(3)[1][1]
    assert any("tpu_custom_call" in name for name in top)


def test_short_name():
    text = ('%transpose_jvp___.9 = bf16[64,4096,128]{2,1,0:T(8,128)(2,1)} '
            'custom-call(bf16[64,4096,128]{2,1,0} %bitcast.685), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert tr.short_name(text) == \
        "%transpose_jvp___.9 tpu_custom_call bf16[64,4096,128]"
    assert tr.short_name(
        "%fusion.7 = (f32[2,4096]{1,0:T(2,128)S(1)}, bf16[2]{0}) "
        "fusion(bf16[2]{0} %p), kind=kOutput") == "%fusion.7 fusion f32[2,4096]"


def test_gaps_are_named_by_the_span_that_covers_them(reduced):
    gaps = reduced.idle_gaps(5)
    assert len(gaps) == 5
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert gaps[0] == ["bench.fetch_loss", pytest.approx(0.011696469)]
    # while the device waits between two steps the host is in next_batch
    # or dispatching: the first gap of the window starts before any span
    names = {g[0] for g in reduced.idle_gaps(1000)}
    assert "bench.fetch_loss" in names and len(names) > 1
    assert sum(g[1] for g in reduced.idle_gaps(10 ** 6)) == pytest.approx(
        reduced.window_s - reduced.busy_s)


def test_a_window_with_no_device_op_is_an_error(events):
    host_only = [e for e in events if e.plane == tr.HOST_PLANE]
    with pytest.raises(tr.TraceError):
        tr.Reduced(host_only, chips=1)
    with pytest.raises(tr.TraceError, match="device planes"):
        tr.Reduced(events, chips=4)


def test_breakdown_shape(reduced):
    b = reduced.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 5
    assert all(isinstance(n, str) and s > 0 for n, s in b["device_ops"])
