"""Tick spans (docs/OBSERVABILITY.md "Tick spans"): what the host does
inside ``Engine.step()`` and ``TrainStep.__call__`` is named on the
profiler's clock through ``profiler.RecordEvent`` — recorded while a
``Profiler`` or a ``jax.profiler`` trace runs, inert otherwise — the
engine's XLA programs carry names, and the per-token gap is counted
where tokens are appended (``serving.hist.itl_ms``)."""
import glob

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference.engine import Engine, SamplingParams
from paddle_tpu.profiler import Profiler, RecordEvent, chrome_trace
from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

# span -> the arguments the table in docs/OBSERVABILITY.md names
STEP_SPANS = {
    "engine.step": {"step", "active", "waiting", "prefilling"},
    "engine.decode.dispatch": {"variant", "slots", "ctx_tokens", "ticks",
                               "inflight"},
    "engine.flush_state": {"rows", "block_table"},
    "engine.expire": set(),
    "engine.admit": {"admitted"},
    "engine.prefill": {"req", "bucket", "tokens", "start", "final"},
    "engine.decode.wait": set(),
    "engine.harvest": {"tokens", "finished"},
    "engine.ensure_pages": {"allocated", "preempted"},
    "engine.bookkeeping": set(),
}
PARENT = {"engine.flush_state": "engine.decode.dispatch"}


def _net(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab=64, hidden=64, layers=2, heads=4)
    cfg.use_flash_attention = False
    net = LlamaForCausalLM(cfg)
    net.eval()
    return net


def _engine(**kw):
    return Engine(_net(), max_slots=2, page_size=8, pool_pages=64,
                  max_context=64, **kw)


def _prompt(n, lo=1):
    return np.arange(lo, lo + n, dtype=np.int64)


@pytest.fixture(scope="module")
def one_step():
    """One step() that holds a decode dispatch AND a prefill, recorded
    by a Profiler: (rows of the host store, outputs of the whole run,
    the engine)."""
    eng = _engine()
    first = eng.add_request(_prompt(5), SamplingParams(max_new_tokens=6))
    eng.step()                                   # warm: prefill + compile
    eng.step()                                   # warm: decode compile
    with Profiler(timer_only=True) as prof:
        second = eng.add_request(_prompt(7, 2),
                                 SamplingParams(max_new_tokens=3))
        outs = eng.step()
    rows = list(prof._store.events)
    while not eng.idle:
        outs += eng.step()
    return rows, {o.req_id: o for o in outs}, (first, second)


def _by_name(rows):
    out = {}
    for name, t0, t1, args in rows:
        out.setdefault(name, []).append((t0, t1, args))
    return out


def test_one_step_yields_every_span_with_its_args(one_step):
    rows, _, _ = one_step
    spans = _by_name(rows)
    for name, want in STEP_SPANS.items():
        assert name in spans, f"{name} missing from {sorted(spans)}"
        assert len(spans[name]) == 1, name
        assert set(spans[name][0][2]) == want, name
    t0, t1, args = spans["engine.decode.dispatch"][0]
    assert args["variant"] == "greedy" and args["slots"] == 1
    assert args["ticks"] == 1
    # the first request: 5 prompt tokens + the token decoded before
    assert args["ctx_tokens"] == 6
    assert spans["engine.admit"][0][2]["admitted"] == 1
    assert spans["engine.harvest"][0][2] == {"tokens": 1, "finished": 0}
    pf = spans["engine.prefill"][0][2]
    assert (pf["tokens"], pf["start"], pf["final"]) == (7, 0, 1)
    assert pf["bucket"] >= 7


def test_add_request_span_lies_outside_step(one_step):
    rows, _, (_, second) = one_step
    spans = _by_name(rows)
    a0, a1, args = spans["engine.add_request"][0]
    assert args == {"req": second, "prompt_tokens": 7}
    s0, _, _ = spans["engine.step"][0]
    assert a1 <= s0


def test_spans_nest_and_cover_the_step(one_step):
    rows, _, _ = one_step
    spans = _by_name(rows)
    s0, s1, _ = spans["engine.step"][0]
    children = []
    for name in STEP_SPANS:
        if name == "engine.step":
            continue
        c0, c1, _ = spans[name][0]
        assert s0 <= c0 <= c1 <= s1, name
        if name in PARENT:
            p0, p1, _ = spans[PARENT[name]][0]
            assert p0 <= c0 <= c1 <= p1, name
        else:
            children.append((c0, c1))
    # top-level children do not overlap (one thread, nested by `with`)
    children.sort()
    assert all(a[1] <= b[0] for a, b in zip(children, children[1:]))
    covered = sum(c1 - c0 for c0, c1 in children)
    assert covered >= 0.95 * (s1 - s0)


def test_prefill_span_req_joins_the_request_timeline(one_step):
    rows, outs, (_, second) = one_step
    req = _by_name(rows)["engine.prefill"][0][2]["req"]
    assert req == second
    phases = [s["phase"] for s in outs[req].spans]
    assert "PREFILL" in phases and phases[0] == "QUEUED"


def test_a_chunk_is_waited_for_behind_the_next_steps_dispatch():
    """With a tick in flight the step that dispatches a prefill chunk
    does not wait for it: the NEXT step dispatches its tick first and
    then harvests the chunk (`engine.prefill.harvest`, the wait its
    child), so the device goes from the chunk into a tick. The slot
    joins the dispatch after that."""
    eng = _engine()
    eng.add_request(_prompt(5), SamplingParams(max_new_tokens=9))
    eng.step()
    eng.step()
    second = eng.add_request(_prompt(7, 2), SamplingParams(max_new_tokens=3))
    with Profiler(timer_only=True) as prof:
        eng.step()
    first = _by_name(prof._store.events)
    assert "engine.prefill" in first
    assert "engine.prefill.wait" not in first
    assert eng.num_prefilling == 1 and len(eng._prefilled) == 1
    with Profiler(timer_only=True) as prof:
        eng.step()
    spans = _by_name(prof._store.events)
    d0, d1, dargs = spans["engine.decode.dispatch"][0]
    h0, h1, hargs = spans["engine.prefill.harvest"][0]
    w0, w1, _ = spans["engine.prefill.wait"][0]
    assert hargs == {"req": second} and dargs["slots"] == 1
    assert d1 <= h0 <= w0 <= w1 <= h1
    assert spans["engine.harvest"][0][1] <= h0 <= h1 \
        <= spans["engine.expire"][0][0]
    assert eng.num_prefilling == 0 and not eng._prefilled
    with Profiler(timer_only=True) as prof:
        eng.step()
    third = _by_name(prof._store.events)
    assert third["engine.decode.dispatch"][0][2]["slots"] == 2
    while not eng.idle:
        eng.step()


def test_a_chunk_with_no_tick_in_flight_is_waited_for_at_once():
    eng = _engine()
    eng.add_request(_prompt(5), SamplingParams(max_new_tokens=2))
    with Profiler(timer_only=True) as prof:
        eng.step()
    spans = _by_name(prof._store.events)
    assert spans["engine.prefill"][0][1] <= \
        spans["engine.prefill.harvest"][0][0]
    assert eng.num_active == 1 and not eng._prefilled
    while not eng.idle:
        eng.step()


def test_dispatch_of_tick_t_opens_before_the_wait_for_tick_t_minus_1():
    """Run-ahead: inside one step() the dispatch span (tick t, made with
    tick t-1 in flight) comes first, then `engine.decode.wait` and
    `engine.harvest`, which belong to tick t-1."""
    eng = _engine()
    eng.add_request(_prompt(5), SamplingParams(max_new_tokens=8))
    eng.step()                              # prefill
    with Profiler(timer_only=True) as prof:
        eng.step()                          # tick 1: nothing to harvest
    first = _by_name(prof._store.events)
    assert first["engine.decode.dispatch"][0][2]["inflight"] == 0
    assert "engine.decode.wait" not in first
    with Profiler(timer_only=True) as prof:
        eng.step()                          # tick 2 out, tick 1 in
    spans = _by_name(prof._store.events)
    d0, d1, args = spans["engine.decode.dispatch"][0]
    w0, w1, _ = spans["engine.decode.wait"][0]
    h0, _, hargs = spans["engine.harvest"][0]
    assert args["inflight"] == 1
    assert d0 <= d1 <= w0 <= w1 <= h0
    assert hargs == {"tokens": 1, "finished": 0}
    assert spans["engine.ensure_pages"][0][0] >= h0
    while not eng.idle:
        eng.step()


def test_dispatch_args_are_what_the_program_reads():
    """`ctx_tokens` / `slots` describe the dispatched program's input:
    the device-resident positions of the lanes alive in-graph, one tick
    past the host's mirrors while a tick is in flight, and without the
    lane the host knows to be out of budget."""
    eng = _engine()
    long_ = eng.add_request(_prompt(5), SamplingParams(max_new_tokens=12))
    short = eng.add_request(_prompt(7, 2), SamplingParams(max_new_tokens=4))
    seen = []
    for _ in range(8):
        # what the next dispatch will read (the fetch waits for the tick
        # in flight; it harvests nothing)
        pos, live, bud = (np.asarray(eng._dev[j]) for j in (1, 6, 8))
        dirty = set(eng._dirty)
        with Profiler(timer_only=True) as prof:
            eng.step()
        rows = _by_name(prof._store.events)["engine.decode.dispatch"]
        args = rows[0][2]
        if not args or dirty:
            continue            # nothing dispatched / rows merged first
        alive = (live > 0) & (bud > 0)
        assert args["slots"] == int(alive.sum())
        assert args["ctx_tokens"] == int(pos[alive].sum())
        seen.append((args["slots"], args["inflight"]))
    # both lanes, then the long one alone once the short one's budget
    # is known to be spent, all with a tick in flight
    assert (2, 1) in seen and (1, 1) in seen
    assert eng.requests.keys() == {long_}
    assert short not in eng.requests
    while not eng.idle:
        eng.step()


def test_runahead_counters_count_what_the_case_did():
    names = ("dispatches", "drains.api", "drains.preempt",
             "dead_lane_ticks")

    def read():
        snap = monitor.snapshot()
        return [int(snap.get("serving.runahead." + n, 0)) for n in names]

    c0, s0 = read(), monitor.counter("serving.steps").get()
    eng = _engine()
    rid = eng.add_request(_prompt(5), SamplingParams(max_new_tokens=6))
    outs = []
    for _ in range(4):                      # prefill, ticks 1..3
        outs += eng.step()
    # ticks 2 and 3 were dispatched behind a tick in flight
    assert [a - b for a, b in zip(read(), c0)] == [2, 0, 0, 0]
    held = len(eng.requests[rid].generated)
    out = eng.cancel(rid)                   # drains tick 3 first
    assert len(out.token_ids) == held + 1
    assert [a - b for a, b in zip(read(), c0)] == [2, 1, 0, 0]
    # an eos the host cannot foresee: its lane rides one dead tick
    ref = eng.run([(_prompt(5), SamplingParams(max_new_tokens=6))])[0]
    eos = ref.token_ids[2]
    assert eos not in ref.token_ids[:2]
    c1 = read()
    out, = eng.run([(_prompt(5), SamplingParams(max_new_tokens=6,
                                                eos_token_id=eos))])
    assert out.token_ids == ref.token_ids[:3]
    # tick 1 alone, ticks 2 and the dead tick 3 behind one in flight;
    # run() ends by harvesting the dead tick
    assert [a - b for a, b in zip(read(), c1)] == [2, 1, 0, 1]
    assert monitor.counter("serving.steps").get() > s0
    assert eng.idle and eng.leaked_pages() == 0


def _run_virtual(record):
    """Three staggered requests on a virtual clock; returns what a
    client can see of every Output."""
    vt = [0.0]
    eng = _engine(clock=lambda: vt[0])
    outs, plan = [], {0: (5, 6), 2: (9, 4), 3: (3, 5)}
    prof = Profiler(timer_only=True) if record else None
    if prof is not None:
        prof.start()
    try:
        for step in range(40):
            if step in plan:
                n, new = plan[step]
                eng.add_request(_prompt(n, step + 1),
                                SamplingParams(max_new_tokens=new))
            outs += eng.step()
            vt[0] += 0.004
    finally:
        if prof is not None:
            prof.stop()
    assert len(outs) == 3
    return [(o.req_id, o.token_ids, o.finish_reason, o.ttft_ms, o.tpot_ms,
             o.spans) for o in sorted(outs, key=lambda o: o.req_id)]


def test_recording_changes_no_token_and_no_timeline():
    assert _run_virtual(record=False) == _run_virtual(record=True)


def test_itl_histogram_counts_tokens_minus_requests():
    hist = monitor.histogram("serving.hist.itl_ms")
    tokens = monitor.counter("serving.tokens")
    h0, z0, t0 = hist.count, hist.to_dict()["zeros"], tokens.get()
    vt = [1.0]
    eng = _engine(clock=lambda: vt[0])
    news = (6, 9, 4)
    for i, new in enumerate(news):
        eng.add_request(_prompt(4 + i, i + 1),
                        SamplingParams(max_new_tokens=new))
    outs = []
    while not eng.idle:
        outs += eng.step()
        vt[0] += 0.01
    assert sorted(len(o.token_ids) for o in outs) == sorted(news)
    assert tokens.get() - t0 == sum(news)
    assert hist.count - h0 == sum(news) - len(news)
    # one token a step, the clock moves between steps: no 0 gap
    assert hist.to_dict()["zeros"] == z0


def test_extract_request_resets_the_gap_stamp():
    eng = _engine()
    rid = eng.add_request(_prompt(5), SamplingParams(max_new_tokens=8))
    for _ in range(3):
        eng.step()
    assert eng.requests[rid].last_token_t > 0.0
    req = eng.extract_request(rid)
    assert req.generated and req.last_token_t == 0.0
    assert req.first_token_t > 0.0


def _trace_events(tmp_path, work):
    """Names -> list of stats dicts of every host event of a
    jax.profiler trace around `work()`."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                seen.setdefault(ev.name, []).append(dict(ev.stats))
    return seen


def test_jax_trace_shows_span_args_and_program_names(tmp_path):
    eng = _engine()
    eng.add_request(_prompt(5), SamplingParams(max_new_tokens=4))
    eng.step()
    eng.step()

    def work():
        eng.add_request(_prompt(7, 2), SamplingParams(max_new_tokens=2))
        eng.step()

    seen = _trace_events(tmp_path, work)
    dispatch, = seen["engine.decode.dispatch"]
    assert dispatch["ctx_tokens"] == 6 and dispatch["variant"] == "greedy"
    assert seen["engine.prefill"][0]["bucket"] == eng._pbucket(7)
    names = " ".join(seen)
    assert "serve_decode_greedy" in names
    assert f"serve_prefill_{eng._pbucket(7)}" in names
    assert "jit_body" not in names and "(body)" not in names


def test_program_names_of_every_executable_family():
    eng = _engine()
    assert eng._get_decode_fn("plain").__name__ == "serve_decode_plain"
    assert eng._get_prefill_fn(32).__name__ == "serve_prefill_32"
    assert eng._get_verify_fn("greedy").__name__ == "serve_verify_greedy"


def test_trainstep_yields_its_three_spans():
    paddle.seed(0)
    net = paddle.nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    step = paddle.jit.TrainStep(
        net, lambda out, lab: ((out - lab) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((2, 8), np.float32))
    y = paddle.to_tensor(np.zeros((2, 4), np.float32))
    step(x, y)
    with Profiler(timer_only=True) as prof:
        loss = step(x, y)
    assert np.isfinite(float(loss.numpy()))
    names = [row[0] for row in prof._store.events]
    assert names == ["trainstep.prepare", "trainstep.dispatch",
                     "trainstep.write_back"]
    rows = prof._store.events
    assert all(a[2] <= b[1] for a, b in zip(rows, rows[1:]))


def test_record_event_keeps_args_and_exports_them():
    with Profiler(timer_only=True) as prof:
        with RecordEvent("phase", step=3) as ev:
            ev.set(rows=2)
        with RecordEvent("bare"):
            pass
    (name, t0, t1, args), bare = prof._store.events
    assert (name, args) == ("phase", {"step": 3, "rows": 2}) and t1 >= t0
    assert bare[3] == {}
    assert prof._store.aggregate()["phase"]["calls"] == 1
    user = [e for e in chrome_trace.build_trace(prof)["traceEvents"]
            if e.get("cat") == "user"]
    assert user[0]["args"] == {"step": 3, "rows": 2}
    assert "args" not in user[1]
    # with nothing recording a span is inert: no store, no error
    with RecordEvent("after", x=1) as ev:
        ev.set(y=2)
    assert len(prof._store.events) == 2
