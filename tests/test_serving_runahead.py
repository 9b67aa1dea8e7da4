"""Run-ahead decode (docs/SERVING.md "Dispatch pipelining"): Engine.step()
dispatches tick t before it waits for tick t-1, so the host learns each
token, and each finish, one tick late. Under test: every request's
stream is the b=1 ``generate()`` stream whatever ends it and whenever
(the tick in flight holds its lane dead in-graph, or its token is
discarded), the engine is idle only after the last harvest, no page
leaks, and the ``serving.runahead.*`` counters say which path ran.
"""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference.disagg import replay_rng_key
from paddle_tpu.inference.engine import (Engine, SamplingParams,
                                         host_prng_key)
from paddle_tpu.inference.reliability import FaultInjector, FaultPlan
from paddle_tpu.text.generation import generate
from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM

PAGE = 8
COUNTERS = ("dispatches", "dead_lane_ticks", "drains.api",
            "drains.preempt")


def _net(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab=64, hidden=64, layers=2, heads=4)
    cfg.use_flash_attention = False
    net = LlamaForCausalLM(cfg)
    net.eval()
    return net


@pytest.fixture(scope="module")
def net():
    return _net()


def _engine(net, **kw):
    kw = {"max_slots": 2, "page_size": PAGE, "pool_pages": 64,
          "max_context": 64, "prefill_bucket": 8, **kw}
    return Engine(net, **kw)


def _prompt(n, lo=1):
    return (np.arange(lo, lo + n) % 64).astype(np.int64)


def _ref(net, prompt, sp: SamplingParams):
    """The b=1 generate() stream, cut behind the request's eos."""
    out = np.asarray(generate(
        net, paddle.to_tensor(prompt[None]), sp.max_new_tokens,
        temperature=sp.temperature, top_k=sp.top_k, top_p=sp.top_p,
        seed=sp.seed).numpy())[0, len(prompt):].tolist()
    if sp.eos_token_id is not None and sp.eos_token_id in out:
        out = out[:out.index(sp.eos_token_id) + 1]
    return out


def _counts():
    snap = monitor.snapshot()
    return {c: int(snap.get("serving.runahead." + c, 0)) for c in COUNTERS}


def _delta(before):
    return {c: v - before[c] for c, v in _counts().items()}


def _drive(eng, plan, hooks=None, max_steps=300):
    """Step `eng` through `plan` ({step: [(prompt, params)]}) and
    `hooks` ({step: fn(eng, ids) -> Outputs it retired}); returns
    ({req_id: Output}, ids in arrival order). Holds on the way that the
    engine is never idle with a tick still to harvest, and at the end
    that it is idle, with nothing in flight and no page leaked."""
    done, ids = {}, []
    last = max(list(plan) + list(hooks or ()))
    for step in range(max_steps):
        for prompt, sp in plan.get(step, ()):
            ids.append(eng.add_request(prompt, sp))
        if hooks and step in hooks:
            for o in hooks[step](eng, ids) or ():
                done[o.req_id] = o
        for o in eng.step():
            done[o.req_id] = o
        if eng._inflight is not None:
            assert not eng.idle
        if step >= last and eng.idle:
            break
    assert eng.idle and eng._inflight is None
    assert eng.leaked_pages() == 0
    return done, ids


def _exact(net, done, ids, reqs, skip=()):
    for rid, (prompt, sp) in zip(ids, reqs):
        if rid in skip:
            continue
        assert done[rid].ok, (rid, done[rid].finish_reason)
        assert done[rid].token_ids == _ref(net, prompt, sp), rid


def _eos_request(net, mod, lo):
    """A greedy request that ends by eos after k tokens, with the first
    position its lane would write after that (prompt + k - 1) on a page
    boundary (mod 0) or in the middle of a page (mod 4)."""
    for n in range(4, 12):
        prompt = _prompt(n, lo)
        ref = _ref(net, prompt, SamplingParams(max_new_tokens=20))
        for k in range(3, 18):
            if ref[k - 1] not in ref[:k - 1] \
                    and (n + k - 1) % PAGE == mod:
                return prompt, SamplingParams(max_new_tokens=20,
                                              eos_token_id=ref[k - 1]), k
    raise AssertionError("no eos position found")


# -- the host-built key ------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 - 1, 2 ** 63 - 1, -1,
                                  -2 ** 63, 0xDEADBEEF12345])
def test_host_key_equals_prngkey(seed):
    SamplingParams(seed=seed).validate()
    np.testing.assert_array_equal(
        host_prng_key(seed), np.asarray(jax.random.PRNGKey(seed)))
    assert host_prng_key(seed).dtype == np.uint32
    np.testing.assert_array_equal(replay_rng_key(seed, 3, 0.0),
                                  host_prng_key(seed))


def test_seed_beyond_64_bits_is_refused():
    for seed in (2 ** 63, -2 ** 63 - 1):
        with pytest.raises(ValueError, match="seed"):
            SamplingParams(seed=seed).validate()


# -- finishes on the tick in flight ------------------------------------------

@pytest.mark.parametrize("how,mod", [("length", 0), ("length", 4),
                                     ("eos", 0), ("eos", 4)])
def test_finish_on_the_tick_in_flight(net, how, mod):
    """The request's last token comes out of tick t-1 while tick t is
    in flight with its lane in it: by length the host knows and leaves
    the lane out; by eos it does not, the lane rides dead in-graph and
    its token is dropped. On a page boundary the tick in flight would
    have written the first row of a page."""
    if how == "length":
        prompt = _prompt(5)
        first = (prompt, SamplingParams(max_new_tokens=12 if mod == 0
                                        else 8))
        assert (5 + first[1].max_new_tokens - 1) % PAGE == mod
    else:
        prompt, sp, k = _eos_request(net, mod, lo=1)
        first = (prompt, sp)
    other = (_prompt(9, 7), SamplingParams(max_new_tokens=24))
    before = _counts()
    eng = _engine(net)
    done, ids = _drive(eng, {0: [first, other]})
    _exact(net, done, ids, [first, other])
    assert done[ids[0]].finish_reason == how
    d = _delta(before)
    assert d["dispatches"] > 0
    assert d["drains.api"] == d["drains.preempt"] == 0
    # the eos lane rode the tick after its last token; at the end the
    # engine's last tick is known by its budget and none rides dead
    assert d["dead_lane_ticks"] == (1 if how == "eos" else 0)
    eng.close()


def test_eos_of_the_last_request_leaves_one_dead_tick(net):
    prompt, sp, k = _eos_request(net, 4, lo=3)
    before = _counts()
    eng = _engine(net, max_slots=1)
    rid = eng.add_request(prompt, sp)
    outs, steps = [], 0
    while not eng.idle:
        outs += eng.step()
        steps += 1
    assert [o.req_id for o in outs] == [rid]
    assert outs[0].token_ids == _ref(net, prompt, sp)
    assert len(outs[0].token_ids) == k
    # prefill + (k - 1) decode ticks + the dead tick, and one more
    # step to harvest it
    assert steps == k + 2 and eng._inflight is None
    assert _delta(before)["dead_lane_ticks"] == 1
    assert eng.leaked_pages() == 0


def test_slot_reused_the_tick_after_it_died(net):
    """One slot, four requests in a row: each is admitted in the step
    that harvests its predecessor's last token, while the tick in
    flight still carries the predecessor's lane."""
    reqs = [_eos_request(net, 0, lo=2)[:2],
            (_prompt(6, 11), SamplingParams(max_new_tokens=7)),
            _eos_request(net, 4, lo=5)[:2],
            (_prompt(3, 30), SamplingParams(max_new_tokens=9,
                                            temperature=0.8, seed=5))]
    eng = _engine(net, max_slots=1)
    done, ids = _drive(eng, {0: reqs[:2], 3: reqs[2:]})
    _exact(net, done, ids, reqs)
    eng.close()


def test_greedy_sampled_transitions_run_ahead(net):
    """The sampler variant changes with the arrivals while a tick is in
    flight; sampled rows carry their keys on the device across it."""
    reqs = [(_prompt(5), SamplingParams(max_new_tokens=30)),
            (_prompt(7, 3), SamplingParams(max_new_tokens=6,
                                           temperature=0.9, seed=3)),
            (_prompt(4, 9), SamplingParams(max_new_tokens=8,
                                           temperature=1.1, top_k=6,
                                           top_p=0.9, seed=7)),
            (_prompt(6, 20), SamplingParams(max_new_tokens=5)),
            (_prompt(8, 40), SamplingParams(max_new_tokens=7,
                                            temperature=0.7, top_p=0.8,
                                            seed=11))]
    before = _counts()
    eng = _engine(net)
    done, ids = _drive(eng, {0: reqs[:1], 2: reqs[1:2], 9: reqs[2:3],
                             12: reqs[3:4], 20: reqs[4:]})
    _exact(net, done, ids, reqs)
    assert set(eng._decode_fns) == {"greedy", "plain", "filtered"}
    d = _delta(before)
    assert d["drains.preempt"] == d["drains.api"] == 0
    eng.close()


# -- requests ended from outside, with a tick in flight ----------------------

def test_cancel_with_a_tick_in_flight(net):
    reqs = [(_prompt(5), SamplingParams(max_new_tokens=20)),
            (_prompt(9, 7), SamplingParams(max_new_tokens=14,
                                           temperature=0.9, seed=4))]
    before = _counts()
    eng = _engine(net)
    seen = {}

    def cancel(eng, ids):
        assert eng._inflight is not None
        seen["host"] = len(eng.requests[ids[0]].generated)
        out = eng.cancel(ids[0])
        assert eng._inflight is None          # drained first
        return [out]

    done, ids = _drive(eng, {0: reqs}, hooks={5: cancel})
    out = done[ids[0]]
    assert out.finish_reason == "cancelled"
    # the drain brought the token of the tick in flight
    assert len(out.token_ids) == seen["host"] + 1
    assert out.token_ids == _ref(net, *reqs[0])[:len(out.token_ids)]
    _exact(net, done, ids, reqs, skip={ids[0]})
    assert _delta(before)["drains.api"] == 1
    assert eng.cancel(ids[0]) is None
    eng.close()


def test_deadline_expiry_with_a_tick_in_flight(net):
    vt = [0.0]
    eng = _engine(net, clock=lambda: vt[0])
    reqs = [(_prompt(5), SamplingParams(max_new_tokens=40,
                                        deadline_ms=50.0)),
            (_prompt(9, 7), SamplingParams(max_new_tokens=16))]
    before = _counts()

    def tick(eng, ids):
        vt[0] += 0.01

    done, ids = _drive(eng, {0: reqs},
                       hooks={s: tick for s in range(40)})
    out = done[ids[0]]
    assert out.finish_reason == "deadline" and not out.ok
    assert 0 < len(out.token_ids) < 40
    assert out.token_ids == _ref(net, *reqs[0])[:len(out.token_ids)]
    _exact(net, done, ids, reqs, skip={ids[0]})
    # expired inside step() with its lane in the tick in flight: no
    # drain, the lane's token is dropped at the next harvest
    d = _delta(before)
    assert d["drains.api"] == 0 and d["dead_lane_ticks"] == 1
    eng.close()


def test_injected_nan_with_a_tick_in_flight(net):
    inj = FaultInjector(seed=0, rate=0.0,
                        plan=FaultPlan([(4, "decode.nan")]))
    reqs = [(_prompt(5), SamplingParams(max_new_tokens=12)),
            (_prompt(9, 7), SamplingParams(max_new_tokens=12))]
    q0 = monitor.counter("serving.nan_quarantines").get()
    before = _counts()
    eng = _engine(net, fault_injector=inj)
    done, ids = _drive(eng, {0: reqs})
    bad = [rid for rid in ids if not done[rid].ok]
    assert len(bad) == 1
    assert done[bad[0]].finish_reason == "nan_logits"
    toks = done[bad[0]].token_ids
    assert toks == _ref(net, *reqs[ids.index(bad[0])])[:len(toks)]
    _exact(net, done, ids, reqs, skip=set(bad))
    assert monitor.counter("serving.nan_quarantines").get() == q0 + 1
    # the quarantine was found a tick late: the lane rode one more
    assert _delta(before)["dead_lane_ticks"] == 1
    eng.close()


def test_preemption_under_pool_pressure_drains_first(net):
    """The pool cannot hold both sequences: growth preempts the
    youngest, whose sampler key is read from the device only after the
    tick in flight was harvested."""
    reqs = [(_prompt(4), SamplingParams(max_new_tokens=10,
                                        temperature=0.9, seed=2)),
            (_prompt(3, 9), SamplingParams(max_new_tokens=10,
                                           temperature=0.8, seed=6))]
    p0 = monitor.counter("serving.preemptions").get()
    before = _counts()
    eng = _engine(net, page_size=4, pool_pages=4, max_context=16,
                  prefill_bucket=4, watermark_pages=0)
    done, ids = _drive(eng, {0: reqs})
    _exact(net, done, ids, reqs)
    assert monitor.counter("serving.preemptions").get() > p0
    assert max(o.preemptions for o in done.values()) > 0
    assert _delta(before)["drains.preempt"] > 0
    eng.close()


# -- a prefill chunk in flight beside the tick -------------------------------

def _arrivals(n=5, new=10):
    """A first request decoding, then one arrival a step: every later
    chunk is dispatched with a tick in flight, so its wait is the next
    step's."""
    reqs = [(_prompt(5 + i, 3 * i + 1),
             SamplingParams(max_new_tokens=new, temperature=0.7 * (i % 2),
                            seed=i)) for i in range(n)]
    return reqs, {0: reqs[:1], **{2 + i: [r] for i, r in
                                  enumerate(reqs[1:])}}


@pytest.mark.parametrize("budget", [None, 8])
def test_chunks_in_flight_keep_every_stream_exact(net, budget):
    reqs, plan = _arrivals()
    eng = _engine(net, max_slots=3, max_prefill_tokens_per_step=budget)
    deferred = []

    def watch(eng, ids):
        deferred.append(len(eng._prefilled))

    done, ids = _drive(eng, plan, hooks={s: watch for s in range(3, 12)})
    _exact(net, done, ids, reqs)
    assert max(deferred) >= 1 and not eng._prefilled
    eng.close()


def test_cancel_with_a_chunk_in_flight(net):
    reqs, plan = _arrivals(2, new=12)
    eng = _engine(net)

    def cancel(eng, ids):
        assert eng._prefilled and eng._prefilled[0].req.req_id == ids[1]
        out = eng.cancel(ids[1])
        assert not eng._prefilled and eng._inflight is None
        return [out]

    done, ids = _drive(eng, plan, hooks={3: cancel})
    out = done[ids[1]]
    # the drain harvested the chunk first: the Output holds its token
    assert out.finish_reason == "cancelled"
    assert out.token_ids == _ref(net, *reqs[1])[:1]
    _exact(net, done, ids, reqs, skip={ids[1]})
    eng.close()


def test_expiry_right_after_a_chunks_harvest(net):
    vt = [0.0]
    eng = _engine(net, clock=lambda: vt[0])
    reqs, plan = _arrivals(2, new=12)
    reqs[1] = (reqs[1][0], SamplingParams(max_new_tokens=12,
                                          deadline_ms=5.0))
    plan[2] = [reqs[1]]

    def late(eng, ids):
        assert eng._prefilled
        vt[0] += 1.0

    done, ids = _drive(eng, plan, hooks={3: late})
    out = done[ids[1]]
    assert out.finish_reason == "deadline"
    assert out.token_ids == _ref(net, *reqs[1])[:1]
    _exact(net, done, ids, reqs, skip={ids[1]})
    eng.close()


def test_nan_chunk_in_flight_leaves_nothing_in_the_prefix_cache(net):
    """A final chunk's pages are registered at its dispatch (the
    router looks between steps); a chunk that comes back NaN takes
    them out again at its harvest, the cached head it started from
    stays."""
    inj = FaultInjector(seed=0, rate=0.0,
                        plan=FaultPlan([(4, "prefill.nan")]))
    eng = _engine(net, prefix_cache=True, fault_injector=inj,
                  max_slots=3)
    head = _prompt(2 * PAGE)
    bad = np.concatenate([head, _prompt(2 * PAGE + 3, 40)])
    reqs = [(head.copy(), SamplingParams(max_new_tokens=14)),
            (bad, SamplingParams(max_new_tokens=4))]
    seen = {}

    def registered(eng, ids):
        assert eng._prefilled
        seen["at_dispatch"] = eng._prefix.lookup(bad)

    def harvested(eng, ids):
        seen["after"] = eng._prefix.lookup(bad)

    done, ids = _drive(eng, {0: reqs[:1], 4: reqs[1:]},
                       hooks={5: registered, 7: harvested})
    assert seen["at_dispatch"] == 4 * PAGE
    assert done[ids[1]].finish_reason == "nan_logits"
    assert seen["after"] == 2 * PAGE          # the first request's pages
    _exact(net, done, ids, reqs, skip={ids[1]})
    eng.close()


def test_preempting_the_request_of_a_chunk_in_flight(net):
    """Growth finds the pool dry in the step that dispatched the
    youngest request's chunk: the drain harvests the chunk before the
    victim's slot is cleared."""
    reqs = [(_prompt(7), SamplingParams(max_new_tokens=9,
                                        temperature=0.9, seed=2)),
            (_prompt(4, 9), SamplingParams(max_new_tokens=6))]
    p0 = monitor.counter("serving.preemptions").get()
    eng = _engine(net, page_size=4, pool_pages=4, max_context=16,
                  prefill_bucket=4, watermark_pages=0)
    done, ids = _drive(eng, {0: reqs[:1], 2: reqs[1:]})
    _exact(net, done, ids, reqs)
    assert monitor.counter("serving.preemptions").get() > p0
    eng.close()


@pytest.mark.parametrize("sync", [True, False])
def test_snapshot_restore_mid_flight(net, sync):
    """sync=True harvests the tick in flight and reads the device's
    keys; sync=False (a wedged device) leaves it, and the snapshot is
    the host's view one tick behind. Greedy streams are exact both
    ways; sampled ones with the sync."""
    temp = 0.9 if sync else 0.0
    reqs = [(_prompt(5), SamplingParams(max_new_tokens=14,
                                        temperature=temp, seed=3)),
            (_prompt(9, 7), SamplingParams(max_new_tokens=10)),
            (_prompt(6, 21), SamplingParams(max_new_tokens=6))]
    before = _counts()
    eng = _engine(net)
    ids = [eng.add_request(p, sp) for p, sp in reqs]
    done = {}
    for _ in range(5):
        for o in eng.step():
            done[o.req_id] = o
    assert eng._inflight is not None
    snap = eng.snapshot(sync=sync)
    assert (eng._inflight is None) == sync
    assert _delta(before)["drains.api"] == int(sync)
    eng2 = _engine(net)
    assert eng2.restore(snap) == len(ids) - len(done)
    rest, _ = _drive(eng2, {0: []})
    done.update(rest)
    _exact(net, done, ids, reqs)
    # the engine snapshotted keeps serving, to the same streams
    more, _ = _drive(eng, {0: []})
    assert {r: o.token_ids for r, o in more.items()} == \
        {r: o.token_ids for r, o in rest.items()}
    eng.close()
    eng2.close()


@pytest.mark.parametrize("device_key", [True, False])
def test_extract_request_mid_flight(net, device_key):
    """device_key=True drains and pulls the key of the newest token;
    False reads no device: the request leaves with the tokens the host
    holds (the fleet replays its key) and the token of the tick in
    flight is produced again where it resumes."""
    reqs = [(_prompt(5), SamplingParams(max_new_tokens=14,
                                        temperature=0.9, seed=3)),
            (_prompt(9, 7), SamplingParams(max_new_tokens=10))]
    eng = _engine(net)
    ids = [eng.add_request(p, sp) for p, sp in reqs]
    for _ in range(5):
        eng.step()
    held = len(eng.requests[ids[0]].generated)
    req = eng.extract_request(ids[0], device_key=device_key)
    assert (eng._inflight is None) == device_key
    assert len(req.generated) == held + int(device_key)
    if not device_key:
        req.key = replay_rng_key(3, len(req.generated), 0.9)
    np.testing.assert_array_equal(
        req.key, replay_rng_key(3, len(req.generated), 0.9))
    dst = _engine(net)
    dst.requests[req.req_id] = req
    dst._waiting.append(req)
    moved, _ = _drive(dst, {0: []})
    stayed, _ = _drive(eng, {0: []})
    _exact(net, {**moved, **stayed}, ids, reqs)
    eng.close()
    dst.close()


def test_extract_and_return_with_a_chunk_in_flight(net):
    """extract_request(device_key=False) drains nothing: a request
    can leave with its chunk in flight and come back to the same
    engine before the next step. The stale chunk hands nothing over;
    the new admission's prefill does."""
    reqs, plan = _arrivals(2, new=12)
    eng = _engine(net)

    def bounce(eng, ids):
        assert eng._prefilled and eng._prefilled[0].req.req_id == ids[1]
        req = eng.extract_request(ids[1], device_key=False)
        assert eng._prefilled and req.state == "WAITING"
        eng.requests[req.req_id] = req
        eng._waiting.append(req)

    done, ids = _drive(eng, plan, hooks={3: bounce})
    _exact(net, done, ids, reqs)
    eng.close()


# -- the dispatches that do not run ahead ------------------------------------

def test_speculative_engine_keeps_no_tick_in_flight(net):
    reqs = [(_prompt(5), SamplingParams(max_new_tokens=12)),
            (_prompt(9, 7), SamplingParams(max_new_tokens=9,
                                           temperature=0.9, seed=4))]
    before = _counts()
    eng = _engine(net, draft_model=_net(seed=1), spec_k=3)
    ids = [eng.add_request(p, sp) for p, sp in reqs]
    done = {}
    while not eng.idle:
        for o in eng.step():
            done[o.req_id] = o
        assert eng._inflight is None
    _exact(net, done, ids, reqs)
    assert _delta(before) == dict.fromkeys(COUNTERS, 0)
    assert eng.leaked_pages() == 0
    eng.close()


def test_pure_greedy_stretch_never_drains(net):
    """All-greedy lanes with nothing waiting or prefilling: every decode
    dispatch but the first queues behind a tick in flight, and nothing
    forces a drain before the run's end."""
    reqs = [(_prompt(5), SamplingParams(max_new_tokens=24)),
            (_prompt(7, 3), SamplingParams(max_new_tokens=24))]
    before = _counts()
    eng = _engine(net)
    ids = [eng.add_request(p, sp) for p, sp in reqs]
    done, steps = {}, 0
    while not eng.idle:
        for o in eng.step():
            done[o.req_id] = o
        steps += 1
        # step 1 holds the two prefills, the last step only a harvest;
        # every step between leaves its tick in flight
        assert (eng._inflight is not None) == (1 < steps < 25)
    _exact(net, done, ids, reqs)
    assert steps == 1 + 23 + 1
    d = _delta(before)
    # 23 decode dispatches, all but the first behind a tick in flight
    assert d.pop("dispatches") == 23 - 1
    assert d == dict.fromkeys(d, 0)
    eng.close()


@pytest.mark.parametrize("door", ["Engine", "DisaggEngine", "ServingFleet"])
def test_front_doors_take_no_multi_tick(net, door):
    from paddle_tpu import inference
    with pytest.raises(TypeError, match="multi_tick"):
        getattr(inference, door)(net, multi_tick=4)


def test_snapshot_with_a_multi_tick_field_restores(net):
    """Builds up to PR 30 wrote "multi_tick" into the soft fingerprint;
    such a snapshot restores strictly, silently and token-exact."""
    import warnings
    reqs = [(_prompt(5), SamplingParams(max_new_tokens=12)),
            (_prompt(9, 7), SamplingParams(max_new_tokens=9,
                                           temperature=0.8, seed=5))]
    eng = _engine(net)
    ids = [eng.add_request(p, sp) for p, sp in reqs]
    for _ in range(4):
        eng.step()
    snap = eng.snapshot()
    eng.close()
    assert "multi_tick" not in snap["fingerprint"]["soft"]
    snap["fingerprint"]["soft"]["multi_tick"] = 4
    eng2 = _engine(net)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert eng2.restore(snap, strict=True) == len(ids)
    done, _ = _drive(eng2, {0: []})
    _exact(net, done, ids, reqs)
    eng2.close()


def test_hotpath_inventory_names_the_families_that_exist(net):
    eng = _engine(net, draft_model=_net(seed=1), spec_k=3)
    inv = eng._hotpath_inventory()
    families = {e.name.split("[")[0] for e in inv.executables}
    assert families == {"decode", "verify", "prefill", "draft-loop",
                        "draft-prefill"}
    assert set(inv.cache_keys) == {"_decode_fns", "_verify_fns",
                                   "_prefill_fns", "_spec._prefill_fns"}
    ticks = {f.__name__ for f in inv.tick_functions}
    assert {"_decode_dispatch", "_dispatch_spec", "_harvest_single",
            "_harvest_spec", "_flush_state", "_drain"} <= ticks
    assert all(hasattr(eng, n) for n in ticks | set(inv.steady_functions))
    eng.close()
    plain = _engine(net)
    assert {e.name.split("[")[0]
            for e in plain._hotpath_inventory().executables} == \
        {"decode", "prefill"}
    plain.close()


def test_close_and_run_leave_nothing_in_flight(net):
    prompt, sp, _ = _eos_request(net, 4, lo=3)
    eng = _engine(net)
    out, = eng.run([(prompt, sp)])
    assert out.token_ids == _ref(net, prompt, sp)
    assert eng._inflight is None and eng.idle
    eng.add_request(_prompt(5), SamplingParams(max_new_tokens=6))
    for _ in range(3):
        eng.step()
    assert eng._inflight is not None and not eng.idle
    assert eng.check_invariants() == []
    assert eng._inflight is None
    eng.close()
    assert eng._inflight is None
