"""Dispatch pipelining + multi-tick fused decode (docs/SERVING.md
"Dispatch pipelining & multi-tick decode").

The contract under test: with ``multi_tick=K`` the engine runs up to K
greedy device ticks per host round-trip as ONE fused scan executable,
and the fusion is a pure scheduling change — every request emits
exactly the tokens the single-tick engine (and therefore the b=1
generate() reference) emits, across eos mid-stretch, length finishes
on and off the k-bucket boundary, staggered arrivals, and
greedy↔sampled traffic transitions; the clamp ladder (max_new / page
coverage / deadline) bounds every dispatch; the k-bucket executable
set keeps steady-state recompiles at zero; and the fused scan body is
part of the hot-path lint inventory.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference.engine import Engine, SamplingParams
from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM


def _tiny_net(seed=0, layers=2, heads=4, vocab=64, hidden=64):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab=vocab, hidden=hidden, layers=layers,
                           heads=heads)
    cfg.use_flash_attention = False
    net = LlamaForCausalLM(cfg)
    net.eval()
    return net


def _prompts(rng, lens, vocab=64):
    return [rng.integers(0, vocab, (n,)).astype(np.int64) for n in lens]


def _drain(eng, want, max_steps=200):
    done = {}
    for _ in range(max_steps):
        for o in eng.step():
            done[o.req_id] = o
        if len(done) == want:
            break
    assert len(done) == want
    return done


def _run_trace(net, reqs, multi_tick, **eng_kw):
    """Replay (prompt, params) pairs; returns req_id->Output."""
    eng = Engine(net, max_slots=eng_kw.pop("max_slots", 2),
                 page_size=8, pool_pages=64, max_context=64,
                 multi_tick=multi_tick, **eng_kw)
    for p, sp in reqs:
        eng.add_request(p, sp)
    done = _drain(eng, len(reqs))
    recompiles = eng.steady_state_recompiles()
    eng.close()
    return done, recompiles


def test_multi_tick_token_exact_vs_single_tick(rng):
    """The exactness matrix: same staggered greedy trace through
    multi_tick=1 and multi_tick=8 — identical token streams and
    finish reasons per request, including a length finish mid-bucket
    (max_new 7), on the bucket boundary (8) and past it (12)."""
    net = _tiny_net()
    prompts = _prompts(rng, (5, 9, 3, 7))
    maxnews = (7, 8, 12, 5)
    reqs = [(p, SamplingParams(max_new_tokens=n))
            for p, n in zip(prompts, maxnews)]
    ref, _ = _run_trace(net, reqs, multi_tick=1)
    got, _ = _run_trace(net, reqs, multi_tick=8)
    assert set(ref) == set(got)
    for rid in ref:
        assert got[rid].token_ids == ref[rid].token_ids, rid
        assert got[rid].finish_reason == ref[rid].finish_reason
        assert got[rid].finish_reason == "length"


def test_multi_tick_eos_freezes_mid_stretch(rng):
    """A row that hits eos inside a fused stretch freezes in-graph:
    the host discards its post-finish scan positions, the finish
    reason is "eos", and the tokens match the single-tick engine
    truncated at the same position."""
    net = _tiny_net()
    prompt = _prompts(rng, (6,))[0]
    # discover what greedy emits, then make the eos id a token that
    # FIRST appears at position >= 2, so it fires strictly inside an
    # 8-tick fused stretch (the tiny net repeats itself: taking token
    # #2 blindly can name an id already emitted at #0, and the request
    # then — correctly — ends after one token)
    probe, _ = _run_trace(
        net, [(prompt, SamplingParams(max_new_tokens=8))], multi_tick=1)
    stream = next(iter(probe.values())).token_ids
    at = next(i for i in range(2, len(stream) - 1)
              if stream[i] not in stream[:i])
    eos = stream[at]
    reqs = [(prompt, SamplingParams(max_new_tokens=8,
                                    eos_token_id=int(eos)))]
    ref, _ = _run_trace(net, reqs, multi_tick=1)
    got, _ = _run_trace(net, reqs, multi_tick=8)
    r, g = next(iter(ref.values())), next(iter(got.values()))
    assert g.token_ids == r.token_ids
    assert g.token_ids[-1] == eos and len(g.token_ids) == at + 1
    assert g.finish_reason == r.finish_reason == "eos"


def test_greedy_sampled_transitions_disable_fusion(rng):
    """Fusion disengages while ANY live slot samples and re-engages
    when the trace turns pure-greedy again — tokens stay exact vs the
    single-tick engine for both populations."""
    net = _tiny_net()
    prompts = _prompts(rng, (5, 7, 4))

    def reqs():
        return [
            (prompts[0], SamplingParams(max_new_tokens=12)),
            (prompts[1], SamplingParams(max_new_tokens=4,
                                        temperature=0.9, seed=7)),
            (prompts[2], SamplingParams(max_new_tokens=10)),
        ]

    ref, _ = _run_trace(net, reqs(), multi_tick=1, max_slots=3)
    before = monitor.snapshot()
    got, _ = _run_trace(net, reqs(), multi_tick=8, max_slots=3)
    after = monitor.snapshot()
    for rid in ref:
        assert got[rid].token_ids == ref[rid].token_ids, rid
    # the sampled row's lifetime forces single ticks; once it retires
    # (max_new 4) the surviving greedy rows fuse again
    fused = int(after.get("serving.multi_tick.dispatches", 0)) - \
        int(before.get("serving.multi_tick.dispatches", 0))
    assert fused > 0


def test_multi_tick_counters_and_scan_exits(rng):
    """serving.multi_tick.* telemetry (docs/OBSERVABILITY.md): every
    fused dispatch counts itself and its ticks, clamps record which
    horizon bit, and each harvested row's exit lands in exactly one
    scan_exit.* bucket."""
    net = _tiny_net()
    prompts = _prompts(rng, (5, 9))
    before = monitor.snapshot()
    # 12 post-prefill tokens = three full k=4 stretches: both rows
    # finish by length INSIDE the last fused scan -> scan_exit.length
    got, _ = _run_trace(
        net, [(p, SamplingParams(max_new_tokens=13)) for p in prompts],
        multi_tick=4)
    # 3 remaining tokens < k: the max_new clamp fires (bucket 2), the
    # leftover token decodes as a plain single tick
    got2, _ = _run_trace(
        net, [(prompts[0], SamplingParams(max_new_tokens=4))],
        multi_tick=4)
    after = monitor.snapshot()

    def delta(key):
        return int(after.get(key, 0)) - int(before.get(key, 0))

    nd = delta("serving.multi_tick.dispatches")
    nt = delta("serving.multi_tick.ticks")
    assert nd > 0 and nt > nd          # every dispatch fused >= 2 ticks
    assert delta("serving.multi_tick.clamp.max_new") > 0
    assert delta("serving.multi_tick.scan_exit.length") == 2
    assert all(o.finish_reason == "length" for o in got.values())
    assert all(o.finish_reason == "length" for o in got2.values())


def test_zero_recompiles_across_mixed_k_buckets(rng):
    """One compiled executable per k bucket: traces whose clamps walk
    k through {8, 4, 2} plus single ticks stay at zero steady-state
    recompiles after the engine has seen each bucket once."""
    net = _tiny_net()
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                 max_context=64, multi_tick=8)

    def run(lens_and_maxnew):
        rng2 = np.random.default_rng(42)
        for n, mx in lens_and_maxnew:
            eng.add_request(
                rng2.integers(0, 64, (n,)).astype(np.int64),
                SamplingParams(max_new_tokens=mx))
        _drain(eng, len(lens_and_maxnew))

    # warm every bucket the clamp can produce: long (k=8), then
    # horizons that clamp to 4, 2, and a single tick
    run([(5, 20), (7, 20)])
    run([(5, 5)])
    run([(5, 3)])
    run([(5, 1)])
    mark = eng.steady_state_recompiles()
    run([(6, 20), (4, 6), (8, 3), (5, 1)])
    assert eng.steady_state_recompiles() == mark == 0
    assert set(eng._multi_fns) <= {2, 4, 8}
    eng.close()


def test_clamp_max_new_horizon(rng):
    """Unit: the max_new leg — the fused length never exceeds the
    LONGEST remaining budget (shorter rows freeze in-graph), and the
    clamp rounds down to a compiled bucket."""
    net = _tiny_net()
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                 max_context=64, multi_tick=8)
    eng.add_request(_prompts(rng, (5,))[0],
                    SamplingParams(max_new_tokens=6))
    eng.add_request(_prompts(rng, (4,))[0],
                    SamplingParams(max_new_tokens=3))
    eng.step()                        # prefills -> both rows DECODE
    active = [i for i in range(eng.max_slots)
              if eng._slots[i] is not None]
    b0 = monitor.snapshot().get("serving.multi_tick.clamp.max_new", 0)
    # longest remaining budget is 5 (6 - 1 prefill token) -> bucket 4
    assert eng._multi_k(active, "greedy") == 4
    assert monitor.snapshot()["serving.multi_tick.clamp.max_new"] \
        == int(b0) + 1
    eng.close()


def test_clamp_page_coverage_horizon(rng):
    """Unit: the page leg — k is HARD-capped by the tightest slot's
    allocated coverage (the scan has no host allocator in the loop),
    and k < 2 degrades to a plain single tick."""
    net = _tiny_net()
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                 max_context=64, multi_tick=8)
    eng.add_request(_prompts(rng, (5,))[0],
                    SamplingParams(max_new_tokens=20))
    eng.step()
    active = [i for i in range(eng.max_slots)
              if eng._slots[i] is not None]
    req = eng._slots[active[0]]
    # synthetic tight coverage: 3 unwritten positions in the last page
    req.written = len(req.pages) * eng.page_size - 3
    b0 = monitor.snapshot().get("serving.multi_tick.clamp.pages", 0)
    assert eng._multi_k(active, "greedy") == 2     # bucket(3) == 2
    assert monitor.snapshot()["serving.multi_tick.clamp.pages"] \
        == int(b0) + 1
    req.written = len(req.pages) * eng.page_size - 1
    assert eng._multi_k(active, "greedy") == 1     # k < 2 -> single
    eng.close()


def test_clamp_deadline_horizon(rng):
    """Unit: the deadline leg — with a tick-duration estimate on the
    injectable clock, a near deadline bounds the fused length so the
    overrun is at most one dispatch; no estimate means no clamp."""
    t = [0.0]
    net = _tiny_net()
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                 max_context=64, multi_tick=8, clock=lambda: t[0])
    eng.add_request(_prompts(rng, (5,))[0],
                    SamplingParams(max_new_tokens=20,
                                   deadline_ms=50.0))
    eng.step()
    active = [i for i in range(eng.max_slots)
              if eng._slots[i] is not None]
    assert eng._deadline_ticks(active) == 8        # no estimate yet
    eng._tick_est_ms = 10.0
    # 50ms left at 10ms/tick -> 5 ticks -> bucket 4
    b0 = monitor.snapshot().get("serving.multi_tick.clamp.deadline", 0)
    assert eng._deadline_ticks(active) == 5
    assert eng._multi_k(active, "greedy") == 4
    assert monitor.snapshot()["serving.multi_tick.clamp.deadline"] \
        == int(b0) + 1
    t[0] = 0.045                                   # 5ms left -> 1 tick
    assert eng._deadline_ticks(active) == 1
    assert eng._multi_k(active, "greedy") == 1
    eng.close()


def test_clamp_spec_exclusion(rng):
    """A speculative decoder excludes fusion entirely (the draft/
    verify loop owns the horizon): every decode dispatch of a
    multi_tick>1 + draft_model engine rides the spec path and counts
    under serving.multi_tick.clamp.spec, tokens stay identical to the
    spec-only engine, and a multi_tick=1 + spec engine never touches
    the counter (no fusion was configured, nothing was excluded)."""
    net = _tiny_net(seed=3)
    draft = _tiny_net(seed=11)
    prompts = _prompts(rng, (5, 9))
    reqs = [(p, SamplingParams(max_new_tokens=6)) for p in prompts]

    def run(multi_tick):
        snap0 = monitor.snapshot()
        done, recompiles = _run_trace(net, reqs, multi_tick=multi_tick,
                                      draft_model=draft, spec_k=2)
        snap1 = monitor.snapshot()

        def delta(name):
            return int(snap1.get(name, 0)) - int(snap0.get(name, 0))

        return done, recompiles, delta

    ref, _, d1 = run(1)
    got, recompiles, d4 = run(4)
    assert d1("serving.multi_tick.clamp.spec") == 0
    assert d4("serving.multi_tick.clamp.spec") > 0   # per dispatch
    assert d4("serving.multi_tick.dispatches") == 0  # never fused
    assert recompiles == 0
    assert set(ref) == set(got)
    for rid in ref:
        assert got[rid].token_ids == ref[rid].token_ids


def test_multi_bucket_rounding():
    """Unit: bucket set = powers of two plus multi_tick itself,
    rounded DOWN — the executable family stays bounded."""
    net = _tiny_net()
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                 max_context=64, multi_tick=6)
    assert eng._multi_bucket(2) == 2
    assert eng._multi_bucket(3) == 2
    assert eng._multi_bucket(5) == 4
    assert eng._multi_bucket(6) == 6      # the configured maximum
    assert eng._multi_bucket(7) == 6
    eng.close()


def test_hotpath_inventory_carries_fused_scan(rng):
    """The fused scan executable is part of the hot-path lint surface
    (docs/ANALYSIS.md "Hot-path rules"): the inventory lists a
    decode-multi spec per warm k bucket and the analyzer finds
    nothing on it — donated carries, token-sized fetch set."""
    pytest.importorskip("paddle_tpu.analysis.hotpath_lint")
    net = _tiny_net()
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                 max_context=64, multi_tick=4)
    eng.add_request(np.arange(5, dtype=np.int64),
                    SamplingParams(max_new_tokens=10))
    _drain(eng, 1)
    inv = eng._hotpath_inventory()
    names = [s.name for s in inv.executables]
    assert any(n.startswith("decode-multi[") for n in names)
    findings = eng.inspect_hotpath()
    assert not findings, findings.format()
    eng.close()


def test_multi_tick_validation():
    net = _tiny_net()
    with pytest.raises(ValueError):
        Engine(net, max_slots=2, page_size=8, pool_pages=64,
               max_context=64, multi_tick=0)
