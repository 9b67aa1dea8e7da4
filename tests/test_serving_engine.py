"""Continuous-batching serving engine (docs/SERVING.md).

The contract under test: the engine is a SCHEDULER, not a new numeric
path — a request decoded through any slot mix emits exactly the tokens
a ``batch=1 text.generate`` emits with the same seed (greedy AND seeded
sampling), across staggered arrivals, preemption/resume round trips,
and page-pool pressure; the whole mixed trace runs on exactly two
compiled step families (bucketed prefill + one [max_slots] decode), so
steady-state recompiles are zero; and the allocator's free list
balances to empty when the engine drains. Satellite surface: per-row
max_new_tokens / eos_token_id on the one-shot generate() path.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference.allocator import PageAllocator
from paddle_tpu.inference.engine import Engine, SamplingParams
from paddle_tpu.text.generation import generate
from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM


def _tiny_net(seed=0, layers=2, heads=4, vocab=64, hidden=64, kv=None,
              window=None):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab=vocab, hidden=hidden, layers=layers,
                           heads=heads)
    if kv is not None:
        cfg.num_key_value_heads = kv
    cfg.sliding_window = window
    cfg.use_flash_attention = False
    net = LlamaForCausalLM(cfg)
    net.eval()
    return net


def _prompts(rng, lens, vocab=64):
    return [rng.integers(0, vocab, (n,)).astype(np.int64) for n in lens]


def _ref_row(net, prompt, max_new, **kw):
    """batch=1 generate() — the sequential reference the engine must
    match token-for-token."""
    out = np.asarray(generate(net, paddle.to_tensor(prompt[None]),
                              max_new, **kw).numpy())
    return out[0, len(prompt):].tolist()


def _trunc_at_eos(tokens, eos):
    if eos is None or eos not in tokens:
        return tokens
    return tokens[:tokens.index(eos) + 1]


def test_engine_greedy_token_exact_staggered(rng):
    """Greedy requests arriving mid-flight (slots join a running batch
    at different positions) decode the exact b=1 generate() tokens."""
    net = _tiny_net()
    prompts = _prompts(rng, (5, 9, 3, 7))
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                 max_context=64)
    done = {}
    r0 = eng.add_request(prompts[0], SamplingParams(max_new_tokens=8))
    r1 = eng.add_request(prompts[1], SamplingParams(max_new_tokens=6))
    for _ in range(3):                       # partial progress
        for o in eng.step():
            done[o.req_id] = o
    r2 = eng.add_request(prompts[2], SamplingParams(max_new_tokens=8))
    r3 = eng.add_request(prompts[3], SamplingParams(max_new_tokens=5))
    for _ in range(60):
        for o in eng.step():
            done[o.req_id] = o
        if len(done) == 4:
            break
    assert len(done) == 4
    for rid, p, n in ((r0, prompts[0], 8), (r1, prompts[1], 6),
                      (r2, prompts[2], 8), (r3, prompts[3], 5)):
        assert done[rid].token_ids == _ref_row(net, p, n), rid
        assert done[rid].finish_reason == "length"
    # drained engine: every page back on the free list, no live slots
    assert eng.pages_free == eng.pool_pages
    assert eng.num_active == 0 and eng.num_waiting == 0


def test_engine_seeded_sampling_token_exact(rng):
    """Mixed per-request sampling configs (temperature-only, top-k +
    top-p composed, nucleus-only; distinct seeds) in ONE running batch
    each reproduce their b=1 generate() chain exactly — per-slot rng
    keys advance per request, not per batch."""
    net = _tiny_net(seed=1)
    prompts = _prompts(rng, (6, 4, 11, 5))
    # the greedy row rides INSIDE the sampling batch (any sampling
    # request switches the decode executable to the sampler variant;
    # greedy rows there must still match — and consume no rng)
    cfgs = [dict(max_new_tokens=7, temperature=0.9, seed=3),
            dict(max_new_tokens=5, temperature=1.2, top_k=8, top_p=0.9,
                 seed=7),
            dict(max_new_tokens=9, temperature=0.7, top_p=0.85,
                 seed=11),
            dict(max_new_tokens=6)]
    refs = [_ref_row(net, p, c["max_new_tokens"],
                     temperature=c.get("temperature", 0.0),
                     top_k=c.get("top_k", 0), top_p=c.get("top_p", 0.0),
                     seed=c.get("seed", 0))
            for p, c in zip(prompts, cfgs)]
    eng = Engine(net, max_slots=4, page_size=8, pool_pages=32,
                 max_context=64)
    outs = eng.run([(p, SamplingParams(**c))
                    for p, c in zip(prompts, cfgs)])
    for ref, out in zip(refs, outs):
        assert out.token_ids == ref


def test_engine_preempt_resume_round_trip(rng):
    """A pool too small for every admitted sequence preempts the
    youngest back to WAITING (pages freed, rng chain kept); the resumed
    request still emits the exact uninterrupted token stream."""
    net = _tiny_net()
    # both sequences grow to 4 pages but the pool holds 4 total: the
    # admission watermark can't save this — growth must preempt
    prompts = _prompts(rng, (4, 3))
    monitor.counter("serving.preemptions").reset()
    eng = Engine(net, max_slots=2, page_size=4, pool_pages=4,
                 max_context=16, prefill_bucket=4, watermark_pages=0)
    outs = eng.run([(p, SamplingParams(max_new_tokens=10))
                    for p in prompts])
    assert monitor.counter("serving.preemptions").get() > 0
    assert max(o.preemptions for o in outs) > 0
    for p, o in zip(prompts, outs):
        assert o.token_ids == _ref_row(net, p, 10)
    assert eng.pages_free == eng.pool_pages      # free list balanced
    eng.close()


def test_engine_zero_recompiles_mixed_trace(rng):
    """After the warmup that builds the two step families (one prefill
    executable per prompt bucket + ONE decode shape), a fresh wave of
    mixed arrivals triggers ZERO XLA compiles."""
    net = _tiny_net(layers=1, heads=2, vocab=32, hidden=32)
    eng = Engine(net, max_slots=3, page_size=8, pool_pages=64,
                 max_context=64, prefill_bucket=8)
    wave1 = _prompts(rng, (5, 9, 3), vocab=32)
    eng.run([(p, SamplingParams(max_new_tokens=6)) for p in wave1])
    # second wave: same buckets (5->8, 9->16, 3->8), different lengths
    # and arrival pattern — must reuse the warm executables
    wave2 = _prompts(rng, (7, 12, 2, 4), vocab=32)
    eng.add_request(wave2[0], SamplingParams(max_new_tokens=5))
    done = 0
    for _ in range(3):
        done += len(eng.step())
    for p in wave2[1:]:
        eng.add_request(p, SamplingParams(max_new_tokens=7))
    for _ in range(60):
        done += len(eng.step())
        if done == 4:
            break
    assert done == 4
    assert eng.steady_state_recompiles() == 0, \
        eng._tracker.compiles


def test_engine_eos_frees_pages_mid_run(rng):
    """A request hitting its per-request eos finishes THAT step: its
    pages return to the free list and it stops counting toward
    serving.slots_active while other requests keep decoding."""
    net = _tiny_net()
    prompts = _prompts(rng, (5, 9))
    ref = _ref_row(net, prompts[0], 12)
    eos = ref[2]                      # force an early eos for row 0
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=16,
                 max_context=64)
    eng.add_request(prompts[0],
                    SamplingParams(max_new_tokens=12, eos_token_id=eos))
    eng.add_request(prompts[1], SamplingParams(max_new_tokens=12))
    done = {}
    free_after_eos = None
    for _ in range(30):
        for o in eng.step():
            done[o.req_id] = o
        if 0 in done and free_after_eos is None:
            free_after_eos = eng.pages_free
            # the finished request's page(s) are already back while
            # request 1 still holds its own
            assert eng.num_active == 1
            assert monitor.gauge("serving.slots_active").get() == 1
        if len(done) == 2:
            break
    assert len(done) == 2
    assert free_after_eos is not None and free_after_eos > 0
    assert done[0].finish_reason == "eos"
    assert done[0].token_ids == _trunc_at_eos(ref, eos)
    assert done[1].finish_reason == "length"


def test_engine_mixed_variant_trace_zero_recompiles(rng):
    """The fused decode step's THREE static sampler variants (greedy /
    no-filter / filtered) each compile once; a trace that bounces
    between all-greedy, temperature-only and filtered active sets —
    with admissions landing mid-flight so the device-resident state is
    merged repeatedly — triggers ZERO steady-state recompiles and
    every request stays token-exact vs its b=1 generate()."""
    net = _tiny_net(seed=4, layers=1, heads=2, vocab=32, hidden=32)
    eng = Engine(net, max_slots=3, page_size=8, pool_pages=64,
                 max_context=64, prefill_bucket=8)
    cfgs = [dict(max_new_tokens=6),                      # greedy
            dict(max_new_tokens=5, temperature=0.8, seed=3),   # plain
            dict(max_new_tokens=7, temperature=1.1, top_k=6,
                 top_p=0.9, seed=9)]                     # filtered
    prompts = _prompts(rng, (5, 7, 3), vocab=32)
    # warmup wave touches all three variants (sequentially: each
    # request alone so the active set takes each variant in turn)
    for p, c in zip(prompts, cfgs):
        eng.run([(p, SamplingParams(**c))])
    # measured wave: all three kinds live AT ONCE plus staggered
    # arrivals — the active set flips variants between ticks
    wave = _prompts(rng, (4, 9, 6, 2), vocab=32)
    wcfg = [cfgs[0], cfgs[2], cfgs[1], cfgs[0]]
    ids = [eng.add_request(wave[0], SamplingParams(**wcfg[0]))]
    for _ in range(2):
        eng.step()
    ids += [eng.add_request(w, SamplingParams(**c))
            for w, c in zip(wave[1:], wcfg[1:])]
    done = {}
    for _ in range(80):
        for o in eng.step():
            done[o.req_id] = o
        if len(done) >= len(ids):
            break
    assert set(ids) <= set(done)
    for rid, p, c in zip(ids, wave, wcfg):
        ref = _ref_row(net, p, c["max_new_tokens"],
                       temperature=c.get("temperature", 0.0),
                       top_k=c.get("top_k", 0),
                       top_p=c.get("top_p", 0.0), seed=c.get("seed", 0))
        assert done[rid].token_ids == ref, rid
    assert eng.steady_state_recompiles() == 0
    assert set(eng._decode_fns) == {"greedy", "plain", "filtered"}


def test_engine_idle_lanes_do_not_drift(rng):
    """Idle decode lanes must not advance their device-resident cache
    position tick over tick: a drifting pos would re-enter the decode
    kernel as a growing fake context_len and stream scratch pages
    forever (the 'empty lanes cost no bandwidth' contract). Only live
    rows advance; idle rows ride at cache_index -1 → context 0."""
    net = _tiny_net(layers=1, heads=2, vocab=32, hidden=32)
    eng = Engine(net, max_slots=4, page_size=8, pool_pages=32,
                 max_context=64)
    p = rng.integers(0, 32, (5,)).astype(np.int64)
    eng.add_request(p, SamplingParams(max_new_tokens=10))
    for _ in range(6):                    # mid-run: request still live
        eng.step()
    pos = np.asarray(eng._dev[1])
    live = np.asarray(eng._dev[6])
    assert live[0] == 1 and (live[1:] == 0).all()
    assert (pos[1:] == 0).all(), pos      # idle lanes pinned at 0
    assert pos[0] > 5                     # the live lane does advance
    # and the decode stays token-exact with idle lanes at context 0
    outs = []
    for _ in range(20):
        outs += eng.step()
        if outs:
            break
    assert outs[0].token_ids == _ref_row(net, p, 10)


def test_engine_pallas_eligibility_surfaced_at_init(rng):
    """Satellite: Pallas paged-decode eligibility is validated ONCE at
    Engine construction — an ineligible (head_dim, page_size,
    cache_dtype) geometry names the violated constraint and bumps
    serving.decode_fallback instead of silently gathering every
    step."""
    from paddle_tpu.kernels.paged_attention import \
        paged_pallas_requirements

    before = monitor.counter("serving.decode_fallback").get()
    net = _tiny_net(layers=1, heads=2, vocab=32, hidden=32)  # hd=16
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=8,
                 max_context=32)
    assert not eng.pallas_eligible
    assert "head_dim 16" in eng.decode_fallback_reason
    assert monitor.counter("serving.decode_fallback").get() == before + 1
    # an eligible geometry carries no reason (the constraint helper is
    # the same one the kernel call sites consult)
    assert paged_pallas_requirements(128, 16, "bfloat16") is None
    # int8 needs whole 128-lane scale rows: page_size 16 fails for int8
    why = paged_pallas_requirements(128, 16, "int8")
    assert why is not None and "128 lanes" in why


def test_serving_replay_expect_pallas_fails_loud(rng, capsys):
    """Satellite: --expect-pallas turns a replay that fell off the
    Pallas decode path into exit code 4 with the decode-path breakdown
    and the ineligibility reason on stderr — a fallback must never be
    just slow numbers."""
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        import serving_replay
    finally:
        sys.path.pop(0)
    trace = os.path.join(repo, "tests", "fixtures",
                         "serving_trace.jsonl")
    args = [trace, "--layers", "1", "--hidden", "32", "--heads", "2",
            "--vocab", "32", "--max-slots", "2", "--page-size", "8",
            "--pool-pages", "24"]
    rc = serving_replay.main(args + ["--expect-pallas", "--json"])
    assert rc == 4
    cap = capsys.readouterr()
    assert "expect-pallas FAILED" in cap.err
    assert "head_dim 16" in cap.err
    import json as _json
    report = _json.loads(cap.out.strip().splitlines()[-1])
    assert report["decode_paths"]["pallas"] == 0
    assert report["decode_paths"]["gather_step"] > 0
    assert report["pallas_eligible"] is False
    assert "head_dim 16" in report["pallas_ineligible_reason"]


def test_engine_gqa_window_int8_token_exact(rng):
    """The model-variant matrix through the engine: GQA caches
    (kv heads < q heads), sliding-window band masks, and int8 KV pools
    (5-tuple caches with per-slot scale pools) all decode per-slot
    token-identically to the one-shot paged generate()."""
    # GQA + sliding window, f32-auto caches
    net = _tiny_net(seed=2, kv=2, window=6)
    prompts = _prompts(rng, (5, 10))
    refs = [_ref_row(net, p, 8, cache_impl="paged", page_size=8)
            for p in prompts]
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=16,
                 max_context=48)
    outs = eng.run([(p, SamplingParams(max_new_tokens=8))
                    for p in prompts])
    for ref, out in zip(refs, outs):
        assert out.token_ids == ref
    # GQA + int8 KV pools (no window)
    net8 = _tiny_net(seed=3, kv=2)
    refs8 = [_ref_row(net8, p, 6, cache_dtype="int8") for p in prompts]
    eng8 = Engine(net8, max_slots=2, page_size=8, pool_pages=16,
                  max_context=48, cache_dtype="int8")
    outs8 = eng8.run([(p, SamplingParams(max_new_tokens=6))
                      for p in prompts])
    for ref, out in zip(refs8, outs8):
        assert out.token_ids == ref


def test_engine_same_tick_admissions_respect_pool(rng):
    """Admissions within ONE tick reserve their prefill pages before
    any of them allocates: three long prompts arriving together on a
    pool that fits two must leave the third WAITING (admitted later),
    not blow up the third prefill's allocation."""
    net = _tiny_net(layers=1, heads=2, vocab=32, hidden=32)
    prompts = _prompts(rng, (30, 30, 30), vocab=32)
    eng = Engine(net, max_slots=3, page_size=8, pool_pages=8,
                 max_context=48, prefill_bucket=32, watermark_pages=0)
    for p in prompts:                 # 4 pages each; pool holds 8
        eng.add_request(p, SamplingParams(max_new_tokens=4))
    done = {}
    for o in eng.step():
        done[o.req_id] = o
    assert eng.num_active == 2 and eng.num_waiting == 1
    for _ in range(20):
        for o in eng.step():
            done[o.req_id] = o
        if len(done) == 3:
            break
    assert len(done) == 3
    for i, p in enumerate(prompts):
        assert done[i].token_ids == _ref_row(net, p, 4), i


def test_allocator_free_list_accounting():
    """PageAllocator: watermark admission, FIFO reuse, loud
    RuntimeError on exhaustion (naming pool size / live pages / seq)
    and on double-free."""
    al = PageAllocator(4, base=1)
    assert al.free_pages == 4 and al.live_pages == 0
    a = al.alloc(2, seq="a")
    assert a == [1, 2] and al.owner(1) == "a"
    assert al.can_alloc(2) and not al.can_alloc(2, watermark=1)
    with pytest.raises(RuntimeError) as ei:
        al.alloc(3, seq="b")
    msg = str(ei.value)
    assert "4" in msg and "'b'" in msg and "2" in msg  # pool/seq/live
    al.free(a)
    assert al.free_pages == 4
    with pytest.raises(RuntimeError, match="double-free|not live"):
        al.free([1])
    b = al.alloc(4, seq="c")
    assert b == [3, 4, 1, 2]          # FIFO: oldest-freed last reused


def test_engine_validates_requests_and_model(rng):
    """Cacheless models and oversized/empty requests fail loudly at the
    API boundary, not as silent cache corruption later."""
    import paddle_tpu.nn as nn

    class NoCache(nn.Layer):
        def __init__(self):
            super().__init__()
            self.config = LlamaConfig.tiny()
            self.fc = nn.Linear(4, 4)

        def forward(self, x):
            return self.fc(x)

    with pytest.raises(ValueError, match="kv_caches"):
        Engine(NoCache())
    net = _tiny_net(layers=1, heads=2, vocab=32, hidden=32)
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=8,
                 max_context=32)
    with pytest.raises(ValueError, match="empty"):
        eng.add_request(np.zeros((0,), np.int64))
    with pytest.raises(ValueError, match="max_context"):
        eng.add_request(np.zeros((5,), np.int64),
                        SamplingParams(max_new_tokens=64))
    with pytest.raises(ValueError, match="ONE prompt"):
        # a [2, s] batch must not silently concatenate into one prompt
        eng.add_request(np.zeros((2, 5), np.int64))
    with pytest.raises(ValueError, match="max_new_tokens"):
        SamplingParams(max_new_tokens=0).validate()


def test_generate_per_row_budgets_and_eos(rng):
    """Satellite: generate() takes PER-ROW max_new_tokens /
    eos_token_id vectors — each row stops at its own budget (padding
    with its eos, or 0 with none set) and the shared prefix is
    token-identical to the scalar call."""
    net = _tiny_net()
    ids = paddle.to_tensor(rng.integers(0, 64, (3, 6)).astype(np.int64))
    ref = np.asarray(generate(net, ids, 7).numpy())
    out = np.asarray(generate(net, ids, np.array([3, 7, 5])).numpy())
    assert out.shape == (3, 6 + 7)
    np.testing.assert_array_equal(out[0, :6 + 3], ref[0, :6 + 3])
    np.testing.assert_array_equal(out[1], ref[1])
    np.testing.assert_array_equal(out[2, :6 + 5], ref[2, :6 + 5])
    assert (out[0, 6 + 3:] == 0).all() and (out[2, 6 + 5:] == 0).all()
    # per-row eos: row 0 freezes at its own eos token, row 1 never sees
    # its (out-of-vocab) eos and runs to the budget
    eos0 = int(ref[0, 6 + 1])
    out2 = np.asarray(generate(
        net, ids, 7, eos_token_id=np.array([eos0, 999, 999])).numpy())
    np.testing.assert_array_equal(out2[0, 6:6 + 2], ref[0, 6:6 + 2])
    assert (out2[0, 6 + 2:] == eos0).all()
    np.testing.assert_array_equal(out2[1], ref[1])
    # 0-dim arrays normalize to the scalar path (hashable jit-cache key)
    out3 = np.asarray(generate(net, ids, 7,
                               eos_token_id=np.asarray(999)).numpy())
    np.testing.assert_array_equal(out3, ref)
    with pytest.raises(ValueError, match="batch"):
        generate(net, ids, np.array([3, 7]))
    with pytest.raises(ValueError, match="batch"):
        generate(net, ids, 4, eos_token_id=np.zeros((2, 3), np.int64))


def test_inference_package_lint_clean():
    """Satellite: the paddle_lint sweep covers the new inference/
    package (the engine's host loop must never grow traced-value
    branches — the whole-package --self-check CI guard includes it)."""
    import importlib.util
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    adir = os.path.join(repo, "paddle_tpu", "analysis")
    sys.path.insert(0, adir)
    try:
        spec = importlib.util.spec_from_file_location(
            "ast_lint", os.path.join(adir, "ast_lint.py"))
        ast_lint = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ast_lint)
    finally:
        sys.path.remove(adir)
    found = ast_lint.lint_paths(
        [os.path.join(repo, "paddle_tpu", "inference")])
    assert found == [], [f.message for f in found]


def test_serving_replay_tool(rng, capsys):
    """tools/serving_replay.py replays the fixture JSONL trace against
    a tiny engine and prints TTFT/TPOT/throughput percentiles plus the
    decode-path counters."""
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        import serving_replay
    finally:
        sys.path.pop(0)
    trace = os.path.join(repo, "tests", "fixtures",
                         "serving_trace.jsonl")
    rc = serving_replay.main([trace, "--layers", "1", "--hidden", "32",
                              "--heads", "2", "--vocab", "32",
                              "--max-slots", "2", "--page-size", "8",
                              "--pool-pages", "24",
                              "--expect-complete-timelines"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "ttft_ms" in out and "tpot_ms" in out
    assert "tokens_per_sec" in out
    assert "requests" in out and "preemptions" in out


def test_engine_deadline_expiry_matrix(rng):
    """deadline_ms is enforced on the engine's step clock at every
    tick start — a WAITING request expires without ever taking a
    slot, and a mid-decode request fails with its partial tokens and
    frees its pages that tick, while unconstrained requests finish
    untouched (docs/SERVING.md 'Reliability')."""
    net = _tiny_net()
    clk = {"t": 0.0}
    eng = Engine(net, max_slots=1, page_size=8, pool_pages=32,
                 max_context=64, clock=lambda: clk["t"])
    prompts = _prompts(rng, (5, 7, 4))
    t0 = monitor.counter("serving.timeouts").get()
    # slot 0 busy with r0; r1 waits with a deadline it cannot make
    r0 = eng.add_request(prompts[0],
                         SamplingParams(max_new_tokens=10,
                                        deadline_ms=10_000.0))
    r1 = eng.add_request(prompts[1],
                         SamplingParams(max_new_tokens=4,
                                        deadline_ms=50.0))
    done = {}
    for _ in range(3):
        for o in eng.step():
            done[o.req_id] = o
    clk["t"] = 0.1                     # 100ms: r1's deadline passed
    for _ in range(20):
        for o in eng.step():
            done[o.req_id] = o
        if len(done) == 2:
            break
    assert done[r1].finish_reason == "deadline"
    assert not done[r1].ok and done[r1].token_ids == []
    assert done[r0].ok
    assert done[r0].token_ids == _ref_row(net, prompts[0], 10)
    # mid-decode expiry: the request keeps its partial tokens
    r2 = eng.add_request(prompts[2],
                         SamplingParams(max_new_tokens=50,
                                        deadline_ms=80.0))
    for _ in range(4):
        eng.step()
    clk["t"] = 0.5
    out2 = None
    for _ in range(5):
        for o in eng.step():
            out2 = o
        if out2 is not None:
            break
    assert out2.req_id == r2 and out2.finish_reason == "deadline"
    assert 0 < len(out2.token_ids) < 50
    assert out2.token_ids == \
        _ref_row(net, prompts[2], 50)[:len(out2.token_ids)]
    assert monitor.counter("serving.timeouts").get() == t0 + 2
    assert eng.pages_free == eng.pool_pages


def test_engine_queue_step_budget(rng):
    """max_queue_steps fails a request that cannot get a slot within
    its step budget ('queue_timeout'); re-queueing via preemption
    resets the budget (a preempted request is not a stuck one)."""
    net = _tiny_net()
    eng = Engine(net, max_slots=1, page_size=8, pool_pages=32,
                 max_context=64)
    prompts = _prompts(rng, (5, 7))
    eng.add_request(prompts[0], SamplingParams(max_new_tokens=12))
    r1 = eng.add_request(prompts[1],
                         SamplingParams(max_new_tokens=4,
                                        max_queue_steps=3))
    done = {}
    for _ in range(20):
        for o in eng.step():
            done[o.req_id] = o
        if len(done) == 2:
            break
    assert done[r1].finish_reason == "queue_timeout"
    assert not done[r1].ok
    assert done[0].token_ids == _ref_row(net, prompts[0], 12)
    assert eng.num_waiting == 0 and eng.pages_free == eng.pool_pages


def test_engine_cancel_matrix(rng):
    """cancel() at every lifecycle point — WAITING (never scheduled),
    DECODE (mid-stream, device lane reclaimed), PREEMPTED (resume
    state discarded) — frees the pages immediately, returns the
    partial Output, and leaves every other request token-exact;
    unknown/already-retired ids return None."""
    net = _tiny_net()
    prompts = _prompts(rng, (5, 9, 4, 3))
    c0 = monitor.counter("serving.cancelled").get()
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                 max_context=64)
    # cancel while WAITING: slots full of r0/r1, r2 still queued
    r0 = eng.add_request(prompts[0], SamplingParams(max_new_tokens=8))
    r1 = eng.add_request(prompts[1], SamplingParams(max_new_tokens=8))
    r2 = eng.add_request(prompts[2], SamplingParams(max_new_tokens=8))
    eng.step()
    assert eng.num_waiting == 1
    out2 = eng.cancel(r2)
    assert out2.finish_reason == "cancelled" and out2.token_ids == []
    assert eng.num_waiting == 0
    # cancel mid-DECODE: r1 has tokens, its lane frees, r0 unaffected
    for _ in range(2):
        eng.step()
    out1 = eng.cancel(r1)
    assert out1.finish_reason == "cancelled"
    assert 0 < len(out1.token_ids) < 8
    assert out1.token_ids == \
        _ref_row(net, prompts[1], 8)[:len(out1.token_ids)]
    assert eng.num_active == 1
    done = {}
    for _ in range(20):
        for o in eng.step():
            done[o.req_id] = o
        if r0 in done:
            break
    assert done[r0].token_ids == _ref_row(net, prompts[0], 8)
    # cancel while PREEMPTED: tight pool forces r3's eviction; cancel
    # must drop its resume state cleanly
    eng2 = Engine(net, max_slots=2, page_size=4, pool_pages=4,
                  max_context=16, prefill_bucket=4, watermark_pages=0)
    p = _prompts(rng, (4, 3))
    eng2.add_request(p[0], SamplingParams(max_new_tokens=10))
    r3 = eng2.add_request(p[1], SamplingParams(max_new_tokens=10))
    preempted = None
    for _ in range(30):
        eng2.step()
        req = eng2.requests.get(r3)
        if req is not None and req.state == "PREEMPTED":
            preempted = req
            break
    assert preempted is not None
    out3 = eng2.cancel(r3)
    assert out3.finish_reason == "cancelled" and out3.token_ids
    # retired/unknown ids: None, and the cancel counter counted 3
    assert eng.cancel(r1) is None and eng.cancel(9999) is None
    assert monitor.counter("serving.cancelled").get() == c0 + 3
    for e in (eng, eng2):
        for _ in range(40):
            if e.num_active == 0 and e.num_waiting == 0:
                break
            e.step()
        assert e.pages_free == e.pool_pages
    assert eng.steady_state_recompiles() == 0


def test_engine_rejected_requests_leave_state_untouched(rng):
    """Satellite: failed add_request validation (oversized context,
    impossible lifetime page demand, batched/empty prompts, bad
    params) must leave allocator AND prefix-cache state byte-identical
    to never having seen the rejects — asserted by interleaving
    rejects with accepted requests and comparing stats() against a
    control engine that only saw the accepted ones."""
    net = _tiny_net()
    prompts = _prompts(rng, (9, 6, 12))

    def drive(eng, with_rejects):
        rids = []
        for i, p in enumerate(prompts):
            if with_rejects:
                with pytest.raises(ValueError, match="max_context"):
                    eng.add_request(p, SamplingParams(
                        max_new_tokens=500))
                with pytest.raises(ValueError, match="ONE prompt"):
                    eng.add_request(np.zeros((2, 5), np.int64))
                with pytest.raises(ValueError, match="empty"):
                    eng.add_request(np.zeros((0,), np.int64))
                with pytest.raises(ValueError, match="deadline_ms"):
                    eng.add_request(p, SamplingParams(
                        max_new_tokens=2, deadline_ms=-1.0))
            rids.append(eng.add_request(
                p, SamplingParams(max_new_tokens=6)))
        outs = {}
        for _ in range(60):
            for o in eng.step():
                outs[o.req_id] = o
            if len(outs) == len(rids):
                break
        return [outs[r].token_ids for r in rids]

    eng_a = Engine(net, max_slots=2, page_size=8, pool_pages=32,
                   max_context=48, prefill_bucket=8, prefix_cache=True)
    eng_b = Engine(net, max_slots=2, page_size=8, pool_pages=32,
                   max_context=48, prefill_bucket=8, prefix_cache=True)
    toks_a = drive(eng_a, with_rejects=True)
    toks_b = drive(eng_b, with_rejects=False)
    assert toks_a == toks_b
    assert eng_a._alloc.stats() == eng_b._alloc.stats()
    assert eng_a._prefix.stats() == eng_b._prefix.stats()
    assert eng_a.check_invariants() == []
    # rejected requests consumed no ids either: the engines assigned
    # the same id sequence
    assert eng_a._next_id == eng_b._next_id


@pytest.mark.slow
def test_engine_stress_mixed_trace(rng):
    """Stress: many short requests with random arrivals through a
    small slot/page budget — every output token-exact, allocator
    balanced, zero steady-state recompiles."""
    net = _tiny_net(layers=1, heads=2, vocab=32, hidden=32)
    eng = Engine(net, max_slots=3, page_size=8, pool_pages=12,
                 max_context=48, prefill_bucket=8, watermark_pages=2)
    lens = rng.integers(2, 14, size=12)
    news = rng.integers(1, 9, size=12)
    prompts = _prompts(rng, lens, vocab=32)
    # warm the buckets with one pass, then measure the second
    eng.run([(p, SamplingParams(max_new_tokens=int(n)))
             for p, n in zip(prompts[:4], news[:4])])
    done = {}
    pending = list(zip(prompts, news))
    i = 0
    for step in range(400):
        if i < len(pending) and step % 2 == 0:
            p, n = pending[i]
            eng.add_request(p, SamplingParams(max_new_tokens=int(n)))
            i += 1
        for o in eng.step():
            done[o.req_id] = o
        if i == len(pending) and \
                eng.num_active == 0 and eng.num_waiting == 0:
            break
    # attribution is step-scoped: the reference generate() compiles
    # below must NOT leak into the engine's recompile tally
    steady = eng.steady_state_recompiles()
    # the warmup pass used req ids [0, 4); the measured wave follows
    assert len(done) == len(pending)
    for j, (p, n) in enumerate(pending):
        o = done[4 + j]
        assert o.token_ids == _ref_row(net, p, int(n)), j
    assert eng.pages_free == eng.pool_pages
    assert steady == 0
    # ...and the reference generate() compiles above did NOT leak into
    # the engine's tally (attribution is scoped to its own step()s)
    assert eng.steady_state_recompiles() == 0
