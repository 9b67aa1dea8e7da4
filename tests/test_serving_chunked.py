"""Chunked prefill (docs/SERVING.md "Chunked prefill").

The contract under test: ``Engine(max_prefill_tokens_per_step=N)``
splits long prompts into bounded bucketed slices interleaved with
decode ticks, and the slicing is PURELY a scheduling change — token
streams are bit-identical to the monolithic engine (greedy and seeded
sampling, prefix hits deeper than one bucket, preemption at a slice
boundary, snapshot/restore mid-prefill, speculative decoding), zero
steady-state recompiles hold across mixed whale/small traffic, a
mid-prefill request stays cancellable / deadline-expirable with all
pages freed, and ``add_request`` charges the per-slice peak so a long
prompt that fits incrementally is admitted (the monolithic engine
rejects it). The long-context replay fixture's p99-TTFT gate rides in
tools/serving_replay.py.
"""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference.engine import (PREFILL, Engine,
                                         SamplingParams)
from paddle_tpu.text.generation import generate
from paddle_tpu.text.models import LlamaConfig, LlamaForCausalLM


def _tiny_net(seed=0, layers=2, heads=4, vocab=64, hidden=64, kv=None):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(vocab=vocab, hidden=hidden, layers=layers,
                           heads=heads)
    if kv is not None:
        cfg.num_key_value_heads = kv
    cfg.use_flash_attention = False
    net = LlamaForCausalLM(cfg)
    net.eval()
    return net


def _ref_row(net, prompt, max_new, **kw):
    out = np.asarray(generate(net, paddle.to_tensor(prompt[None]),
                              max_new, **kw).numpy())
    return out[0, len(prompt):].tolist()


def _drain(eng, max_steps=400):
    outs = {}
    for _ in range(max_steps):
        for o in eng.step():
            outs[o.req_id] = o
        if eng.idle:
            break
    return outs


def test_chunked_token_exact_vs_monolithic_and_generate(rng):
    """Mixed whale/small traffic (greedy + seeded sampling, GQA):
    the chunked engine emits exactly the monolithic engine's tokens —
    which are exactly b=1 generate()'s — with zero steady-state
    recompiles and slices actually happening."""
    net = _tiny_net(kv=2)
    whale = rng.integers(0, 64, (90,)).astype(np.int64)
    smalls = [rng.integers(0, 64, (n,)).astype(np.int64)
              for n in (5, 9)]
    reqs = [(whale, SamplingParams(max_new_tokens=6)),
            (smalls[0], SamplingParams(max_new_tokens=8,
                                       temperature=0.9, seed=3)),
            (smalls[1], SamplingParams(max_new_tokens=5))]

    def run(max_pf):
        eng = Engine(net, max_slots=4, page_size=8, pool_pages=96,
                     max_context=128, prefill_bucket=16,
                     max_prefill_tokens_per_step=max_pf)
        outs = eng.run(reqs)
        assert eng.steady_state_recompiles() == 0
        assert eng.pages_free == eng.pool_pages
        return [o.token_ids for o in outs]

    slices0 = int(monitor.counter("serving.prefill_slices").get())
    mono = run(None)
    chunked = run(32)
    assert chunked == mono
    # the whale's 90-token prompt really ran as multiple 32-token
    # slices (plus the smalls' single-slice prefills)
    assert int(monitor.counter("serving.prefill_slices").get()) \
        - slices0 >= 3 + 3
    refs = [_ref_row(net, whale, 6),
            _ref_row(net, smalls[0], 8, temperature=0.9, seed=3),
            _ref_row(net, smalls[1], 5)]
    assert chunked == refs


def test_chunked_prefix_hit_deeper_than_one_bucket(rng):
    """Prefix-cache composition: a second request sharing a 48-token
    prefix (3 pages, 3 bucket-sized chunks deep) maps the cached head
    and slices only its tail — token streams stay exact and the reuse
    counters show the deep hit."""
    net = _tiny_net(seed=1)
    shared = rng.integers(0, 64, (48,)).astype(np.int64)
    tails = [rng.integers(0, 64, (n,)).astype(np.int64)
             for n in (37, 21)]
    prompts = [np.concatenate([shared, t]) for t in tails]
    reqs = [(p, SamplingParams(max_new_tokens=5)) for p in prompts]

    def run(max_pf):
        eng = Engine(net, max_slots=2, page_size=16, pool_pages=64,
                     max_context=128, prefill_bucket=16,
                     prefix_cache=True,
                     max_prefill_tokens_per_step=max_pf)
        # serialize the two requests so the second hits the cache
        o1 = eng.run([reqs[0]])
        reused0 = int(
            monitor.counter("serving.prefix_tokens_reused").get())
        o2 = eng.run([reqs[1]])
        reused = int(
            monitor.counter("serving.prefix_tokens_reused").get()) \
            - reused0
        return [o1[0].token_ids, o2[0].token_ids], reused

    mono, reused_m = run(None)
    chunked, reused_c = run(16)
    assert chunked == mono
    # the whole 48-token (3-page) shared head was skipped — deeper
    # than one 16-token prefill bucket — in BOTH modes
    assert reused_m == 48 and reused_c == 48
    assert chunked[0] == _ref_row(net, prompts[0], 5)
    assert chunked[1] == _ref_row(net, prompts[1], 5)


def test_preempt_mid_prefill_at_slice_boundary(rng):
    """Pool pressure mid-prefill: a decoding request's page growth
    lands on an empty pool while the whale is half-prefilled — the
    whale (youngest) is preempted AT THE SLICE BOUNDARY, its pages
    return, and its restarted prefill still emits the exact tokens."""
    net = _tiny_net(seed=2)
    a = rng.integers(0, 64, (22,)).astype(np.int64)
    whale = rng.integers(0, 64, (112,)).astype(np.int64)
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=16,
                 max_context=128, prefill_bucket=8,
                 max_prefill_tokens_per_step=8)
    ra = eng.add_request(a, SamplingParams(max_new_tokens=16))
    rw = eng.add_request(whale, SamplingParams(max_new_tokens=4))
    # run until the tick BEFORE request A's next page-growth step,
    # then pin the pool so the whale's slice takes the LAST free page
    # and A's growth lands on an empty pool
    for _ in range(12):
        eng.step()
    wreq = eng.requests[rw]
    assert wreq.state == PREFILL and 0 < wreq.written < len(whale)
    stolen = eng._alloc.alloc(eng.pages_free - 1, seq="pin")
    eng.step()
    assert eng.requests[rw].preemptions == 1   # evicted mid-prefill
    assert eng.requests[rw].state in ("WAITING", PREFILL)
    eng._alloc.free(stolen)
    outs = _drain(eng)
    assert set(outs) == {ra, rw}
    assert outs[rw].preemptions == 1
    assert outs[ra].token_ids == _ref_row(net, a, 16)
    assert outs[rw].token_ids == _ref_row(net, whale, 4)
    assert eng.pages_free == eng.pool_pages
    assert eng.steady_state_recompiles() == 0


# snapshot matrix leg: reliability's snapshot_restore_token_exact_
# full_matrix keeps snapshot/restore tier-1; the chunked-slice
# boundary variant rides slow.
@pytest.mark.slow
def test_snapshot_restore_at_slice_boundary(rng):
    """snapshot() while the whale is half-prefilled (state PREFILL
    between ticks) restores through the resume machinery bit-exactly:
    the restored engine's outputs equal an uninterrupted run's."""
    net = _tiny_net(seed=3)
    whale = rng.integers(0, 64, (80,)).astype(np.int64)
    small = rng.integers(0, 64, (6,)).astype(np.int64)
    reqs = [(whale, SamplingParams(max_new_tokens=5)),
            (small, SamplingParams(max_new_tokens=7, temperature=1.1,
                                   seed=9))]

    def make():
        return Engine(net, max_slots=2, page_size=8, pool_pages=64,
                      max_context=128, prefill_bucket=16,
                      max_prefill_tokens_per_step=16)

    ref_eng = make()
    ref = {o.req_id: o.token_ids for o in ref_eng.run(reqs)}

    eng = make()
    for p, sp in reqs:
        eng.add_request(p, sp)
    eng.step()
    eng.step()
    mid = [r for r in eng._slots if r is not None
           and r.state == PREFILL]
    assert mid and 0 < mid[0].written < len(mid[0].prompt)
    snap = eng.snapshot()
    eng2 = make()
    assert eng2.restore(snap) == 2
    outs = _drain(eng2)
    assert {rid: o.token_ids for rid, o in outs.items()} == ref
    assert eng2.pages_free == eng2.pool_pages


def test_chunked_spec_decode_exact(rng):
    """Speculative decoding over chunked prefill: the draft pools
    mirror every slice, and the drafted engine's output is
    bit-identical to the draft-free chunked engine."""
    net = _tiny_net(seed=4)
    paddle.seed(5)
    dcfg = LlamaConfig.tiny(vocab=64, hidden=64, layers=1, heads=4)
    dcfg.use_flash_attention = False
    draft = LlamaForCausalLM(dcfg)
    draft.eval()
    whale = rng.integers(0, 64, (70,)).astype(np.int64)
    small = rng.integers(0, 64, (7,)).astype(np.int64)
    reqs = [(whale, SamplingParams(max_new_tokens=6)),
            (small, SamplingParams(max_new_tokens=8))]

    def run(dm):
        eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                     max_context=96, prefill_bucket=16,
                     draft_model=dm, spec_k=3,
                     max_prefill_tokens_per_step=16)
        outs = eng.run(reqs)
        assert eng.steady_state_recompiles() == 0
        return [o.token_ids for o in outs]

    assert run(draft) == run(None)


def test_deadline_expiry_mid_prefill_frees_all_pages(rng):
    """A whale whose deadline lapses between slices is FAILED at the
    next tick start with every partially written page freed — nothing
    leaks, and the co-resident small request is untouched."""
    vt = [0.0]
    net = _tiny_net(seed=6)
    whale = rng.integers(0, 64, (96,)).astype(np.int64)
    small = rng.integers(0, 64, (5,)).astype(np.int64)
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                 max_context=128, prefill_bucket=16,
                 max_prefill_tokens_per_step=16,
                 clock=lambda: vt[0])
    rw = eng.add_request(whale, SamplingParams(max_new_tokens=4,
                                               deadline_ms=50.0))
    rs = eng.add_request(small, SamplingParams(max_new_tokens=6))
    eng.step()                                 # slice 1 of the whale
    req = eng.requests[rw]
    assert req.state == PREFILL and 0 < req.written < len(whale)
    assert req.pages
    vt[0] = 0.2                                # 200ms > 50ms deadline
    outs = {o.req_id: o for o in eng.step()}
    assert outs[rw].error == "deadline"
    outs.update(_drain(eng))
    assert outs[rs].ok
    assert outs[rs].token_ids == _ref_row(net, small, 6)
    assert eng.pages_free == eng.pool_pages


def test_cancel_mid_prefill_frees_pages(rng):
    net = _tiny_net(seed=6)
    whale = rng.integers(0, 64, (96,)).astype(np.int64)
    eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                 max_context=128, prefill_bucket=16,
                 max_prefill_tokens_per_step=16)
    rw = eng.add_request(whale, SamplingParams(max_new_tokens=4))
    eng.step()
    assert eng.requests[rw].state == PREFILL
    out = eng.cancel(rw)
    assert out is not None and out.error == "cancelled"
    assert eng.pages_free == eng.pool_pages


def test_add_request_charges_per_slice_peak(rng):
    """The lifetime-page admission check under chunked prefill charges
    the per-slice peak: a prompt that fits incrementally is accepted
    (and completes) where the monolithic engine rejects the bucketed
    whole — and a genuinely oversized request is still refused."""
    net = _tiny_net(seed=7)
    prompt = rng.integers(0, 64, (96,)).astype(np.int64)

    def make(max_pf, pool):
        return Engine(net, max_slots=1, page_size=8, pool_pages=pool,
                      max_context=128, prefill_bucket=16,
                      max_prefill_tokens_per_step=max_pf)

    # monolithic peak: pbucket(96 + 4) = 112 tokens -> 13 pages;
    # sliced peak: max(96 prefill, 99 decode+lookahead) -> 13... use a
    # pool of 12: chunked (ceil(100/8) = 13? no — decode peak 96+4-1+1
    # = 100 -> 13) — pick sizes where the two modes disagree:
    # prompt 90, new 2: mono pbucket(92)=96+lookahead-1 -> 12 pages;
    # chunked peak = max(88+16=104 clipped... measure via the engine's
    # own helper to keep the boundary exact under refactors.
    eng_c = make(16, 1)
    need_c = eng_c._lifetime_pages(len(prompt), 4)
    eng_m = make(None, 1)
    need_m = eng_m._lifetime_pages(len(prompt), 4)
    assert need_c < need_m          # slicing lowers the peak
    pool = need_c                   # fits incrementally, not bucketed
    eng = make(16, pool)
    rid = eng.add_request(prompt, SamplingParams(max_new_tokens=4))
    outs = _drain(eng)
    assert outs[rid].token_ids == _ref_row(net, prompt, 4)
    with pytest.raises(RuntimeError, match="never be scheduled"):
        make(None, pool).add_request(
            prompt, SamplingParams(max_new_tokens=4))
    # a genuinely oversized request (peak pages beyond the pool even
    # when sliced) is still refused
    with pytest.raises(RuntimeError, match="never be scheduled"):
        make(16, pool).add_request(
            rng.integers(0, 64, (100,)).astype(np.int64),
            SamplingParams(max_new_tokens=20))


def test_longctx_replay_p99_ttft_gate(capsys):
    """The long-context fixture under chunked prefill passes the
    whale-starvation gate: small-request p99 TTFT stays within 2x the
    small-only baseline on the deterministic virtual clock (the
    monolithic contrast trips the same gate — nightly test below)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import serving_replay
    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "serving_trace_longctx.jsonl")
    # small-only baseline p99 on this fixture/geometry is ~16.4ms
    # (recorded in docs/SERVING.md; a first token is charged to the
    # step that harvests it, the one after its chunk's); 33 ≈ the 2x bar
    rc = serving_replay.main([
        fixture, "--pool-pages", "256", "--max-slots", "8",
        "--max-prefill-tokens", "32",
        "--expect-p99-ttft-ms", "33", "--ttft-tag", "small",
        "--expect-complete-timelines", "--json"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    report = json.loads(out[-1])
    assert report["steady_state_recompiles"] == 0
    assert not report["failed"]
    assert report["ttft_ms_by_tag"]["small"]["p99"] <= 33
    # whales finish too (bounded slowdown, not starvation)
    assert report["ttft_ms_by_tag"]["whale"]["p99"] > 0


@pytest.mark.slow
def test_longctx_replay_monolithic_trips_gate(capsys):
    """Contrast run: WITHOUT chunked prefill the same trace blows the
    small-request p99 budget (exit 7) — whale prefills monopolize the
    loop exactly the way the gate exists to catch."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import serving_replay
    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "serving_trace_longctx.jsonl")
    rc = serving_replay.main([
        fixture, "--pool-pages", "256", "--max-slots", "8",
        "--expect-p99-ttft-ms", "33", "--ttft-tag", "small",
        "--json"])
    capsys.readouterr()
    assert rc == 7
