"""Disaggregated prefill/decode serving + TP-sharded decode
(docs/SERVING.md "Disaggregated serving").

The contract under test: splitting the serving loop into prefill
workers and decode workers — with KV pages migrating between their
separate pools — changes NOTHING about the tokens: every request
emits exactly the single-loop Engine's (and the b=1 generate()'s)
stream, through prefix-cache hits crossing the migration boundary,
speculative decoding, preemption/resume, mid-migration preemption,
snapshot/restore of a migrating request, and whole-worker deaths.
Each worker's compiled surface stays fixed (zero steady-state
recompiles per worker), and the migration step lints device-free as a
valid collective over the worker axis. TP side: mp=2 `generate` and
the engine decode step are token-exact vs single device across cache
variants.
"""
import asyncio

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.inference.disagg import (DisaggEngine, lint_migration,
                                         replay_rng_key)
from paddle_tpu.inference.engine import Engine, SamplingParams
from paddle_tpu.text.generation import generate
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


def _ref_rows(net, prompts, cfgs):
    return [np.asarray(generate(
        net, paddle.to_tensor(p[None]), c["max_new_tokens"],
        temperature=c.get("temperature", 0.0),
        top_k=c.get("top_k", 0), top_p=c.get("top_p", 0.0),
        seed=c.get("seed", 0)).numpy())[0, len(p):].tolist()
        for p, c in zip(prompts, cfgs)]


def _drained(eng):
    for w in eng.prefill + eng.decode:
        if w is None:
            continue
        held = sum(1 for r in w._slots if r is not None)
        assert held == 0, f"undrained worker slots: {held}"
    assert eng.num_waiting == 0 and eng.num_migrating == 0


@pytest.mark.slow
def test_disagg_greedy_token_exact_staggered(rng):
    """Requests arriving mid-flight, prefilled on one fleet and
    decoded on another, emit the exact b=1 generate() tokens.
    (`slow`: the staggered-arrival exactness surface is also held by
    test_disagg_matches_single_loop_engine and the MULTICHIP disagg
    phase — this variant rides the stress tier.)"""
    net = _tiny_net()
    prompts = _prompts(rng, (5, 9, 3, 7))
    cfgs = [dict(max_new_tokens=n) for n in (8, 6, 8, 5)]
    refs = _ref_rows(net, prompts, cfgs)
    eng = DisaggEngine(net, prefill_workers=2, decode_workers=2,
                       max_slots=2, page_size=8, pool_pages=64,
                       max_context=64)
    done = {}
    ids = [eng.add_request(prompts[0], SamplingParams(**cfgs[0])),
           eng.add_request(prompts[1], SamplingParams(**cfgs[1]))]
    for _ in range(3):
        for o in eng.step():
            done[o.req_id] = o
    ids.append(eng.add_request(prompts[2], SamplingParams(**cfgs[2])))
    ids.append(eng.add_request(prompts[3], SamplingParams(**cfgs[3])))
    for _ in range(60):
        for o in eng.step():
            done[o.req_id] = o
        if len(done) == 4:
            break
    assert len(done) == 4
    for rid, ref in zip(ids, refs):
        assert done[rid].token_ids == ref
        assert done[rid].finish_reason == "length"
    assert monitor.counter("serving.disagg.migrations").get() > 0
    _drained(eng)
    eng.close()


def test_disagg_matches_single_loop_engine(rng):
    """Same trace through the single-loop Engine and the disaggregated
    one: identical outputs — the split is a scheduler change, not a
    numeric one. Mixed greedy + seeded-sampling configs."""
    net = _tiny_net(seed=1)
    prompts = _prompts(rng, (6, 4, 11, 5))
    cfgs = [dict(max_new_tokens=7, temperature=0.9, seed=3),
            dict(max_new_tokens=5, temperature=1.2, top_k=8, top_p=0.9,
                 seed=7),
            dict(max_new_tokens=9, temperature=0.7, top_p=0.85,
                 seed=11),
            dict(max_new_tokens=6)]
    single = Engine(net, max_slots=4, page_size=8, pool_pages=64,
                    max_context=64)
    ref = single.run([(p, SamplingParams(**c))
                      for p, c in zip(prompts, cfgs)])
    eng = DisaggEngine(net, prefill_workers=1, decode_workers=2,
                       max_slots=2, page_size=8, pool_pages=64,
                       max_context=64)
    m0 = monitor.counter("serving.disagg.migrations").get()
    outs = eng.run([(p, SamplingParams(**c))
                    for p, c in zip(prompts, cfgs)])
    for r, o in zip(ref, outs):
        assert o.token_ids == r.token_ids
    assert monitor.counter("serving.disagg.migrations").get() > m0
    assert eng.steady_state_recompiles() == 0
    _drained(eng)
    single.close()
    eng.close()


# prefix matrix leg: disagg_matches_single_loop_engine keeps the
# prefill->decode migration path tier-1; cross-boundary prefix
# sharing rides the slow tier.
@pytest.mark.slow
def test_disagg_prefix_shared_pages_cross_boundary(rng):
    """Prefix-cache-shared pages crossing the prefill→decode boundary:
    the migrated copy is private to the decode worker, the prefill-side
    pages stay under the cache's references (refcounts preserved — the
    second request still hits), and outputs stay exact."""
    net = _tiny_net(seed=2)
    system = rng.integers(0, 64, (16,)).astype(np.int64)
    tails = _prompts(rng, (5, 7))
    prompts = [np.concatenate([system, t]) for t in tails]
    refs = _ref_rows(net, prompts,
                     [dict(max_new_tokens=6)] * 2)
    eng = DisaggEngine(net, prefill_workers=1, decode_workers=1,
                       max_slots=2, page_size=8, pool_pages=64,
                       max_context=64, prefix_cache=True)
    pw = eng.prefill[0]
    r0 = eng.add_request(prompts[0], SamplingParams(max_new_tokens=6))
    done = {}
    for _ in range(40):
        for o in eng.step():
            done[o.req_id] = o
        if r0 in done:
            break
    # request 0 finished and migrated away; its full pages live on
    # ONLY under the prefix cache's references
    cached = len(pw._prefix._store)
    assert cached >= 2                     # two full system pages
    for ent in pw._prefix._store.values():
        assert pw._alloc.refcount(ent.page) == 1
    r1 = eng.add_request(prompts[1], SamplingParams(max_new_tokens=6))
    for _ in range(40):
        for o in eng.step():
            done[o.req_id] = o
        if r1 in done:
            break
    assert done[r0].token_ids == refs[0]
    assert done[r1].token_ids == refs[1]
    assert monitor.counter("serving.prefix_hits").get() > 0
    assert pw.prefix_hit_rate > 0.0
    # drained: every page either free or under exactly one cache ref
    _drained(eng)
    assert pw._alloc.free_pages == pw.pool_pages - len(pw._prefix._store)
    assert eng.check_invariants() == []
    eng.close()


def test_disagg_spec_decode_token_exact(rng):
    """Draft/verify speculative decoding across the split: draft KV
    migrates beside the target KV, and the emitted streams stay
    bit-identical to the draft-free single-loop run."""
    net = _tiny_net(seed=3)
    draft = _tiny_net(seed=4, layers=1)
    prompts = _prompts(rng, (6, 9))
    cfgs = [dict(max_new_tokens=8),
            dict(max_new_tokens=7, temperature=0.8, seed=5)]
    refs = _ref_rows(net, prompts, cfgs)
    eng = DisaggEngine(net, prefill_workers=1, decode_workers=2,
                       max_slots=2, page_size=8, pool_pages=64,
                       max_context=64, draft_model=draft, spec_k=3)
    outs = eng.run([(p, SamplingParams(**c))
                    for p, c in zip(prompts, cfgs)])
    for o, ref in zip(outs, refs):
        assert o.token_ids == ref
    assert monitor.counter("serving.disagg.migrations").get() > 0
    assert eng.steady_state_recompiles() == 0
    # a post-worker-death snapshot still carries the fleet's spec_k
    # (worker 0 may be the dead slot — the crash-recovery artifact
    # must stay restorable)
    eng.kill_worker("decode", 0)
    assert eng.snapshot()["fingerprint"]["spec_k"] == 3
    eng.close()


def test_disagg_preempt_resume_round_trip(rng):
    """Decode-pool pressure preempts the youngest request back to the
    DRIVER (not the decode worker's own prefill surface); its resume
    re-prefills on the prefill fleet, re-migrates, and the stream is
    the exact uninterrupted one."""
    net = _tiny_net()
    prompts = _prompts(rng, (4, 3))
    refs = _ref_rows(net, prompts, [dict(max_new_tokens=10)] * 2)
    monitor.counter("serving.preemptions").reset()
    eng = DisaggEngine(net, prefill_workers=1, decode_workers=1,
                       max_slots=2, page_size=4, pool_pages=4,
                       prefill_pool_pages=8, prefill_bucket=4,
                       max_context=16, watermark_pages=0)
    outs = eng.run([(p, SamplingParams(max_new_tokens=10))
                    for p in prompts])
    assert monitor.counter("serving.preemptions").get() > 0
    for o, ref in zip(outs, refs):
        assert o.token_ids == ref
    _drained(eng)
    eng.close()


def test_disagg_mid_migration_preemption(rng):
    """A request parked MIGRATING (decode fleet full) can be preempted
    — prefill-side pages freed NOW — and still finishes token-exact
    after its re-prefill once capacity returns."""
    net = _tiny_net()
    prompts = _prompts(rng, (5, 6, 4))
    refs = _ref_rows(net, prompts, [dict(max_new_tokens=6)] * 3)
    eng = DisaggEngine(net, prefill_workers=1, decode_workers=1,
                       max_slots=1, page_size=8, pool_pages=8,
                       max_context=32)
    ids = [eng.add_request(p, SamplingParams(max_new_tokens=6))
           for p in prompts]
    done = {}
    parked = None
    for _ in range(80):
        for o in eng.step():
            done[o.req_id] = o
        if parked is None and eng.num_migrating > 0:
            # one decode slot busy, the next prefilled request parks
            parked = eng._ready[0][1].req_id
            pw = eng.prefill[0]
            free_before = pw._alloc.free_pages
            assert eng.preempt_migrating(parked)
            assert pw._alloc.free_pages > free_before   # pages back NOW
            assert monitor.counter(
                "serving.disagg.migration_preempts").get() > 0
        if len(done) == 3:
            break
    assert len(done) == 3
    assert parked is not None, "no request ever parked MIGRATING"
    for rid, ref in zip(ids, refs):
        assert done[rid].token_ids == ref
    _drained(eng)
    eng.close()


def test_disagg_snapshot_restore_migrating_state(rng):
    """snapshot() while a request sits in the MIGRATING state
    serializes it as resumable host truth (first token + replayed rng
    chain); restore into a FRESH driver finishes every request
    bit-identically — including seeded sampling."""
    net = _tiny_net(seed=5)
    prompts = _prompts(rng, (5, 7))
    cfgs = [dict(max_new_tokens=8, temperature=0.9, seed=13),
            dict(max_new_tokens=6)]
    refs = _ref_rows(net, prompts, cfgs)
    eng = DisaggEngine(net, prefill_workers=1, decode_workers=1,
                       max_slots=1, page_size=8, pool_pages=32,
                       max_context=64)
    ids = [eng.add_request(p, SamplingParams(**c))
           for p, c in zip(prompts, cfgs)]
    snap = None
    for _ in range(40):
        eng.step()
        if eng.num_migrating > 0:
            snap = eng.snapshot()          # one request mid-migration
            break
    assert snap is not None, "no MIGRATING state reached"
    states = {e["req_id"]: e for e in snap["requests"]}
    assert len(states) == 2
    eng.close()

    eng2 = DisaggEngine(net, prefill_workers=1, decode_workers=1,
                        max_slots=1, page_size=8, pool_pages=32,
                        max_context=64)
    assert eng2.restore(snap) == 2
    done = {}
    for _ in range(80):
        for o in eng2.step():
            done[o.req_id] = o
        if len(done) == 2:
            break
    for rid, ref in zip(ids, refs):
        assert done[rid].token_ids == ref
    _drained(eng2)
    eng2.close()


def test_disagg_worker_death_chaos(rng):
    """kill_worker drops a worker wholesale mid-trace; every request
    that lived there re-admits elsewhere from host truth alone (the
    dead device is never read) and finishes token-exact — prefill and
    decode deaths, greedy and seeded sampling."""
    net = _tiny_net(seed=6)
    prompts = _prompts(rng, (5, 8, 4, 6))
    cfgs = [dict(max_new_tokens=10),
            dict(max_new_tokens=9, temperature=0.8, seed=3),
            dict(max_new_tokens=8),
            dict(max_new_tokens=7, temperature=1.1, seed=9)]
    refs = _ref_rows(net, prompts, cfgs)
    eng = DisaggEngine(net, prefill_workers=2, decode_workers=2,
                       max_slots=2, page_size=8, pool_pages=64,
                       max_context=64)
    ids = [eng.add_request(p, SamplingParams(**c))
           for p, c in zip(prompts, cfgs)]
    done = {}
    killed = False
    for step in range(120):
        for o in eng.step():
            done[o.req_id] = o
        if not killed and eng.num_active > 0:
            # kill the decode worker holding the most live requests,
            # then a prefill worker — mid-decode failover both ways
            loads = [(sum(1 for r in w._slots if r is not None), i)
                     for i, w in enumerate(eng.decode) if w is not None]
            victim = max(loads)[1]
            assert eng.kill_worker("decode", victim) >= 0
            eng.kill_worker("prefill", 0)
            killed = True
        if len(done) == 4:
            break
    assert killed and len(done) == 4
    assert eng.decode[max(loads)[1]] is None
    for rid, ref in zip(ids, refs):
        assert done[rid].token_ids == ref, rid
    assert monitor.counter("serving.disagg.worker_kills").get() >= 2
    # the last worker of a kind is protected
    with pytest.raises(RuntimeError, match="last"):
        eng.kill_worker("prefill", 1)
    eng.close()


def test_replay_rng_key_matches_device_chain(rng):
    """The failover path's replayed rng chain equals the key the live
    engine pulls from the device — n splits from PRNGKey(seed) for n
    sampled tokens, untouched for greedy."""
    net = _tiny_net()
    p = _prompts(rng, (5,))[0]
    eng = Engine(net, max_slots=1, page_size=8, pool_pages=16,
                 max_context=32)
    rid = eng.add_request(p, SamplingParams(max_new_tokens=6,
                                            temperature=0.9, seed=11))
    req = eng.requests[rid]
    for _ in range(4):
        eng.step()
    # pull the device chain exactly like preemption does; the device
    # is one tick past the tokens the host holds (run-ahead)
    assert eng._inflight is not None
    key_dev = np.asarray(eng._dev[5])[req.slot].astype(np.uint32)
    key_replayed = replay_rng_key(11, len(req.generated) + 1, 0.9)
    np.testing.assert_array_equal(key_dev, key_replayed)
    assert (replay_rng_key(11, 5, 0.0)
            == np.asarray(jax.random.PRNGKey(11), np.uint32)).all()
    eng.close()


def test_disagg_streaming_front_door(rng):
    """stream() yields tokens incrementally as ticks produce them;
    astream() interleaves two consumers over one loop — both streams
    equal the b=1 generate() reference."""
    net = _tiny_net()
    prompts = _prompts(rng, (5, 7))
    refs = _ref_rows(net, prompts, [dict(max_new_tokens=6)] * 2)
    eng = DisaggEngine(net, prefill_workers=1, decode_workers=1,
                       max_slots=2, page_size=8, pool_pages=64,
                       max_context=64)
    rid = eng.add_request(prompts[0], SamplingParams(max_new_tokens=6))
    got = list(eng.stream(rid))
    assert got == refs[0]

    r0 = eng.add_request(prompts[0], SamplingParams(max_new_tokens=6))
    r1 = eng.add_request(prompts[1], SamplingParams(max_new_tokens=6))

    async def consume(r):
        toks = []
        async for t in eng.astream(r):
            toks.append(t)
        return toks

    async def both():
        return await asyncio.gather(consume(r0), consume(r1))

    t0, t1 = asyncio.run(both())
    assert t0 == refs[0]
    assert t1 == refs[1]
    eng.close()


def test_disagg_tenant_fairness(rng):
    """A flooding tenant cannot starve another tenant's request:
    dispatch round-robins one request per tenant per turn, so the
    single request of tenant B admits long before tenant A's flood
    drains."""
    net = _tiny_net()
    flood = _prompts(rng, (6,) * 4)
    single = _prompts(rng, (5,))[0]
    eng = DisaggEngine(net, prefill_workers=1, decode_workers=1,
                       max_slots=2, page_size=8, pool_pages=64,
                       max_context=64)
    flood_ids = [eng.add_request(p, SamplingParams(max_new_tokens=8),
                                 tenant="flood") for p in flood]
    vip = eng.add_request(single, SamplingParams(max_new_tokens=4),
                          tenant="vip")
    finish_order = []
    for _ in range(120):
        for o in eng.step():
            finish_order.append(o.req_id)
        if len(finish_order) == 5:
            break
    assert len(finish_order) == 5
    # the vip request (arrived after 8 flooders) finishes well before
    # the flood drains — round-robin put it second in line
    assert finish_order.index(vip) <= 2
    eng.close()


def test_disagg_zero_recompiles_mixed_trace(rng):
    """Mixed greedy/sampled traffic with migrations, preemptions and
    staggered arrivals keeps EVERY worker's compiled surface fixed:
    per-worker steady_state_recompiles() == 0."""
    net = _tiny_net()
    prompts = _prompts(rng, (5, 9, 3, 7, 6, 4))
    cfgs = [dict(max_new_tokens=6),
            dict(max_new_tokens=5, temperature=0.9, seed=3),
            dict(max_new_tokens=7),
            dict(max_new_tokens=4, temperature=0.7, top_k=8, seed=7),
            dict(max_new_tokens=6),
            dict(max_new_tokens=5)]
    eng = DisaggEngine(net, prefill_workers=2, decode_workers=2,
                       max_slots=2, page_size=8, pool_pages=64,
                       max_context=64)
    eng.run([(p, SamplingParams(**c)) for p, c in zip(prompts, cfgs)])
    # warm: now drive a second mixed wave — nothing may recompile
    eng.run([(p, SamplingParams(**c)) for p, c in zip(prompts, cfgs)])
    for i, w in enumerate(eng.prefill + eng.decode):
        assert w.steady_state_recompiles() == 0, f"worker {i}"
    assert eng.steady_state_recompiles() == 0
    eng.close()


def test_serving_replay_disagg_with_worker_kill(rng, capsys):
    """tools/serving_replay.py --disagg: per-worker utilization +
    migration counts in the report, and the --kill-worker failover
    chaos variant holds survivors token-exact (exit 0; a diverging
    survivor would exit 8)."""
    import json
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, "tools"))
    import serving_replay
    trace = os.path.join(os.path.dirname(__file__), "fixtures",
                         "serving_trace.jsonl")
    rc = serving_replay.main([
        trace, "--disagg", "--prefill-workers", "2",
        "--decode-workers", "2", "--kill-worker", "decode:1:8",
        "--expect-complete-timelines", "--json"])
    out = capsys.readouterr().out.strip().splitlines()[-1]
    assert rc == 0
    report = json.loads(out)
    dg = report["disagg"]
    assert dg["migrations"] > 0 and dg["migrated_pages"] > 0
    assert set(dg["workers"]) == {"prefill0", "prefill1", "decode0",
                                  "decode1"}
    assert not dg["workers"]["decode1"]["alive"]
    assert all(0.0 <= w["utilization"] <= 1.0
               for w in dg["workers"].values())
    wk = report["worker_kill"]
    assert wk["survivors_exact"] and wk["leaked_pages"] == 0
    assert report["steady_state_recompiles"] == 0


def test_migration_collective_lints_clean():
    """The migration step's redistribution expression validates
    device-free against worker meshes of several sizes — the static
    half of the MULTICHIP serving-disagg gate."""
    for w in (2, 3, 4):
        assert lint_migration(w, max_blocks=6, kv_heads=4, page_size=8,
                              head_dim=16, layers=2) == []
    assert lint_migration(2, max_blocks=6, kv_heads=4, page_size=8,
                          head_dim=16, quant=True) == []


def test_disagg_validates_requests(rng):
    net = _tiny_net()
    eng = DisaggEngine(net, prefill_workers=1, decode_workers=1,
                       max_slots=2, page_size=8, pool_pages=3,
                       prefill_pool_pages=8, max_context=32)
    with pytest.raises(ValueError, match="max_context"):
        eng.add_request(np.arange(30, dtype=np.int64) % 64,
                        SamplingParams(max_new_tokens=30))
    with pytest.raises(RuntimeError, match="never be scheduled"):
        eng.add_request(np.arange(8, dtype=np.int64),
                        SamplingParams(max_new_tokens=20))
    with pytest.raises(ValueError, match="ONE prompt"):
        eng.add_request(np.zeros((2, 4), np.int64))
    with pytest.raises(ValueError):
        DisaggEngine(net, prefill_workers=0, decode_workers=1)
    with pytest.raises(ValueError, match="kind"):
        eng.kill_worker("prefil", 0)       # typo must not kill decode
    with pytest.raises(ValueError, match="out of range"):
        eng.kill_worker("decode", -1)
    eng.close()


# -- TP-sharded decode -------------------------------------------------------

@pytest.fixture
def mp2_mesh():
    prev = mesh_mod.get_mesh()
    m = mesh_mod.build_mesh({"dp": 1, "mp": 2},
                            devices=jax.devices()[:2])
    # install paddle's global too: on a jax with native set_mesh the
    # `with jax.set_mesh(...)` in the tests would otherwise leave
    # llama's TP layer selection reading an unset global (dense model)
    mesh_mod.set_mesh(m)
    yield m
    mesh_mod._global_mesh = prev


def _dense_refs(cfg, x, make_refs):
    """Build the single-device reference model + outputs, restoring
    the ambient mesh after."""
    prev = mesh_mod.get_mesh()
    mesh_mod.set_mesh(mesh_mod.build_mesh(
        {"dp": 1}, devices=[jax.devices()[0]]))
    try:
        paddle.seed(2)
        dense = LlamaForCausalLM(cfg)
        dense.eval()
        sd = {n: np.asarray(p._data)
              for n, p in dense.named_parameters()}
        return sd, make_refs(dense)
    finally:
        mesh_mod._global_mesh = prev


def test_llama_tp2_generate_token_exact(mp2_mesh):
    """mp=2 TP-sharded generate — dense, paged and int8-KV cache
    variants, greedy and seeded sampling — emits exactly the
    single-device tokens (VERDICT's "TP-sharded generate" ask)."""
    cfg = LlamaConfig.tiny()
    cfg.use_flash_attention = False
    rng = np.random.default_rng(2)
    x = paddle.to_tensor(rng.integers(0, cfg.vocab_size,
                                      (2, 8)).astype(np.int64))

    def refs(net):
        return [
            np.asarray(generate(net, x, 12).numpy()),
            np.asarray(generate(net, x, 12, cache_impl="paged",
                                page_size=8).numpy()),
            np.asarray(generate(net, x, 12, cache_impl="paged",
                                page_size=8,
                                cache_dtype="int8").numpy()),
            np.asarray(generate(net, x, 12, temperature=0.8, top_k=8,
                                seed=5).numpy()),
        ]

    sd, ref = _dense_refs(cfg, x, refs)
    with jax.set_mesh(mp2_mesh):
        paddle.seed(2)
        net = LlamaForCausalLM(cfg)
        for n, p in net.named_parameters():
            p.set_value(sd[n])
        net.eval()
        out = refs(net)
    for i, (o, r) in enumerate(zip(out, ref)):
        np.testing.assert_array_equal(o, r, err_msg=f"variant {i}")


@pytest.mark.slow  # tp2 matrix leg: test_llama_tp2_generate_token_exact
# keeps the mp=2 decode parity path in tier-1 at a third of the cost
def test_llama_tp2_engine_decode_token_exact(mp2_mesh):
    """The serving engine's fused decode step under mp=2 (KV pools
    sharded over the kv-head axis): token-exact vs the single-device
    engine run, auto AND int8 cache dtypes, with zero steady-state
    recompiles — committing the device state's sharding keeps ONE
    compiled decode surface."""
    cfg = LlamaConfig.tiny()
    cfg.use_flash_attention = False
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int64)
               for n in (5, 9, 3)]
    cfgs = [dict(max_new_tokens=8),
            dict(max_new_tokens=6, temperature=0.9, seed=3),
            dict(max_new_tokens=7)]

    def refs(net):
        ref = {}
        for dt in ("auto", "int8"):
            eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                         max_context=64, cache_dtype=dt)
            outs = eng.run([(p, SamplingParams(**c))
                            for p, c in zip(prompts, cfgs)])
            ref[dt] = [o.token_ids for o in outs]
            eng.close()
        return ref

    sd, ref = _dense_refs(cfg, None, refs)
    with jax.set_mesh(mp2_mesh):
        paddle.seed(2)
        net = LlamaForCausalLM(cfg)
        for n, p in net.named_parameters():
            p.set_value(sd[n])
        net.eval()
        for dt in ("auto", "int8"):
            eng = Engine(net, max_slots=2, page_size=8, pool_pages=64,
                         max_context=64, cache_dtype=dt)
            outs = eng.run([(p, SamplingParams(**c))
                            for p, c in zip(prompts, cfgs)])
            for o, r in zip(outs, ref[dt]):
                assert o.token_ids == r, dt
            assert eng.steady_state_recompiles() == 0, dt
            eng.close()
