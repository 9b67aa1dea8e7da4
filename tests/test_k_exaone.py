"""K-EXAONE (text/models/k_exaone.py): sliding-window GQA layers on a
per-slot ring of keys and values beside a full GQA layer on the paged
cache, against the plain reference (benchmark/reference/k_exaone.py), at
a tiny size, float32 both sides; and what the engine does with a slot's
rings.

Tiny size: hidden 64, (sliding, sliding, sliding, full, sliding) with a
window of 8, 4 heads of 16 (2 KV heads), the first layer dense, 8
experts top-2 with one shared, the prediction module."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import k_exaone as ref
from paddle_tpu import monitor
from paddle_tpu.core.dispatch import unwrap
from paddle_tpu.incubate.distributed.models.moe import MoELayer
from paddle_tpu.inference.engine import (Engine, SamplingParams,
                                         _make_spec_pools)
from paddle_tpu.kernels import paged_attention as paged
from paddle_tpu.text.models import KExaoneConfig, KExaoneForCausalLM
from paddle_tpu.text.models.k_exaone import FULL, SLIDING, ring_gqa

TOL = 1e-4
W = 8                                   # the tiny window


@pytest.fixture(autouse=True)
def _small_reference_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(7)
    cfg = KExaoneConfig.tiny()
    net = KExaoneForCausalLM(cfg)
    net.eval()
    return cfg, net, dataclasses.asdict(cfg), ref.model_weights(net)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 96, n)


def _engine(net, **kw):
    args = dict(max_slots=4, page_size=8, prefill_bucket=4,
                max_context=192, keep_logits=True)
    args.update(kw)
    return Engine(net, **args)


def _drain(eng):
    outs = []
    while not eng.idle:
        outs.extend(eng.step())
    return outs


def _against_reference(tiny, out, prompt):
    _, _, model, weights = tiny
    seq = np.concatenate([prompt, out.token_ids[:-1]])
    want = np.asarray(ref.logits(weights, model, seq))[len(prompt) - 1:]
    return ref.errors(np.stack(out.logits), want)["max"]


def test_the_layer_pattern_is_the_published_one():
    cfg = KExaoneConfig()
    assert cfg.layer_types[:5] == (SLIDING, SLIDING, SLIDING, FULL, SLIDING)
    assert cfg.layer_types.count(FULL) == 12 and len(cfg.layer_types) == 48
    assert cfg.sliding_windows[:5] == (128, 128, 128, 0, 128)
    assert cfg.mlp_layer_types == ("dense",) + ("sparse",) * 47
    with pytest.raises(ValueError, match="sliding_windows"):
        KExaoneConfig.tiny(sliding_windows=[8, 8, 8, 8, 8])
    with pytest.raises(ValueError, match="layer_types"):
        KExaoneConfig.tiny(layer_types=[SLIDING] * 4)
    with pytest.raises(ValueError, match="scoring_func"):
        KExaoneConfig.tiny(scoring_func="softmax")


# 5 windows; a length that is no multiple of the query block (the whole
# sequence is then one block)
@pytest.mark.parametrize("n", [40, 37])
def test_full_forward_matches_the_reference(tiny, n):
    cfg, net, model, weights = tiny
    ids = _ids(n)
    got = np.asarray(unwrap(net(paddle.to_tensor(ids[None]))))[0]
    want = np.asarray(ref.logits(weights, model, ids))
    assert ref.errors(got, want)["max"] < TOL
    # and the comparison can tell: each mechanism matters
    for off in (dict(window=False), dict(rope=False), dict(qk_norm=False)):
        other = np.asarray(ref.logits(weights, model, ids, **off))
        assert ref.errors(got, other)["rms"] > 0.05, off


def test_the_prediction_module_matches_the_reference(tiny):
    cfg, net, model, weights = tiny
    ids = _ids(40, seed=3)
    got = np.asarray(unwrap(net.mtp_logits(paddle.to_tensor(ids[None]))))[0]
    want = np.asarray(ref.mtp_logits(weights, model, ids))
    assert got.shape == (39, cfg.vocab_size)
    assert ref.errors(got, want)["max"] < TOL
    # it is a module of its own: not the trunk's next-token logits
    trunk = np.asarray(ref.logits(weights, model, ids))[1:]
    assert ref.errors(got, trunk)["rms"] > 0.05
    # and the served configuration does not build it
    net0 = KExaoneForCausalLM(KExaoneConfig.tiny(num_nextn_predict_layers=0))
    assert not any(n.startswith("mtp.") for n, _ in net0.named_parameters())
    assert any(n.startswith("mtp.") for n, _ in net.named_parameters())


def test_both_layer_kinds_and_the_dense_layer_are_in_the_stack(tiny):
    cfg, net, _, _ = tiny
    kinds = [(lyr.kind, lyr.is_moe) for lyr in net.layers]
    assert kinds == [(SLIDING, False), (SLIDING, True), (SLIDING, True),
                     (FULL, True), (SLIDING, True)]
    assert [lyr.self_attn.window for lyr in net.layers] == [W, W, W, None, W]


@pytest.mark.parametrize("chunk", [None, 44],
                         ids=["monolithic", "chunked-44"])
def test_engine_prefill_and_decode_match_the_reference(tiny, chunk):
    """Prefill, then decode, through the slot's rings and the paged
    cache: the logits rows the engine sampled from against the
    reference's full forward pass over the same tokens. The chunked
    prompt runs as 44 + 26 tokens: the boundary lies INSIDE a window
    (44 = 5 windows + 4), so the second chunk's first queries read the
    rows the first left in the ring, with a second sequence decoding
    between the two chunks at another position."""
    cfg, net, _, _ = tiny
    prompt, n_new = _ids(70, seed=1), 12
    eng = _engine(net, max_prefill_tokens_per_step=chunk)
    between = []
    run = eng._run_prefills

    def spy():
        mid = [r for r in eng._slots if r is not None
               and r.state == "PREFILL" and r.written > 0]
        if mid and eng._inflight is not None:
            between.append(len(eng._inflight.active))
        return run()

    eng._run_prefills = spy
    try:
        eng.add_request(prompt, SamplingParams(max_new_tokens=n_new,
                                               return_logits=True))
        eng.add_request(_ids(13, seed=2), SamplingParams(max_new_tokens=6))
        outs = _drain(eng)
        assert eng.leaked_pages() == 0
    finally:
        eng.close()
    out = next(o for o in outs if o.logits is not None)
    assert out.ok and len(out.token_ids) == n_new
    assert _against_reference(tiny, out, prompt) < TOL
    assert next(o for o in outs if o.logits is None).ok
    assert bool(between) == (chunk is not None)


def test_lanes_at_different_positions_without_a_recompile(tiny):
    """Four requests of different lengths (shorter than a window, a
    window exactly, several windows) decode side by side, each against
    the reference; a second wave through the warm engine compiles
    nothing and leaks nothing."""
    cfg, net, _, _ = tiny
    eng = _engine(net)
    lengths = [5, 8, 21, 40]
    try:
        for wave in range(2):
            prompts = [_ids(n, seed=10 * wave + i)
                       for i, n in enumerate(lengths)]
            if wave == 1:
                before = eng.steady_state_recompiles()
            for p in prompts:
                eng.add_request(p, SamplingParams(max_new_tokens=10,
                                                  return_logits=True))
            outs = sorted(_drain(eng), key=lambda o: o.req_id)
            for p, out in zip(prompts, outs):
                assert out.ok and len(out.token_ids) == 10
                assert _against_reference(tiny, out, p) < TOL
        assert eng.steady_state_recompiles() == before
        assert eng.leaked_pages() == 0
    finally:
        eng.close()


def test_a_preempted_request_has_its_rings_rebuilt(tiny):
    """A pool too small for both sequences preempts the youngest: its
    pages are freed and its rings forgotten; the resume prefill rebuilds
    both from the kept tokens, and the logits still match."""
    cfg, net, _, _ = tiny
    prompts = [_ids(20, seed=3), _ids(20, seed=4)]
    recomputes = monitor.counter("serving.state.recomputes")
    n0 = recomputes.get()
    eng = _engine(net, max_slots=2, pool_pages=7, watermark_pages=0,
                  max_context=64)
    try:
        for p in prompts:
            eng.add_request(p, SamplingParams(max_new_tokens=12,
                                              return_logits=True))
        outs = sorted(_drain(eng), key=lambda o: o.req_id)
        assert eng.leaked_pages() == 0
    finally:
        eng.close()
    assert max(o.preemptions for o in outs) > 0
    assert recomputes.get() > n0
    for p, out in zip(prompts, outs):
        assert out.ok and len(out.token_ids) == 12
        assert _against_reference(tiny, out, p) < TOL


def test_a_reused_slot_reads_none_of_the_rows_left_in_its_rings(tiny):
    """No reset: the second request starts on rings full of the first
    one's rows, and the context length keeps them out."""
    cfg, net, _, _ = tiny
    eng = _engine(net, max_slots=1, max_context=64)
    try:
        for seed, n in ((5, 30), (6, 3)):
            prompt = _ids(n, seed=seed)
            eng.add_request(prompt, SamplingParams(max_new_tokens=4,
                                                   return_logits=True))
            out, = _drain(eng)
            assert _against_reference(tiny, out, prompt) < TOL
        # 3 + 4 tokens < a window: a row of the first request is still there
        assert float(jnp.abs(eng._pools[0][0][0, :, W - 1]).max()) > 0
    finally:
        eng.close()


@pytest.mark.parametrize("max_context", [64, 512])
def test_no_sliding_layer_array_grows_with_max_context(tiny, max_context):
    cfg, net, _, _ = tiny
    eng = _engine(net, max_slots=3, max_context=max_context)
    try:
        assert eng._cache_kinds == ["state"] * 3 + ["kv", "state"]
        for kind, layer in zip(eng._cache_kinds, eng._pools):
            shapes = [tuple(a.shape) for a in layer]
            if kind == "state":
                assert shapes == [(3, 2, W, 16)] * 2
            else:
                assert shapes == [(3 * max_context // 8 + 1, 2, 8, 16)] * 2
        assert monitor.snapshot()["serving.state.bytes"] == \
            4 * 2 * 3 * 2 * W * 16 * 4
    finally:
        eng.close()


def test_the_window_gauge_counts_paged_layers_only(tiny):
    """Every windowed layer of this spec keeps a ring by slot, so no
    page lies outside a window, however long the contexts (a spec whose
    windowed layers are paged still counts them:
    tests/test_dots3_note.py)."""
    cfg, net, _, _ = tiny
    eng = _engine(net, max_slots=2)
    try:
        assert eng.serving_spec["window"] == W and not eng._paged_window
        eng.add_request(_ids(40, seed=1), SamplingParams(max_new_tokens=6))
        while not eng.idle:
            eng.step()
            assert monitor.snapshot()[
                "serving.cache.swa_pages_outside_window"] == 0
    finally:
        eng.close()


@pytest.mark.parametrize("lane", ["between-chunks", "dead-under-run-ahead",
                                  "free"])
def test_a_decode_tick_leaves_other_slots_rings_bit_identical(tiny, lane):
    """The decode program on rings full of numbers: slot 0 decodes; slot
    1 is not live (a slot between two prefill chunks, or a free one) or
    is live with its budget spent. Only slot 0's rows change, and of
    those only the row its position names."""
    cfg, net, _, _ = tiny
    eng = _engine(net, max_slots=3, max_context=64)
    try:
        rng = np.random.default_rng(8)
        pools = [tuple(jnp.asarray(rng.normal(size=a.shape), a.dtype)
                       for a in layer) if kind == "state" else layer
                 for kind, layer in zip(eng._cache_kinds, eng._pools)]
        before = [[np.asarray(a) for a in layer] for layer in pools]
        eng._bt[:, 0] = [1, 2, 3]
        eng._pos[:] = [11, 5, 0]
        eng._live[:] = [1, int(lane == "dead-under-run-ahead"), 0]
        eng._bud[:] = [4, 0, 0]
        state = tuple(eng._up(m) for m in eng._mirrors())
        _, ok, state2, new, *_ = eng._get_decode_fn("greedy")(
            eng._st, pools, eng._up(eng._bt), state, eng._poison_zeros)
        assert np.asarray(ok).all()
        assert np.asarray(state2[1]).tolist() == [12, 5, 0]
        for kind, old, got in zip(eng._cache_kinds, before, new):
            if kind != "state":
                continue
            for a, b in zip(old, got):
                b = np.asarray(b)
                assert (a[1:] == b[1:]).all()          # bit for bit
                changed = (a[0] != b[0]).any(axis=(0, 2))
                assert changed.tolist() == [r == 11 % W for r in range(W)]
    finally:
        eng.close()


def test_paged_decode_on_a_ring_is_the_windows_attention():
    """The kernel the chip runs (interpret mode here) on rings as a pool
    of one page a slot, against a plain softmax over each slot's last
    `window` keys in position order: the order of keys inside a softmax
    does not matter."""
    rng = np.random.default_rng(4)
    slots, G, H, Wn, d = 3, 2, 4, 16, 128
    pos = np.asarray([40, 5, 16])              # wrapped, not full, full
    keys = rng.normal(size=(slots, 64, G, d)).astype("float32")
    vals = rng.normal(size=(slots, 64, G, d)).astype("float32")
    kr = np.zeros((slots, G, Wn, d), "float32")
    vr = np.zeros_like(kr)
    for s in range(slots):
        for p in range(pos[s] + 1):
            kr[s, :, p % Wn], vr[s, :, p % Wn] = keys[s, p], vals[s, p]
    q = rng.normal(size=(slots, H, d)).astype("float32")
    ctx = np.minimum(pos + 1, Wn)
    got = paged.paged_decode_pallas(
        jnp.asarray(q), jnp.asarray(kr), jnp.asarray(vr),
        jnp.arange(slots, dtype=jnp.int32)[:, None], jnp.asarray(ctx),
        interpret=True)
    same = paged.paged_attention_arrays(
        jnp.asarray(q), jnp.asarray(kr), jnp.asarray(vr),
        jnp.arange(slots, dtype=jnp.int32)[:, None], jnp.asarray(ctx))
    for s in range(slots):
        lo = max(0, pos[s] - Wn + 1)
        k, v = keys[s, lo:pos[s] + 1], vals[s, lo:pos[s] + 1]
        sc = np.einsum("grd,Lgd->grL", q[s].reshape(G, H // G, d), k) \
            / np.sqrt(d)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("grL,Lgd->grd", p / p.sum(-1, keepdims=True), v)
        np.testing.assert_allclose(np.asarray(got[s]).reshape(want.shape),
                                   want, atol=2e-5)
        np.testing.assert_allclose(np.asarray(same[s]).reshape(want.shape),
                                   want, atol=2e-5)


def test_a_chunk_leaves_its_last_window_in_the_ring():
    """ring_gqa on a chunk whose real length is shorter than its padding
    and whose start is not a multiple of the window: each of the last
    `window` REAL positions lies on the row it names; another slot's
    rows are untouched."""
    rng = np.random.default_rng(6)
    G, H, d, s, start, n = 2, 4, 16, 24, 13, 19
    kr = jnp.asarray(rng.normal(size=(3, G, W, d)), jnp.float32)
    vr = jnp.asarray(rng.normal(size=(3, G, W, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, s, H, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, s, G, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, s, G, d)), jnp.float32)
    out, (kr2, vr2) = ring_gqa(
        q, k, v, (kr, vr, jnp.asarray([1]), jnp.asarray([n])),
        jnp.asarray([start]), 8, W)
    assert out.shape == (1, s, H, d) and np.isfinite(np.asarray(out)).all()
    for new, old, x in ((kr2, kr, k), (vr2, vr, v)):
        assert (np.asarray(new[0]) == np.asarray(old[0])).all()
        assert (np.asarray(new[2]) == np.asarray(old[2])).all()
        for p in range(start + n - W, start + n):
            np.testing.assert_array_equal(new[1, :, p % W], x[0, p - start])


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: the routed parts the 8 shares give, each
    with its picks' weights times routed_scaling_factor 2.5, with the
    shared expert counted once and unscaled, add up to what the reference
    gives for the whole layer."""
    cfg, net, model, weights = tiny
    assert cfg.routed_scaling_factor == 2.5
    whole = net.layers[1].mlp
    w = {k[len("layers.1."):]: a for k, a in weights.items()
         if k.startswith("layers.1.")}
    z = np.random.default_rng(5).normal(size=(19, cfg.hidden_size)) \
        .astype("float32")
    want = np.asarray(ref.moe_ffn(jnp.asarray(z), w, model))
    of = 8
    held = cfg.num_experts // of
    shared = np.asarray(unwrap(whole.shared_experts(paddle.to_tensor(z))))
    total = shared
    for index in range(of):
        part = MoELayer(cfg.hidden_size, cfg.moe_intermediate_size,
                        cfg.num_experts, gate=type(whole.gate)(
                            cfg.num_experts, cfg.num_experts_per_tok,
                            cfg.norm_topk_prob, cfg.routed_scaling_factor),
                        activation="swiglu", expert_share=(index, of))
        part.gate_weight._data = whole.gate_weight._data
        for name in ("w1", "w3", "w2"):
            getattr(part.experts, name)._data = getattr(
                whole.experts, name)._data[index * held:(index + 1) * held]
        total = total + unwrap(part(paddle.to_tensor(z)))
    np.testing.assert_allclose(total, want, atol=TOL)
    # the factor is on the routed part only
    routed = np.asarray(ref.moe_ffn(jnp.asarray(z), w, model, shared=False))
    unscaled = np.asarray(ref.moe_ffn(
        jnp.asarray(z), w, dict(model, routed_scaling_factor=1.0),
        shared=False))
    np.testing.assert_allclose(routed, 2.5 * unscaled, atol=TOL)
    np.testing.assert_allclose(want, routed + shared, atol=TOL)


def test_a_share_of_the_model_matches_the_reference_given_that_share():
    paddle.seed(11)
    cfg = KExaoneConfig.tiny(expert_share=(1, 2))
    net = KExaoneForCausalLM(cfg)
    net.eval()
    ids = _ids(30, seed=9)
    got = np.asarray(unwrap(net(paddle.to_tensor(ids[None]))))[0]
    want = np.asarray(ref.logits(ref.model_weights(net),
                                 dataclasses.asdict(cfg), ids, (1, 2)))
    assert ref.errors(got, want)["max"] < TOL


@pytest.mark.parametrize("option,kwargs", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("cache_dtype='int8'", dict(cache_dtype="int8")),
    ("draft_model", dict(draft_model="any")),
])
def test_engine_refuses_what_it_cannot_do_for_a_ring_spec(tiny, option,
                                                          kwargs):
    with pytest.raises(ValueError) as e:
        Engine(tiny[1], max_slots=2, page_size=8, prefill_bucket=8,
               max_context=32, **kwargs)
    assert option.split("=")[0] in str(e.value)
    assert "kinds kv, state" in str(e.value)


@pytest.mark.parametrize("entry", ["snapshot", "restore", "extract_request"])
def test_entries_that_move_a_request_refuse_a_ring_spec(tiny, entry):
    eng = Engine(tiny[1], max_slots=2, page_size=8, prefill_bucket=8,
                 max_context=32)
    try:
        call = {"snapshot": lambda: eng.snapshot(),
                "restore": lambda: eng.restore({}),
                "extract_request": lambda: eng.extract_request(0)}[entry]
        with pytest.raises(ValueError) as e:
            call()
        assert entry in str(e.value) and "ring" in str(e.value)
    finally:
        eng.close()


def test_serving_spec_gives_pages_to_one_kind_and_rings_to_the_other(tiny):
    cfg, net, _, _ = tiny
    spec = net.serving_spec()
    assert [layer["kind"] for layer in spec["cache_layers"]] == \
        ["state", "state", "state", "kv", "state"]
    assert spec["cache_layers"][3] == dict(kind="kv", kv_heads=2,
                                           head_dim=16)
    assert spec["cache_layers"][0] == dict(
        kind="state", window=W,
        arrays={"k": ([2, W, 16], None), "v": ([2, W, 16], None)})
    # a dtype of None is the cache's
    pools = _make_spec_pools(spec, 9, 8, jnp.bfloat16, False, slots=5)
    assert [tuple((p.shape, str(p.dtype)) for p in layer)
            for layer in pools] == [
        (((9, 2, 8, 16), "bfloat16"),) * 2 if i == 3 else
        (((5, 2, W, 16), "bfloat16"),) * 2 for i in range(5)]


def test_spans_carry_the_windows_arguments(tiny):
    """`engine.decode.dispatch` names the rows the sliding layers read
    (`win_tokens`, min(context + 1, window) a lane) beside the full
    layer's `ctx_tokens`, and the lanes whose rings the program writes
    (docs/OBSERVABILITY.md)."""
    from paddle_tpu.profiler import Profiler
    cfg, net, _, _ = tiny
    eng = _engine(net, max_prefill_tokens_per_step=16, max_context=64)
    try:
        with Profiler(timer_only=True) as prof:
            eng.add_request(_ids(5, seed=1),
                            SamplingParams(max_new_tokens=6))
            _drain(eng)
            eng.add_request(_ids(30, seed=1),
                            SamplingParams(max_new_tokens=3))
            _drain(eng)
        rows = list(prof._store.events)
    finally:
        eng.close()
    carries = [args["state_carry"] for name, _, _, args in rows
               if name == "engine.prefill"]
    assert carries == [0, 0, 1]
    ticks = [args for name, _, _, args in rows
             if name == "engine.decode.dispatch" and args]
    assert ticks and {t["state_slots"] for t in ticks} == {1}
    for t in ticks:
        assert t["win_tokens"] == min(t["ctx_tokens"] + 1, W)
    assert {t["win_tokens"] for t in ticks} >= {6, 7, W}


def test_parameters_are_created_in_the_configured_dtype():
    net = KExaoneForCausalLM(KExaoneConfig.tiny(dtype="bfloat16"))
    assert {str(unwrap(p).dtype) for _, p in net.named_parameters()} == \
        {"bfloat16"}
    eng = Engine(net, max_slots=2, page_size=8, prefill_bucket=8,
                 max_context=32)
    try:
        # the rings follow the cache, the cache the weights
        assert {str(a.dtype) for layer in eng._pools for a in layer} == \
            {"bfloat16"}
    finally:
        eng.close()
