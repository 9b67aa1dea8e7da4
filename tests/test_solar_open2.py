"""Solar-Open2 (text/models/solar_open2.py): linear-attention (KDA)
layers on a per-slot state beside a paged GQA layer, against the plain
reference (benchmark/reference/solar_open2.py), at a tiny size, float32
both sides; the recurrence's three forms (kernels/kda.py) against each
other; and what the engine does with a slot's state.

Tiny size: hidden 64, a GQA layer (4 heads, 2 KV heads of 16) and two
KDA layers (4 heads of 16, 4 taps); 8 experts top-2 with one shared."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import solar_open2 as ref
from paddle_tpu import monitor
from paddle_tpu.core.dispatch import unwrap
from paddle_tpu.incubate.distributed.models.moe import MoELayer
from paddle_tpu.inference.engine import (Engine, SamplingParams,
                                         _make_spec_pools)
from paddle_tpu.kernels import kda
from paddle_tpu.text.models import SolarOpen2Config, SolarOpen2ForCausalLM

TOL = 1e-4


@pytest.fixture(autouse=True)
def _small_reference_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(7)
    cfg = SolarOpen2Config.tiny()
    net = SolarOpen2ForCausalLM(cfg)
    net.eval()
    return cfg, net, dataclasses.asdict(cfg), ref.model_weights(net)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 96, n)


def _engine(net, **kw):
    args = dict(max_slots=4, page_size=8, prefill_bucket=8,
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


@pytest.mark.parametrize("n", [24, 90])
def test_full_forward_matches_the_reference(tiny, n):
    cfg, net, model, weights = tiny
    ids = _ids(n)
    got = np.asarray(unwrap(net(paddle.to_tensor(ids[None]))))[0]
    want = np.asarray(ref.logits(weights, model, ids))
    assert ref.errors(got, want)["max"] < TOL
    # and the comparison can tell: the decay and the delta rule matter
    for off in (dict(decay=False), dict(delta=False)):
        other = np.asarray(ref.logits(weights, model, ids, **off))
        assert ref.errors(got, other)["rms"] > 0.05


@pytest.mark.parametrize("chunk", [None, 88],
                         ids=["monolithic", "chunked-88"])
def test_engine_prefill_and_decode_match_the_reference(tiny, chunk):
    """Prefill, then decode, through the slot's state and the paged
    cache: the logits rows the engine sampled from against the
    reference's full forward pass over the same tokens. The chunked
    prompt runs as 88 + 62 tokens (88 is no multiple of the recurrence's
    64-token chunk), its state carried through the slot's rows, with a
    second sequence decoding between the two chunks."""
    cfg, net, _, _ = tiny
    prompt, n_new = _ids(150, seed=1), 9
    eng = _engine(net, max_prefill_tokens_per_step=chunk)
    between = []
    run = eng._run_prefills

    def spy():
        # decode lanes the tick in flight covers, while the long prompt
        # sits between two of its chunks
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


def test_a_preempted_request_has_its_state_rebuilt(tiny):
    """A pool too small for both sequences preempts the youngest: its
    pages are freed and its state forgotten; the resume prefill rebuilds
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


def test_a_reused_slot_starts_from_zero(tiny):
    cfg, net, _, _ = tiny
    resets = monitor.counter("serving.state.resets")
    n0 = resets.get()
    eng = _engine(net, max_slots=1, max_context=64)
    try:
        for seed in (5, 6):
            prompt = _ids(30, seed=seed)
            eng.add_request(prompt, SamplingParams(max_new_tokens=5,
                                                   return_logits=True))
            out, = _drain(eng)
            assert _against_reference(tiny, out, prompt) < TOL
        # the slot's rows were left as the first request had them
        assert float(jnp.abs(eng._pools[1][0]).max()) > 0
    finally:
        eng.close()
    assert resets.get() == n0 + 2


@pytest.mark.parametrize("lane", ["between-chunks", "dead-under-run-ahead",
                                  "free"])
def test_a_decode_tick_leaves_other_slots_state_bit_identical(tiny, lane):
    """The decode program on state arrays full of numbers: slot 0
    decodes; slot 1 is not live (a slot between two prefill chunks, or a
    free one) or is live with its budget spent (dead in-graph, the tick
    dispatched ahead of the host learning of its last token). Only slot
    0's rows change."""
    cfg, net, _, _ = tiny
    eng = _engine(net, max_slots=3, max_context=64)
    try:
        rng = np.random.default_rng(8)
        pools = [tuple(jnp.asarray(rng.normal(size=a.shape), a.dtype)
                       for a in layer) if kind == "state" else layer
                 for kind, layer in zip(eng._cache_kinds, eng._pools)]
        before = [[np.asarray(a) for a in layer] for layer in pools]
        eng._bt[:, 0] = [1, 2, 3]
        eng._pos[:] = [3, 5, 0]
        eng._live[:] = [1, int(lane == "dead-under-run-ahead"), 0]
        eng._bud[:] = [4, 0, 0]
        state = tuple(eng._up(m) for m in eng._mirrors())
        _, ok, state2, new, *_ = eng._get_decode_fn("greedy")(
            eng._st, pools, eng._up(eng._bt), state, eng._poison_zeros)
        assert np.asarray(ok).all()
        assert np.asarray(state2[1]).tolist() == [4, 5, 0]
        for kind, old, got in zip(eng._cache_kinds, before, new):
            if kind != "state":
                continue
            for a, b in zip(old, got):
                b = np.asarray(b)
                assert (a[1:] == b[1:]).all()          # bit for bit
                assert not (a[0] == b[0]).all()
    finally:
        eng.close()


@pytest.mark.parametrize("per_token", [1.6, 6.0],
                         ids=["A16-dt0.1", "three-sigma"])
def test_chunked_recurrence_equals_token_by_token_at_strong_decay(per_token):
    """The strongest decay the initialiser draws, exp(A_log) = 16 at
    softplus = 0.1, is 1.6 a token on every channel: 102 over a 64-token
    chunk, past float32's e^88 in the factorised form; a 3-sigma token
    of the low-rank pair reaches 6. Some channels do not decay at all."""
    rng = np.random.default_rng(0)
    b, T, H, d = 2, 150, 3, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, T, H, d)), jnp.float32)
               for _ in range(3))
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    a = jnp.full((b, T, H, d), -per_token, jnp.float32).at[..., ::4].set(0.0)
    beta = jnp.asarray(rng.uniform(0, 2, size=(b, T, H)), jnp.float32)
    S0 = jnp.asarray(rng.normal(size=(b, H, d, d)), jnp.float32)
    got_o, got_S = jax.jit(kda.kda_chunked)(q, k, v, a, beta, S0)

    def step(S, t):
        o, S = kda.kda_step_arrays(S, *t, jnp.ones((b,), bool))
        return S, o

    want_S, want_o = jax.lax.scan(
        step, S0, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, a, beta)))
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, jnp.moveaxis(want_o, 0, 1), atol=2e-5)
    np.testing.assert_allclose(got_S, want_S, atol=2e-5)


def test_chunked_recurrence_ignores_padded_tokens():
    rng = np.random.default_rng(1)
    b, T, H, d = 1, 40, 2, 16
    q, k, v, a = (jnp.asarray(rng.normal(size=(b, T, H, d)), jnp.float32)
                  for _ in range(4))
    beta = jnp.ones((b, T, H), jnp.float32)
    S0 = jnp.zeros((b, H, d, d), jnp.float32)
    real = (jnp.arange(T) < 23)[None, :, None]
    _, S_pad = kda.kda_chunked(q, k, v, jnp.where(real[..., None], -a * a, 0),
                               jnp.where(real, beta, 0), S0)
    _, S_cut = kda.kda_chunked(q[:, :23], k[:, :23], v[:, :23],
                               -(a * a)[:, :23], beta[:, :23], S0)
    np.testing.assert_allclose(S_pad, S_cut, atol=1e-6)


def test_kda_decode_kernel_equals_the_xla_step():
    rng = np.random.default_rng(2)
    b, H, d = 3, 16, 128
    S = jnp.asarray(rng.normal(size=(b, H, d, d)), jnp.float32)
    q, k, v = (jnp.asarray(rng.normal(size=(b, H, d)), jnp.float32)
               for _ in range(3))
    a = -jnp.asarray(rng.uniform(0, 3, size=(b, H, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, size=(b, H)), jnp.float32)
    alive = jnp.asarray([True, False, True])         # slot 1 is dead
    want_o, want_S = kda.kda_step_arrays(S, q, k, v, a, beta, alive)
    for hb in (8, 16):
        got_o, got_S = kda.kda_decode(S, q, k, v, a, beta, alive,
                                      heads_per_block=hb, interpret=True)
        np.testing.assert_allclose(got_o, want_o, atol=1e-4)
        np.testing.assert_allclose(got_S, want_S, atol=1e-5)
        assert (np.asarray(got_S[1]) == np.asarray(S[1])).all()
        assert not np.asarray(got_o[1]).any()
    assert kda.kda_decode_requirements(64, 128, 128) is None
    assert "128" in kda.kda_decode_requirements(4, 16, 16)


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: the routed parts the 4 shares give, with
    the shared expert counted once, add up to what the reference gives
    for the whole layer."""
    cfg, net, model, weights = tiny
    whole = net.layers[1].mlp
    w = {k[len("layers.1."):]: a for k, a in weights.items()
         if k.startswith("layers.1.")}
    z = np.random.default_rng(5).normal(size=(19, cfg.hidden_size)) \
        .astype("float32")
    want = np.asarray(ref.moe_ffn(jnp.asarray(z), w, model))
    of = 4
    held = cfg.n_routed_experts // of
    total = unwrap(whole.shared_experts(paddle.to_tensor(z)))
    for index in range(of):
        part = MoELayer(cfg.hidden_size, cfg.moe_intermediate_size,
                        cfg.n_routed_experts, gate="sigmoid_topk",
                        top_k=cfg.num_experts_per_tok, activation="swiglu",
                        expert_share=(index, of))
        part.gate_weight._data = whole.gate_weight._data
        for name in ("w1", "w3", "w2"):
            getattr(part.experts, name)._data = getattr(
                whole.experts, name)._data[index * held:(index + 1) * held]
        total = total + unwrap(part(paddle.to_tensor(z)))
    np.testing.assert_allclose(total, want, atol=TOL)


def test_a_share_of_the_model_matches_the_reference_given_that_share():
    paddle.seed(11)
    cfg = SolarOpen2Config.tiny(expert_share=(1, 2))
    net = SolarOpen2ForCausalLM(cfg)
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
def test_engine_refuses_what_it_cannot_do_for_a_state_spec(tiny, option,
                                                           kwargs):
    with pytest.raises(ValueError) as e:
        Engine(tiny[1], max_slots=2, page_size=8, prefill_bucket=8,
               max_context=32, **kwargs)
    assert option.split("=")[0] in str(e.value)
    assert "kinds kv, state" in str(e.value)


def test_engine_refuses_an_mp_mesh_for_a_state_spec(tiny):
    from paddle_tpu.distributed import mesh as mesh_mod
    mesh = mesh_mod.build_mesh({"mp": 2}, devices=jax.devices()[:2])
    with mesh_mod.use_mesh(mesh):
        with pytest.raises(ValueError) as e:
            Engine(tiny[1], max_slots=2, page_size=8, prefill_bucket=8,
                   max_context=32)
    assert "mp=2" in str(e.value) and "kinds kv, state" in str(e.value)


@pytest.mark.parametrize("entry", ["snapshot", "restore", "extract_request"])
def test_entries_that_move_a_request_refuse_a_state_spec(tiny, entry):
    eng = Engine(tiny[1], max_slots=2, page_size=8, prefill_bucket=8,
                 max_context=32)
    try:
        call = {"snapshot": lambda: eng.snapshot(),
                "restore": lambda: eng.restore({}),
                "extract_request": lambda: eng.extract_request(0)}[entry]
        with pytest.raises(ValueError) as e:
            call()
        assert entry in str(e.value) and "state" in str(e.value)
    finally:
        eng.close()


def test_serving_spec_gives_pages_to_one_kind_and_slots_to_the_other(tiny):
    cfg, net, _, _ = tiny
    spec = net.serving_spec()
    assert [layer["kind"] for layer in spec["cache_layers"]] == \
        ["kv", "state", "state"]
    assert spec["cache_layers"][0] == dict(kind="kv", kv_heads=2,
                                           head_dim=16)
    assert spec["cache_layers"][1]["arrays"] == {
        "S": ([4, 16, 16], "float32"), "conv0": ([192], "float32"),
        "conv1": ([192], "float32"), "conv2": ([192], "float32")}
    pools = _make_spec_pools(spec, 9, 8, jnp.float32, False, slots=5)
    assert [tuple(p.shape for p in layer) for layer in pools] == [
        ((9, 2, 8, 16), (9, 2, 8, 16))] \
        + [((5, 4, 16, 16),) + ((5, 192),) * 3] * 2
    with pytest.raises(ValueError, match="kinds known"):
        _make_spec_pools(dict(cache_layers=[dict(kind="ring")]), 9, 8,
                         jnp.float32, False)
    eng = Engine(net, max_slots=5, page_size=8, prefill_bucket=8,
                 max_context=32)
    try:
        assert eng._has_state and eng._cache_kinds == ["kv", "state", "state"]
        assert monitor.snapshot()["serving.state.bytes"] == \
            2 * 5 * 4 * (4 * 16 * 16 + 3 * 192)
    finally:
        eng.close()


def test_spans_carry_the_states_arguments(tiny):
    """`engine.decode.dispatch` names the lanes whose state the program
    updates, `engine.prefill` whether the chunk started from the slot's
    rows (docs/OBSERVABILITY.md)."""
    from paddle_tpu.profiler import Profiler
    cfg, net, _, _ = tiny
    eng = _engine(net, max_prefill_tokens_per_step=16, max_context=64)
    try:
        with Profiler(timer_only=True) as prof:
            eng.add_request(_ids(30, seed=1),
                            SamplingParams(max_new_tokens=3))
            _drain(eng)
        rows = list(prof._store.events)
    finally:
        eng.close()
    carries = [args["state_carry"] for name, _, _, args in rows
               if name == "engine.prefill"]
    assert carries == [0, 1]
    slots = [args["state_slots"] for name, _, _, args in rows
             if name == "engine.decode.dispatch" and args]
    assert slots and set(slots) == {1}


def test_parameters_are_created_in_the_configured_dtype():
    net = SolarOpen2ForCausalLM(SolarOpen2Config.tiny(dtype="bfloat16"))
    assert {str(unwrap(p).dtype) for _, p in net.named_parameters()} == \
        {"bfloat16"}
    attn = net.layers[1].self_attn
    a_log = np.asarray(unwrap(attn.A_log).astype(jnp.float32))
    assert (a_log >= 0).all() and (a_log <= np.log(16.1)).all()
    dt = np.asarray(jax.nn.softplus(unwrap(attn.dt_bias)
                                    .astype(jnp.float32)))
    assert (dt > 5e-4).all() and (dt < 0.11).all()
    assert net.serving_spec()["cache_layers"][1]["arrays"]["conv0"][1] == \
        "bfloat16"
