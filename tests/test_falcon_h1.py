"""Falcon-H1 (text/models/falcon_h1.py): a Mamba-2 state-space mixer and
a GQA attention in parallel inside every block, against the plain
reference (benchmark/reference/falcon_h1.py) at a tiny size, float32
both sides; the reference against the published `transformers` code; the
recurrence's three forms (kernels/ssd.py) against each other; and what
the engine does with a block that keeps pages AND a state.

Tiny size: hidden 64, two blocks, 4 query / 2 KV heads of 16, 4 mixer
heads of 8 in 2 groups on a state of 16, 4 taps, the published
multipliers."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import falcon_h1 as ref
from paddle_tpu import monitor
from paddle_tpu.core.dispatch import unwrap
from paddle_tpu.inference.engine import (Engine, SamplingParams,
                                         _make_spec_pools)
from paddle_tpu.kernels import ssd
from paddle_tpu.text.models import FalconH1Config, FalconH1ForCausalLM

TOL = 1e-4


@pytest.fixture(autouse=True)
def _small_reference_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(11)
    cfg = FalconH1Config.tiny()
    net = FalconH1ForCausalLM(cfg)
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


def _forward(net, ids):
    return np.asarray(unwrap(net(paddle.to_tensor(ids[None]))))[0]


def _against_reference(tiny, out, prompt):
    _, _, model, weights = tiny
    seq = np.concatenate([prompt, out.token_ids[:-1]])
    want = np.asarray(ref.logits(weights, model, seq))[len(prompt) - 1:]
    return ref.errors(np.stack(out.logits), want)["max"]


@pytest.mark.parametrize("n", [24, 90])
def test_full_forward_matches_the_reference(tiny, n):
    cfg, net, model, weights = tiny
    ids = _ids(n)
    want = np.asarray(ref.logits(weights, model, ids))
    assert ref.errors(_forward(net, ids), want)["max"] < TOL


# the switches of the chip comparison (PERF.md section 6, PR 40); 40 is a
# chunk's first position, 60.. the ticks that follow a 60-token prompt
SWITCHES = dict(
    mixer=dict(mixer=False), attention=dict(attention=False),
    rope=dict(rope=False), ssm_multipliers_ones=dict(mup=False),
    state_lost_between_chunks=dict(lose_state_at=(40,)),
    tail_lost_between_programs=dict(lose_tail_at=(40, 60, 61, 62, 63)))
MULTIPLIERS = ["embedding_multiplier", "lm_head_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
               "ssm_multipliers.0", "ssm_multipliers.1", "ssm_multipliers.2",
               "ssm_multipliers.3", "ssm_multipliers.4",
               "mlp_multipliers.0", "mlp_multipliers.1"]


@pytest.mark.parametrize("case", list(SWITCHES) + MULTIPLIERS)
def test_every_switch_and_multiplier_changes_the_output(tiny, case):
    """A switch of the reference moves its logits away from the model's;
    a multiplier doubled moves the MODEL's logits (same weights), and the
    reference given the same multiplier follows it."""
    cfg, net, model, weights = tiny
    ids = _ids(64, seed=3)
    base = _forward(net, ids)
    if case in SWITCHES:
        other = np.asarray(ref.logits(weights, model, ids, **SWITCHES[case]))
        assert ref.errors(base[40:], other[40:])["rms"] > 0.02
        return
    name, _, i = case.partition(".")
    value = getattr(cfg, name)
    doubled = 2.0 * value if not i else tuple(
        v * (2.0 if j == int(i) else 1.0) for j, v in enumerate(value))
    cfg2 = dataclasses.replace(cfg, **{name: doubled})
    net2 = FalconH1ForCausalLM(cfg2)
    net2.set_state_dict(net.state_dict())
    net2.eval()
    got = _forward(net2, ids)
    assert ref.errors(got, base)["rms"] > 0.02
    want = np.asarray(ref.logits(weights, dataclasses.asdict(cfg2), ids))
    assert ref.errors(got, want)["max"] < TOL


def test_the_reference_matches_the_published_implementation(tiny):
    """`transformers`' FalconH1ForCausalLM (its pure-torch path, CPU,
    float32) built from the same tiny configuration with the same
    weights copied in: the one test that ties the repo's reference to
    the published code."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    cfg, net, model, weights = tiny
    published = {f.name: getattr(cfg, f.name)
                 for f in dataclasses.fields(cfg)
                 if f.name not in ("dtype", "prefill_query_block",
                                   "attention_bias", "mlp_bias")}
    hf_cfg = transformers.FalconH1Config(
        **{k: list(v) if isinstance(v, tuple) else v
           for k, v in published.items()}, attn_implementation="eager")
    hf = transformers.FalconH1ForCausalLM(hf_cfg).eval()

    def t(name):
        return torch.tensor(np.asarray(weights[name], np.float32))

    theirs = {"model.embed_tokens.weight": t("embed_tokens.weight"),
              "model.final_layernorm.weight": t("final_layernorm.weight"),
              "lm_head.weight": t("lm_head.weight").T}
    for i in range(cfg.num_hidden_layers):
        ours, hf_l = f"layers.{i}.", f"model.layers.{i}."
        for lin in ("self_attn.q_proj", "self_attn.k_proj",
                    "self_attn.v_proj", "self_attn.o_proj", "mamba.in_proj",
                    "mamba.out_proj", "feed_forward.gate_proj",
                    "feed_forward.up_proj", "feed_forward.down_proj"):
            theirs[f"{hf_l}{lin}.weight"] = t(f"{ours}{lin}.weight").T
        for same in ("input_layernorm.weight", "pre_ff_layernorm.weight",
                     "mamba.A_log", "mamba.dt_bias", "mamba.D"):
            theirs[hf_l + same] = t(ours + same)
        theirs[hf_l + "mamba.norm.weight"] = t(ours + "mamba.norm_weight")
        theirs[hf_l + "mamba.conv1d.bias"] = t(ours + "mamba.conv_bias")
        # [taps, channels] -> Conv1d's [channels, 1, taps]
        theirs[hf_l + "mamba.conv1d.weight"] = \
            t(ours + "mamba.conv_weight").T[:, None, :]
    missing, unexpected = hf.load_state_dict(theirs, strict=False)
    assert not unexpected and not [m for m in missing if "rotary" not in m]
    ids = _ids(45, seed=5)                # no multiple of the chunk of 8
    with torch.no_grad():
        got = hf(torch.tensor(ids[None]), use_cache=False).logits[0].numpy()
    want = np.asarray(ref.logits(weights, model, ids))
    assert ref.errors(want, got)["max"] < TOL


@pytest.mark.parametrize("chunk", [None, 88],
                         ids=["monolithic", "chunked-88"])
def test_engine_prefill_and_decode_match_the_reference(tiny, chunk):
    """Prefill, then decode, through the paged cache and the slot's
    state of every block: the logits rows the engine sampled from
    against the reference's full forward pass over the same tokens. The
    chunked prompt runs as 88 + 62 tokens, `S` and the convolution's
    tail carried through the slot's rows, with a second sequence
    decoding between the two chunks."""
    cfg, net, _, _ = tiny
    prompt, n_new = _ids(150, seed=1), 9
    eng = _engine(net, max_prefill_tokens_per_step=chunk)
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


def test_a_preempted_request_has_both_halves_of_a_block_rebuilt(tiny):
    """A pool too small for both sequences preempts the youngest: its
    pages are freed and its state forgotten; the resume prefill rebuilds
    the pages AND the state of every block from the kept tokens, and the
    logits still match."""
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


def test_a_decode_tick_leaves_other_slots_state_bit_identical(tiny):
    """The decode program on state arrays full of numbers: slot 0
    decodes, slot 1 is live with its budget spent (dead in-graph), slot
    2 is free. Only slot 0's rows change, on every block."""
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
        eng._live[:] = [1, 1, 0]
        eng._bud[:] = [4, 0, 0]
        state = tuple(eng._up(m) for m in eng._mirrors())
        _, ok, _, new, *_ = eng._get_decode_fn("greedy")(
            eng._st, pools, eng._up(eng._bt), state, eng._poison_zeros)
        assert np.asarray(ok).all()
        changed = 0
        for kind, old, got in zip(eng._cache_kinds, before, new):
            if kind != "state":
                continue
            for a, b in zip(old, got):
                b = np.asarray(b)
                assert (a[1:] == b[1:]).all()          # bit for bit
                assert not (a[0] == b[0]).all()
            changed += 1
        assert changed == cfg.num_hidden_layers
    finally:
        eng.close()


def _scan_inputs(b, T, H, G, P, N, seed, per_token):
    """A sequence whose last head decays by e^-`per_token` a token."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, T, H, P)).astype(np.float32)
    dt = (0.05 + np.abs(rng.normal(size=(b, T, H)))).astype(np.float32)
    A = -np.linspace(0.3, 1.0, H).astype(np.float32)
    a = dt * A
    a[..., -1] = -per_token
    B, C = (rng.normal(size=(b, T, G, N)).astype(np.float32)
            for _ in range(2))
    S0 = rng.normal(size=(b, H, P, N)).astype(np.float32)
    return x, dt, a, B, C, S0


def _token_by_token(x, dt, a, B, C, S0, alive=None):
    S, ys = jnp.asarray(S0), []
    alive = jnp.ones((x.shape[0],), bool) if alive is None else alive
    for t in range(x.shape[1]):
        y, S = ssd.ssd_step_arrays(S, x[:, t], dt[:, t], a[:, t], B[:, t],
                                   C[:, t], alive)
        ys.append(y)
    return jnp.stack(ys, 1), S


@pytest.mark.parametrize("per_token", [0.7, 6.0],
                         ids=["e-90-a-chunk", "e-768-a-chunk"])
def test_chunked_scan_equals_token_by_token_at_strong_decay(per_token):
    """A head that decays by far more than float32's e^88 over a whole
    chunk of 128: the chunked form works from clamped differences of the
    running log-decay and neither overflows nor loses the other heads."""
    args = _scan_inputs(2, 300, 4, 2, 8, 16, seed=0, per_token=per_token)
    want_y, want_S = _token_by_token(*args)
    y, S = ssd.ssd_chunked(*args, chunk=128)
    assert np.isfinite(np.asarray(y)).all()
    assert float(jnp.abs(y - want_y).max()) < 1e-3 * float(
        jnp.abs(want_y).max())
    assert float(jnp.abs(S - want_S).max()) < 1e-3 * float(
        jnp.abs(want_S).max())


def test_chunked_scan_ignores_padded_tokens():
    x, dt, a, B, C, S0 = _scan_inputs(1, 40, 4, 2, 8, 16, seed=1,
                                      per_token=0.5)
    dt[:, 29:], a[:, 29:] = 0.0, 0.0
    y, S = ssd.ssd_chunked(x, dt, a, B, C, S0, chunk=16)
    y29, S29 = ssd.ssd_chunked(x[:, :29], dt[:, :29], a[:, :29], B[:, :29],
                               C[:, :29], S0, chunk=16)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S29), atol=1e-5)
    np.testing.assert_allclose(np.asarray(y[:, :29]), np.asarray(y29),
                               atol=1e-5)


def test_the_one_token_step_is_the_recurrence_as_written():
    """`ssd_step_arrays` at the published head geometry against the
    recurrence written out a head at a time in numpy (head h reads its
    GROUP's B and C), with a lane that is not decoding: its rows come
    back bit-identical and its y is zero."""
    x, dt, a, B, C, S0 = _scan_inputs(3, 1, 32, 2, 128, 256, seed=2,
                                      per_token=6.0)
    alive = np.asarray([True, False, True])
    step = [t[:, 0] for t in (x, dt, a, B, C)]
    y, S = ssd.ssd_step_arrays(jnp.asarray(S0), *step, jnp.asarray(alive))
    x1, dt1, a1, B1, C1 = step
    for i in np.flatnonzero(alive):
        for h in (0, 15, 16, 31):
            g = h // 16
            want = np.exp(a1[i, h]) * S0[i, h] \
                + np.outer(dt1[i, h] * x1[i, h], B1[i, g])
            np.testing.assert_allclose(np.asarray(S[i, h]), want,
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.asarray(y[i, h]), want @ C1[i, g],
                                       rtol=1e-4, atol=1e-4)
    assert (np.asarray(S[1]) == S0[1]).all()
    assert not np.asarray(y[1]).any()


def test_serving_spec_gives_two_entries_a_block(tiny):
    cfg, net, _, _ = tiny
    spec = net.serving_spec()
    assert [e["kind"] for e in spec["cache_layers"]] == \
        ["kv", "state"] * cfg.num_hidden_layers
    assert spec["cache_layers"][0] == dict(kind="kv", kv_heads=2,
                                           head_dim=16)
    assert spec["cache_layers"][1]["arrays"] == {
        "S": ([4, 8, 16], "float32"), "conv0": ([96], "float32"),
        "conv1": ([96], "float32"), "conv2": ([96], "float32")}
    pools = _make_spec_pools(spec, 9, 8, jnp.float32, False, slots=5)
    assert [tuple(p.shape for p in layer) for layer in pools] == [
        ((9, 2, 8, 16), (9, 2, 8, 16)),
        ((5, 4, 8, 16),) + ((5, 96),) * 3] * cfg.num_hidden_layers
    eng = Engine(net, max_slots=5, page_size=8, prefill_bucket=8,
                 max_context=32)
    try:
        assert eng._has_state and len(eng._cache_kinds) == \
            2 * spec["num_layers"]
        assert monitor.snapshot()["serving.state.bytes"] == \
            cfg.num_hidden_layers * 5 * 4 * (4 * 8 * 16 + 3 * 96)
    finally:
        eng.close()


def test_spans_and_counters_of_the_state(tiny):
    """`engine.decode.dispatch` names the lanes whose state the program
    updates, `engine.prefill` whether the chunk started from the slot's
    rows; the trace-time counter says the chunked scan was traced
    (docs/OBSERVABILITY.md)."""
    from paddle_tpu.profiler import Profiler
    cfg, net, _, _ = tiny
    chunked = monitor.counter("kernels.prefill.ssd_chunked")
    n0 = chunked.get()
    eng = _engine(net, max_prefill_tokens_per_step=16, max_context=64)
    try:
        with Profiler(timer_only=True) as prof:
            eng.add_request(_ids(30, seed=1),
                            SamplingParams(max_new_tokens=3))
            _drain(eng)
        rows = list(prof._store.events)
    finally:
        eng.close()
    assert [args["state_carry"] for name, _, _, args in rows
            if name == "engine.prefill"] == [0, 1]
    slots = [args["state_slots"] for name, _, _, args in rows
             if name == "engine.decode.dispatch" and args]
    assert slots and set(slots) == {1}
    assert chunked.get() - n0 >= cfg.num_hidden_layers


def test_parameters_are_created_in_the_configured_dtype():
    net = FalconH1ForCausalLM(FalconH1Config.tiny(dtype="bfloat16"))
    assert {str(unwrap(p).dtype) for _, p in net.named_parameters()} == \
        {"bfloat16"}
    mixer = net.layers[1].mamba
    a_log = np.asarray(unwrap(mixer.A_log).astype(jnp.float32))
    assert (np.abs(a_log) <= np.log(16.1)).all() and (a_log < 0).any()
    dt = np.asarray(jax.nn.softplus(unwrap(mixer.dt_bias)
                                    .astype(jnp.float32)))
    assert (dt > 5e-4).all() and (dt < 0.11).all()
    assert net.serving_spec()["cache_layers"][1]["arrays"]["conv0"][1] == \
        "bfloat16"
    # the muP draw: a matrix is wider by the multipliers its output meets
    k = np.asarray(unwrap(net.layers[0].self_attn.k_proj.weight)
                   .astype(jnp.float32))
    v = np.asarray(unwrap(net.layers[0].self_attn.v_proj.weight)
                   .astype(jnp.float32))
    assert k.std() / v.std() == pytest.approx(
        1.6 / FalconH1Config().key_multiplier, rel=0.1)
