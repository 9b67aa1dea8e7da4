"""DeepSeek-V2 (text/models/deepseek_v2.py): latent attention on every
layer with no indexer, no gate and no window, YaRN positions, softmax
experts routed within the best groups; against the plain reference
(benchmark/reference/deepseek_v2.py) at a tiny size, float32 both sides.

Tiny size: hidden 64, a leading dense layer and two expert layers; 16
experts in 8 groups of 2, top-4 inside the best 3 groups, two shared;
YaRN over an original context of 16 at factor 4, against contexts of
24-40."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import deepseek_v2 as ref
from paddle_tpu import monitor
from paddle_tpu.core.dispatch import unwrap
from paddle_tpu.incubate.distributed.models.moe import (GroupLimitedGate,
                                                        MoELayer)
from paddle_tpu.incubate.distributed.models.moe.gate import \
    group_limited_softmax_routing
from paddle_tpu.inference.engine import Engine, SamplingParams
from paddle_tpu.text.models import DeepseekV2Config, DeepseekV2ForCausalLM
from paddle_tpu.text.models import mla

TOL = 1e-4


@pytest.fixture(autouse=True)
def _small_reference_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(11)
    cfg = DeepseekV2Config.tiny()
    net = DeepseekV2ForCausalLM(cfg)
    net.eval()
    return cfg, net, dataclasses.asdict(cfg), ref.model_weights(net)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 96, n)


@pytest.mark.parametrize("n", [24, 40])
def test_full_forward_matches_the_reference(tiny, n):
    cfg, net, model, weights = tiny
    ids = _ids(n)
    got = np.asarray(unwrap(net(paddle.to_tensor(ids[None]))))[0]
    want = np.asarray(ref.logits(weights, model, ids))
    assert ref.errors(got, want)["max"] < TOL
    # and the comparison can tell: the group limit and YaRN matter
    for switch in (dict(group_limit=False), dict(yarn=False)):
        off = np.asarray(ref.logits(weights, model, ids, **switch))
        assert ref.errors(got, off)["rms"] > 1e-3, switch


@pytest.mark.parametrize("chunk", [None, 16], ids=["monolithic", "chunked"])
def test_engine_prefill_and_decode_match_the_reference(tiny, chunk):
    """Prefill, then decode, through the paged latent cache: the logits
    rows the engine sampled from against the reference's full forward
    pass over the same tokens."""
    cfg, net, model, weights = tiny
    prompt, n_new = _ids(27, seed=1), 9
    eng = Engine(net, max_slots=4, page_size=8, prefill_bucket=8,
                 max_context=48, keep_logits=True,
                 max_prefill_tokens_per_step=chunk)
    try:
        eng.add_request(prompt, SamplingParams(max_new_tokens=n_new,
                                               return_logits=True))
        eng.add_request(_ids(13, seed=2), SamplingParams(max_new_tokens=4))
        outs = []
        while not eng.idle:
            outs.extend(eng.step())
        assert eng.leaked_pages() == 0
    finally:
        eng.close()
    out = next(o for o in outs if o.logits is not None)
    assert out.ok and len(out.token_ids) == n_new
    seq = np.concatenate([prompt, out.token_ids[:-1]])
    want = np.asarray(ref.logits(weights, model, seq,
                                 rows_from=len(prompt) - 1))
    assert ref.errors(np.stack(out.logits), want)["max"] < TOL
    assert next(o for o in outs if o.logits is None).ok


def _numpy_group_limited(p, top_k, n_group, topk_group, scaling):
    """DeepSeek-V2's `group_limited_greedy` by hand, one token at a time:
    stable sorts, so ties go to the lower group and the lower expert."""
    n, e = p.shape
    size = e // n_group
    idx, w = [], []
    for row in p:
        best = row.reshape(n_group, size).max(-1)
        groups = np.argsort(-best, kind="stable")[:topk_group]
        allowed = np.zeros(e, bool)
        for g in groups:
            allowed[g * size:(g + 1) * size] = True
        picks = np.argsort(-np.where(allowed, row, 0.0), kind="stable")
        idx.append(picks[:top_k])
        w.append(row[picks[:top_k]] * scaling)
    return np.asarray(idx), np.asarray(w)


def test_router_is_group_limited_greedy():
    """Against the hand-written greedy on logits where the plain top-6
    and the group-limited top-6 differ, and on logits where two groups'
    maxima tie (the lower group stays)."""
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(64, 160)).astype(np.float32) * 1.5
    # token 0: two tied group maxima at the cut (groups 2 and 5)
    logits[0] = -4.0
    logits[0, [0, 20, 40, 100, 41, 101]] = [3.0, 2.9, 2.0, 2.0, 1.9, 1.9]
    p = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    idx, w = group_limited_softmax_routing(jnp.asarray(logits), 6, 8, 3,
                                           False, 16.0)
    want_idx, want_w = _numpy_group_limited(p, 6, 8, 3, 16.0)
    np.testing.assert_array_equal(np.sort(idx, 1), np.sort(want_idx, 1))
    np.testing.assert_allclose(np.sort(w, 1), np.sort(want_w, 1), rtol=1e-5)
    assert set(np.asarray(idx[0]) // 20) == {0, 1, 2}
    plain = np.argsort(-p, 1, kind="stable")[:, :6]
    differ = [i for i in range(64)
              if set(plain[i]) != set(np.asarray(idx[i]))]
    assert len(differ) > 32
    # at most topk_group groups a token, and the reference agrees
    assert (np.asarray([len(set(r // 20)) for r in np.asarray(idx)])
            <= 3).all()
    dense = np.asarray(ref.route(
        jnp.asarray(logits), jnp.eye(160, dtype=jnp.float32), top_k=6,
        n_group=8, topk_group=3, norm=False, scaling=16.0, round_to=None))
    got = np.zeros((64, 160), np.float32)
    np.put_along_axis(got, np.asarray(idx), np.asarray(w), 1)
    np.testing.assert_allclose(dense, got, rtol=1e-5, atol=1e-7)


def test_yarn_matches_the_published_numbers():
    """DeepSeek-V2's rope_scaling: the ramp runs from dim 10 to 23 of
    32, inv_freq is freq / 40 past it and freq before it, cos and sin
    keep their scale, softmax_scale = 1.58963 / sqrt(192) = 0.114721; the
    reference computes the same."""
    cfg = DeepseekV2Config()
    inv, factor = mla.yarn(cfg.rope_scaling, 64, 10000.0)
    freq = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, freq / 40 * ramp + freq * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[:11], freq[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], freq[23:] / 40, rtol=1e-6)
    assert 0 < inv[11] / freq[11] < 1 and inv[22] / freq[22] > 1 / 40
    assert factor == pytest.approx(1.26080 ** 2, rel=1e-5)
    net_scale = factor / np.sqrt(192)
    assert net_scale == pytest.approx(0.114721, rel=1e-5)
    r_inv, cs, r_scale = ref.rotary(dataclasses.asdict(cfg))
    np.testing.assert_allclose(r_inv, inv, rtol=1e-6)
    assert cs == 1.0 and r_scale == pytest.approx(net_scale, rel=1e-6)
    with pytest.raises(ValueError, match="mscale"):
        mla.yarn(dict(cfg.rope_scaling, mscale=1.0), 64, 10000.0)


def test_interleaved_rope_equals_the_permuted_form():
    """The model's rope in the published interleaved pairing equals the
    reference's (pairs moved to halves, then rotate-half), and equals
    plain rotate-half applied to inputs whose columns were permuted once
    (the form a model with W_qb's and W_kva's rope columns permuted
    would compute): dot products of q and k agree either way."""
    rng = np.random.default_rng(3)
    inv, _ = mla.yarn(DeepseekV2Config().rope_scaling, 64, 10000.0)
    pos = jnp.arange(40)[None]
    q = jnp.asarray(rng.normal(size=(1, 40, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 40, 64)), jnp.float32)
    got_q = mla.rope(q, pos, inv_freq=inv, interleaved=True)
    got_k = mla.rope(k, pos, inv_freq=inv, interleaved=True)
    np.testing.assert_allclose(got_q[0], ref.apply_rope(q[0], pos[0], inv,
                                                        1.0), atol=1e-5)
    perm = np.concatenate([np.arange(0, 64, 2), np.arange(1, 64, 2)])
    np.testing.assert_allclose(
        got_q, mla.rope(q[..., perm], pos, inv_freq=inv), atol=1e-5)
    np.testing.assert_allclose(
        jnp.einsum("bthd,bsd->bhts", got_q, got_k),
        jnp.einsum("bthd,bsd->bhts",
                   mla.rope(q[..., perm], pos, inv_freq=inv),
                   mla.rope(k[..., perm], pos, inv_freq=inv)), atol=1e-4)


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The expert-share check: the routed parts the 8 shares give (one
    group each), with the shared expert counted once, add up to what the
    reference gives for the whole layer."""
    cfg, net, model, weights = tiny
    whole = net.layers[1].mlp
    w = {k[len("layers.1."):]: a for k, a in weights.items()
         if k.startswith("layers.1.")}
    z = np.random.default_rng(5).normal(size=(19, cfg.hidden_size)) \
        .astype("float32")
    want = np.asarray(ref.moe_ffn(jnp.asarray(z), w, model))
    of = 8
    held = cfg.n_routed_experts // of
    total = unwrap(whole.shared_experts(paddle.to_tensor(z)))
    for index in range(of):
        part = MoELayer(cfg.hidden_size, cfg.moe_intermediate_size,
                        cfg.n_routed_experts, gate=DeepseekV2ForCausalLM
                        .block.gate(cfg), activation="swiglu",
                        expert_share=(index, of))
        part.gate_weight._data = whole.gate_weight._data
        for name in ("w1", "w3", "w2"):
            getattr(part.experts, name)._data = getattr(
                whole.experts, name)._data[index * held:(index + 1) * held]
        got = unwrap(part(paddle.to_tensor(z)))
        total = total + got
        sl = slice(index * held, (index + 1) * held)
        w_part = dict(w, **{f"mlp.experts.{n}": w[f"mlp.experts.{n}"][sl]
                            for n in ("w1", "w3", "w2")})
        np.testing.assert_allclose(
            got, ref.moe_ffn(jnp.asarray(z), w_part, model, (index, of),
                             shared=False), atol=TOL)
        reached, tokens = np.asarray(unwrap(part.last_groups))
        assert tokens == 19 and 19 <= reached <= 19 * cfg.topk_group
    np.testing.assert_allclose(total, want, atol=TOL)


def test_a_share_must_hold_whole_groups():
    gate = GroupLimitedGate(16, 4, n_group=8, topk_group=3)
    with pytest.raises(ValueError, match="whole groups"):
        MoELayer(64, 32, 16, gate=gate, activation="swiglu",
                 expert_share=(0, 16))
    with pytest.raises(ValueError, match="groups must be whole"):
        GroupLimitedGate(20, 4, n_group=8)


def test_serving_spec_and_what_a_tick_reports(tiny):
    """One 128-lane latent row a token a layer and no second pool, no
    selection and no window; through the engine's decode program the
    groups a token's picks reach stay within topk_group, and the rope
    and the router are the ones named by their counters."""
    cfg, net = tiny[:2]
    spec = net.serving_spec()
    assert [e["rows"] for e in spec["cache_layers"]] == [(128,)] * 3
    assert "index_topk" not in spec and "window" not in spec
    attn = net.layers[1].self_attn
    assert attn.gate_proj is None and attn.indexer is None \
        and attn.window is None
    assert not any("gate_proj" in n or "idx_" in n
                   for n, _ in attn.named_parameters())
    names = ("serving.moe.groups_reached", "serving.moe.tokens_routed",
             "serving.moe.layer_ticks", "kernels.rope.yarn",
             "kernels.moe.route.group_limited")
    before = {n: monitor.counter(n).get() for n in names}
    eng = Engine(net, max_slots=4, page_size=8, prefill_bucket=8,
                 max_context=48)
    try:
        eng.add_request(_ids(11, seed=4), SamplingParams(max_new_tokens=5))
        eng.add_request(_ids(7, seed=5), SamplingParams(max_new_tokens=3))
        while not eng.idle:
            eng.step()
    finally:
        eng.close()
    moved = {n: monitor.counter(n).get() - before[n] for n in names}
    assert moved["kernels.rope.yarn"] > 0
    assert moved["kernels.moe.route.group_limited"] > 0
    # a live token a tick an expert layer: 2 layers, 4 + 2 decode ticks
    assert moved["serving.moe.tokens_routed"] == 2 * (4 + 2)
    assert moved["serving.moe.tokens_routed"] <= \
        moved["serving.moe.groups_reached"] <= \
        cfg.topk_group * moved["serving.moe.tokens_routed"]
