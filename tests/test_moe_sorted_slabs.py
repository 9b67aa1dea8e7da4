"""The sorted expert dispatch in slabs (MoELayer._forward_sorted): a layer
that holds one ``of``-th of the experts works its sorted picks off in
slabs of twice its uniform share (in an odd number of row tiles), as many
as the held picks fill, and gives what the whole order in one piece gives,
whatever the routing.

Routing is steered by ``e_score_correction_bias``: +10 on the first
``a`` held experts and -10 on the others held makes every token pick
exactly ``a`` held experts, so ``a * N`` rows are held: of the 1,024
picks of 256 tokens a share of 4 takes slabs of 640 rows (5 x 128), a
share of 8 slabs of 288 (9 x 32)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.core.dispatch import unwrap
from paddle_tpu.incubate.distributed.models.moe import MoELayer, moe_layer
from paddle_tpu.inference.engine import Engine, SamplingParams
from paddle_tpu.jit.functional import functional_call, get_params
from paddle_tpu.text.models import (Dots3NoteConfig, Dots3NoteForCausalLM,
                                    SolarOpen2Config, SolarOpen2ForCausalLM)

D, F, EXPERTS, TOP_K, TOKENS = 32, 16, 32, 4, 256


def _layer(of, a=None, seed=0):
    """A layer holding share 0 of ``of``, every token steered onto
    exactly ``a`` held experts (None: the router as drawn)."""
    paddle.seed(seed)
    layer = MoELayer(D, F, EXPERTS, gate="sigmoid_topk", top_k=TOP_K,
                     activation="swiglu", expert_share=(0, of))
    layer.eval()
    rng = np.random.default_rng(seed)
    for p in (layer.experts.w1, layer.experts.w3, layer.experts.w2):
        p._data = jnp.asarray(rng.normal(size=p.shape), jnp.float32) * 0.3
    if a is not None:
        bias = np.zeros(EXPERTS, "float32")
        bias[:layer.num_held] = -10.0
        bias[:a] = 10.0
        layer.e_score_correction_bias._data = jnp.asarray(bias)
    return layer


def _tokens(n=TOKENS, seed=1):
    return np.random.default_rng(seed).normal(size=(n, D)).astype("float32")


def _run(layer, x, **kw):
    out = np.asarray(unwrap(layer(paddle.to_tensor(x), **kw)))
    return out, np.asarray(unwrap(layer.last_stats))


def _dense(layer, params, x, live=None):
    """Every held expert over every token, weighted by the router: the
    same function written without a sort, for values and for jax.grad."""
    from paddle_tpu.incubate.distributed.models.moe.gate import \
        sigmoid_topk_routing
    logits = jnp.dot(x, params["gate_weight"],
                     preferred_element_type=jnp.float32)
    idx, w = sigmoid_topk_routing(
        logits, unwrap(layer.e_score_correction_bias), TOP_K,
        layer.gate.norm_topk_prob, layer.gate.routed_scaling_factor)
    if live is not None:
        w = jnp.where(jnp.asarray(live)[:, None], w, 0.0)
    out = 0.0
    for e in range(layer.num_held):
        w_e = jnp.sum(jnp.where(idx == e + layer.first_held, w, 0.0), axis=1)
        g, u = x @ params["experts.w1"][e], x @ params["experts.w3"][e]
        out = out + w_e[:, None] * ((jax.nn.silu(g) * u)
                                    @ params["experts.w2"][e])
    return out


# (of, held experts a token is steered onto, slabs that run)
CASES = [(1, None, 1), (1, 4, 1), (2, 0, 1), (2, None, 1), (2, 4, 1),
         (4, 0, 0), (4, None, 1), (4, 2, 1), (4, 3, 2), (4, 4, 2),
         (8, 0, 0), (8, None, 1), (8, 1, 1), (8, 2, 2), (8, 3, 3),
         (8, 4, 4)]


@pytest.mark.parametrize("of,a,slabs", CASES)
def test_slab_form_is_the_whole_form(monkeypatch, of, a, slabs):
    """Bit for bit: a slab computes its rows as the whole order does,
    and a token's picks are added in the same order either way."""
    layer, x = _layer(of, a), _tokens()
    form = monitor.counter(
        f"kernels.moe.sorted.{'slab' if of > 2 else 'whole'}")
    built = form.get()
    got, stats = _run(layer, x)
    assert form.get() == built + 1
    assert stats[3] == slabs
    if a is not None:
        assert stats[0] == a * TOKENS
    assert stats[1] == TOKENS * TOP_K
    monkeypatch.setattr(moe_layer, "_slab_rows", lambda m, of: m)
    want, whole_stats = _run(layer, x)
    assert whole_stats[3] == 1 and (whole_stats[:3] == stats[:3]).all()
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got, _dense(layer, get_params(layer), x),
                               atol=1e-4)


@pytest.mark.parametrize("of,slabs_all,slabs_half", [(1, 1, 1), (4, 2, 1),
                                                     (8, 4, 2)])
def test_dead_tokens_claim_no_row(of, slabs_all, slabs_half):
    """A token masked out adds no pick to any group: with every pick
    held, half the tokens fill half the slabs."""
    layer, x = _layer(of, TOP_K), _tokens()
    live = np.arange(TOKENS) % 2 == 0
    _, stats = _run(layer, x)
    assert stats[3] == slabs_all
    got, stats = _run(layer, x, token_mask=jnp.asarray(live),
                      decode_mode=True)
    assert stats[3] == slabs_half
    assert stats[0] == stats[1] == live.sum() * TOP_K
    assert not got[~live].any()
    np.testing.assert_allclose(
        got, _dense(layer, get_params(layer), x, live), atol=1e-4)


@pytest.mark.parametrize("of,a", [(4, None), (4, 3), (8, None), (8, 3)])
def test_a_token_reads_the_same_alone_and_in_a_batch(of, a):
    """The engine's token-exactness contract: N = 1 takes the whole
    form (one slab takes its picks); in a batch whose other tokens fill
    a second slab its picks lie wherever the sort puts them, some in
    each slab: the bits are the same."""
    layer, x = _layer(of, a), _tokens()
    # the router's matmul sums in another order for one row than for 64
    # on the CPU: on a grid where every partial sum is exact it cannot
    x = np.round(x * 4) / 4
    layer.gate_weight._data = jnp.round(unwrap(layer.gate_weight) * 8) / 8
    if a is None:
        # the others onto the held experts; token 0 as the router has it
        x[1:] += 8 * np.asarray(unwrap(layer.gate_weight))[
            :, :layer.num_held].sum(axis=1)
    params = get_params(layer)

    @jax.jit                    # compiled, as the engine's programs are
    def run(x):
        out, _ = functional_call(layer, params, {}, (paddle.to_tensor(x),),
                                 {})
        return unwrap(out), unwrap(layer.last_stats)

    batch, stats = run(x)
    assert stats[3] >= 2
    for t in (0, 77, TOKENS - 1):
        alone, one = run(x[t:t + 1])
        assert one[3] == 1
        np.testing.assert_array_equal(batch[t], alone[0])


@pytest.mark.parametrize("of,training", [(1, False), (1, True), (4, True)])
def test_grad_flows_through_the_layer(of, training):
    """Reverse mode: the whole form as it stands, the slab form with the
    static trip count it takes in training."""
    layer = _layer(of, 3)
    layer.train() if training else layer.eval()
    params, x = get_params(layer), jnp.asarray(_tokens())

    def loss(params, x):
        out, _ = functional_call(layer, params, {}, (paddle.to_tensor(x),),
                                 {})
        return jnp.sum(unwrap(out) ** 2)

    got = jax.grad(loss, argnums=(0, 1))(params, x)
    if of > 1:
        assert int(unwrap(layer.last_stats)[3]) == 2    # 1,024 in 640s
    want = jax.grad(lambda p, x: jnp.sum(_dense(layer, p, x) ** 2),
                    argnums=(0, 1))(params, x)
    assert float(jnp.abs(want[0]["experts.w2"]).max()) > 0
    assert float(jnp.abs(want[0]["gate_weight"]).max()) > 0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3,
                                   atol=2e-4 * float(jnp.abs(w).max()))


def test_a_traced_trip_count_is_for_inference_only():
    """What `self.training` decides: outside training the slab loop runs
    as often as the held picks need, which reverse mode cannot take."""
    layer = _layer(4, 3)
    params, x = get_params(layer), jnp.asarray(_tokens())

    def loss(params, x):
        out, _ = functional_call(layer, params, {}, (paddle.to_tensor(x),),
                                 {})
        return jnp.sum(unwrap(out))

    with pytest.raises(ValueError, match="[Rr]everse-mode"):
        jax.jit(jax.grad(loss))(params, x)


@pytest.mark.parametrize("m,of,rows", [
    (16384, 8, 4224), (8192, 8, 2176), (14336, 8, 3712), (2048, 8, 640),
    (768, 8, 224), (384, 8, 96), (8, 8, 8), (100, 8, 32),
    (4096, 1, 4096), (4096, 2, 4096), (4096, 4, 2176)])
def test_slab_rows(m, of, rows):
    """Twice the uniform share, rounded up to an odd number of row tiles
    (the tile XLA's ragged_dot then takes): of 128 rows, or of 32 for a
    slab under 512; the whole order when that takes it all."""
    assert moe_layer._slab_rows(m, of) == rows
    tile = 128 if 2 * m // of >= 512 else 32
    assert rows == m or rows % (2 * tile) == tile and rows * of >= 2 * m


@pytest.mark.parametrize("make,config", [
    (Dots3NoteForCausalLM, Dots3NoteConfig),
    (SolarOpen2ForCausalLM, SolarOpen2Config)], ids=["dots3", "solar2"])
def test_models_report_the_slabs(make, config):
    """`serving.moe.slabs` is in `tick_stats` beside the four names the
    benchmark's metrics read, and adds up the layers' slabs; the model
    reports each name as the engine reads it, by name."""
    paddle.seed(3)
    net = make(config.tiny(expert_share=(0, 4)))
    net.eval()
    names = net.serving_spec()["tick_stats"]
    assert names == ("serving.moe.picks_held", "serving.moe.picks_total",
                     "serving.moe.experts_touched",
                     "serving.moe.layer_ticks", "serving.moe.slabs")
    net(paddle.to_tensor(np.random.default_rng(0).integers(0, 96, (1, 40))))
    layers = [lyr.mlp for lyr in net.layers
              if isinstance(lyr.mlp, MoELayer)]
    per_layer = np.stack([np.asarray(unwrap(m.last_stats)) for m in layers])
    got = {n: int(v) for n, v in net.serving_tick_stats().items()}
    assert set(got) == set(names)
    assert got["serving.moe.layer_ticks"] == len(layers)
    assert [got[n] for n in names[:3]] == \
        per_layer[:, :3].sum(axis=0).tolist()
    assert got["serving.moe.slabs"] == per_layer[:, 3].sum() >= 1


def test_engine_counts_slabs_with_the_ticks():
    """Through the engine's decode program: one slab a layer a tick while
    the bound holds, so the two counters move together."""
    paddle.seed(3)
    net = Dots3NoteForCausalLM(Dots3NoteConfig.tiny(expert_share=(0, 2)))
    net.eval()
    before = {n: monitor.counter(f"serving.moe.{n}").get()
              for n in ("slabs", "layer_ticks")}
    eng = Engine(net, max_slots=4, page_size=8, prefill_bucket=8,
                 max_context=48)
    try:
        eng.add_request(np.random.default_rng(1).integers(0, 96, 11),
                        SamplingParams(max_new_tokens=5))
        while not eng.idle:
            eng.step()
    finally:
        eng.close()
    moved = {n: monitor.counter(f"serving.moe.{n}").get() - before[n]
             for n in before}
    assert moved["slabs"] == moved["layer_ticks"] > 0
