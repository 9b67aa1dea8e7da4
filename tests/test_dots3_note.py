"""dots3-note (text/models/dots3_note.py): the latent-attention decoder
with two kinds of layer, against the plain reference
(benchmark/reference/dots3_note.py), at a tiny size, float32 both sides.

Tiny size: hidden 64, a leading dense full-attention layer, a
full-attention expert layer and a sliding expert layer; 8 experts top-2
with one shared; index_topk 8 and window 5 against contexts of 24-40, so
that the selection and the window both bite."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import dots3_note as ref
from paddle_tpu.core.dispatch import unwrap
from paddle_tpu.incubate.distributed.models.moe import MoELayer
from paddle_tpu.incubate.distributed.models.moe.gate import \
    sigmoid_topk_routing
from paddle_tpu.inference.engine import (Engine, SamplingParams,
                                         _make_paged_pools,
                                         _make_spec_pools)
from paddle_tpu.kernels import paged_attention as paged
from paddle_tpu.text.models import (Dots3NoteConfig, Dots3NoteForCausalLM,
                                    LlamaConfig, LlamaForCausalLM)
from paddle_tpu.text.models.dots3_note import topk_mask

TOL = 1e-4


@pytest.fixture(autouse=True)
def _small_reference_blocks(monkeypatch):
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(7)
    cfg = Dots3NoteConfig.tiny()
    net = Dots3NoteForCausalLM(cfg)
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
    # and the comparison can tell: the selection and the window matter
    dense = np.asarray(ref.logits(weights, model, ids, select=False,
                                  window=False))
    assert ref.errors(got, dense)["rms"] > 0.05


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
        # a second sequence beside it, so the slots differ in position
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
    want = np.asarray(ref.logits(weights, model, seq))[len(prompt) - 1:]
    assert ref.errors(np.stack(out.logits), want)["max"] < TOL
    assert next(o for o in outs if o.logits is None).ok


def test_return_logits_needs_keep_logits(tiny):
    eng = Engine(tiny[1], max_slots=2, page_size=8, prefill_bucket=8,
                 max_context=32)
    try:
        with pytest.raises(ValueError, match="keep_logits"):
            eng.add_request(_ids(5), SamplingParams(return_logits=True))
    finally:
        eng.close()


@pytest.mark.parametrize("option,kwargs", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("cache_dtype='int8'", dict(cache_dtype="int8")),
    ("draft_model", dict(draft_model="any")),
])
def test_engine_refuses_what_it_cannot_do_for_a_latent_spec(tiny, option,
                                                            kwargs):
    with pytest.raises(ValueError, match=option.split("=")[0]):
        Engine(tiny[1], max_slots=2, page_size=8, prefill_bucket=8,
               max_context=32, **kwargs)


def test_latent_spec_pools_are_one_row_a_token(tiny):
    spec = tiny[1].serving_spec()
    pools = _make_spec_pools(spec, 9, 8, jnp.float32, False)
    # full layers: [c_kv 16 ; k_rope 8] padded to 128 lanes + indexer key
    assert [tuple(p.shape for p in layer) for layer in pools] == [
        ((9, 8, 128), (9, 8, 16)), ((9, 8, 128), (9, 8, 16)),
        ((9, 8, 128),)]


def test_one_geometry_spec_builds_todays_pools():
    """A decoder whose spec gives one kv_heads x head_dim (Mistral,
    LLaMA) gets exactly the pools it got before the per-layer spec."""
    net = LlamaForCausalLM(LlamaConfig.tiny())
    spec = net.serving_spec()
    for dtype, quant in ((jnp.bfloat16, False), (jnp.int8, True)):
        new = _make_spec_pools(spec, 11, 16, dtype, quant)
        old = _make_paged_pools(spec["num_layers"], 11, spec["kv_heads"],
                                16, spec["head_dim"], dtype, quant)
        assert jax.tree_util.tree_structure(new) == \
            jax.tree_util.tree_structure(old)
        assert [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(new)] \
            == [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(old)]
    eng = Engine(net, max_slots=2, page_size=16, prefill_bucket=16,
                 max_context=64)
    try:
        assert [tuple(p.shape for p in layer) for layer in eng._pools] == \
            [((9, 4, 16, 16), (9, 4, 16, 16))] * 2
        assert not eng._per_layer and not eng._tick_stats
    finally:
        eng.close()


@pytest.mark.parametrize("kw", [dict(), dict(window=20), dict(mask=True)],
                         ids=["causal", "window", "selected-set"])
def test_paged_mla_decode_matches_the_xla_formulation(kw):
    rng = np.random.default_rng(0)
    b, h, w, dv, bs, mb = 3, 8, 256, 128, 16, 6
    pool = jnp.asarray(rng.normal(size=(1 + b * mb, bs, w)), jnp.float32)
    bt = jnp.asarray(1 + np.arange(b * mb).reshape(b, mb), jnp.int32)
    ctx = jnp.asarray([70, 0, 33], jnp.int32)        # slot 1 is dead
    q = jnp.asarray(rng.normal(size=(b, h, w)), jnp.float32)
    if kw.get("mask"):
        kw = dict(mask=jnp.asarray(rng.integers(0, 2, (b, mb * bs))))
    want = paged.paged_mla_arrays(q, pool, bt, ctx, dv, 0.1, **kw)
    got = paged.paged_mla_decode(q, pool, bt, ctx, dv, 0.1, interpret=True,
                                 **kw)
    assert got.shape == (b, h, dv)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert not np.asarray(got[1]).any()


def test_absorbed_decode_equals_expanded_attention(tiny):
    """One layer of each kind: the last position of an expanded pass over
    n tokens = a one-token absorbed step against the rows the first n - 1
    wrote, through the paged pools."""
    cfg, net, _, _ = tiny
    n, bs_, mb = 21, 8, 4
    x = paddle.to_tensor(np.random.default_rng(3).normal(
        size=(1, n, cfg.hidden_size)).astype("float32"))
    bt = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    for layer in (net.layers[1], net.layers[2]):
        attn = layer.self_attn
        want = unwrap(attn(x))[:, -1]
        pools = tuple(jnp.zeros((mb + 1, bs_, w), jnp.float32)
                      for w in attn.cache_rows())
        _, cache = attn(x[:, :n - 1], kv_cache=pools + (bt,),
                        cache_index=jnp.asarray([0], jnp.int32))
        got, _ = attn(x[:, n - 1:], kv_cache=cache,
                      cache_index=jnp.asarray([n - 1], jnp.int32))
        np.testing.assert_allclose(unwrap(got)[:, 0], want, atol=TOL)


@pytest.mark.parametrize("starts,s,width", [
    ([0], 16, 6), ([5], 16, 6), ([16], 16, 6), ([19], 16, 6),
    ([7], 32, 5), ([3, 22], 16, 6)],
    ids=["before-the-window", "at-the-window", "page-aligned",
         "past-the-window-unaligned", "last-block-clamped", "two-rows"])
def test_a_sliding_block_scores_the_band_its_window_can_keep(
        tiny, monkeypatch, starts, s, width):
    """A sliding layer's chunk of `s` tokens at `starts` through the paged
    pool, in blocks of 8 queries that each score a band of 24 rows (window
    5, pages of 8) from an aligned start, against the cache-less pass over
    the same tokens in ONE block, which scores every row. The last block
    of a table `width` = 5 columns wide starts where the clamp puts it;
    block-table columns past a row's context point at a page of large
    numbers."""
    from paddle_tpu import monitor
    cfg, net, _, _ = tiny
    attn = net.layers[2].self_attn
    b, bs_ = len(starts), 8
    band, whole = (monitor.counter(f"kernels.prefill.swa_{n}")
                   for n in ("band", "whole"))
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(b, max(starts) + s, cfg.hidden_size)), jnp.float32)
    monkeypatch.setattr(attn, "q_block", 1 << 20)
    n_whole, n_band = whole.get(), band.get()
    want = unwrap(attn(paddle.to_tensor(x)))
    assert (whole.get(), band.get()) == (n_whole + 1, n_band)
    junk = b * width + 1
    pools = tuple(jnp.zeros((junk + 1, bs_, w), jnp.float32).at[junk].set(1e3)
                  for w in attn.cache_rows())
    bt = np.full((b, width), junk, np.int32)
    for r, p0 in enumerate(starts):
        n_pages = -(-(p0 + s) // bs_)
        bt[r, :n_pages] = 1 + r * width + np.arange(n_pages)
    bt = jnp.asarray(bt)
    for r, p0 in enumerate(starts):
        if p0:      # what came before the chunk, written a row at a time
            _, cache = attn(paddle.to_tensor(x[r:r + 1, :p0]),
                            kv_cache=pools + (bt[r:r + 1],),
                            cache_index=jnp.asarray([0], jnp.int32))
            pools = cache[:-1]
    monkeypatch.setattr(attn, "q_block", 8)
    n_band = band.get()
    chunk = jnp.stack([x[r, p0:p0 + s] for r, p0 in enumerate(starts)])
    got, _ = attn(paddle.to_tensor(chunk), kv_cache=pools + (bt,),
                  cache_index=jnp.asarray(starts, jnp.int32))
    assert band.get() == n_band + 1
    for r, p0 in enumerate(starts):
        np.testing.assert_allclose(unwrap(got)[r], want[r, p0:p0 + s],
                                   rtol=1e-5, atol=1e-6)


# the kernel under a full layer's prefill, at the published head widths
# (192-wide keys, 128-wide values) and its own tiles (256 queries x 512
# keys), two heads: starts of each batch row, chunk tokens, gathered
# keys, the selection's size (None: L <= index_topk, plain causal)
FLASH_CASES = {
    "first-chunk-causal": ([0], 512, 512, None),
    "second-chunk-selected": ([512], 512, 1024, 384),
    "start-off-a-tile-bound": ([300], 512, 1024, 384),
    "two-rows": ([0, 700], 256, 1024, 384),
    "garbage-past-the-last-query": ([100], 256, 1536, None),
    "chunk-shorter-than-a-key-tile": ([128], 128, 512, 200),
}


@pytest.mark.parametrize("starts,s,L,topk", FLASH_CASES.values(),
                         ids=FLASH_CASES.keys())
def test_mla_flash_prefill_matches_the_xla_blocks(starts, s, L, topk):
    """`mla_flash_prefill` (interpret mode) against `mla_block_xla`, the
    XLA formulation a block at a time, on the same q, K, V and mask: the
    mask a full layer builds (causal from each row's start, then
    `topk_mask` of seeded indexer scores). Rows past a row's last query
    hold large numbers: the kernel skips their tiles or masks them, and
    not a digit changes when they are zeros instead."""
    from paddle_tpu.kernels import mla_prefill as mp
    rng = np.random.default_rng(11)
    b, H, dk, dv, scale = len(starts), 2, 192, 128, 192 ** -0.5
    q, k, v = (jnp.asarray(rng.normal(size=(b, n, H, d)), jnp.float32)
               for n, d in ((s, dk), (L, dk), (L, dv)))
    q_pos = np.asarray(starts)[:, None] + np.arange(s)[None]
    keep = jnp.asarray(np.arange(L)[None, None] <= q_pos[:, :, None])
    if topk is not None:
        I = jnp.where(keep, jnp.asarray(rng.normal(size=(b, s, L)),
                                        jnp.float32), -jnp.inf)
        keep &= topk_mask(I.reshape(b * s, L), topk).reshape(b, s, L)
        assert int(keep.sum(-1).max()) == topk
    live = jnp.asarray(np.arange(L)[None] <= q_pos[:, -1:])    # [b, L]
    junk = jnp.where(live[:, :, None, None], 0.0, 1e3)

    def flash(k, v):
        return mp.mla_flash_prefill(
            *(jnp.moveaxis(a, 2, 1) for a in (q, k, v)),
            keep.astype(jnp.int8), scale, interpret=True)

    got = flash(k + junk, v + junk)
    bq = mp.mla_prefill_tiles(s, L, dk, dv, jnp.float32)[0]
    want = jnp.concatenate(
        [mp.mla_block_xla(q[:, i:i + bq], k, v, keep[:, i:i + bq], scale)
         for i in range(0, s, bq)], 1)
    assert got.shape == (b, s, H * dv) and got.dtype == jnp.float32
    np.testing.assert_allclose(got.reshape(want.shape), want, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(
        got, flash(jnp.where(live[:, :, None, None], k, 0.0),
                   jnp.where(live[:, :, None, None], v, 0.0)))


def test_mla_flash_prefill_requirements_name_what_does_not_tile():
    from paddle_tpu.kernels import mla_prefill as mp
    assert mp.mla_prefill_requirements(2048, 5120, 192, 128,
                                       jnp.bfloat16) is None
    assert mp.mla_prefill_tiles(2048, 5120, 192, 128,
                                jnp.bfloat16)[:2] == (256, 512)
    assert mp.mla_prefill_tiles(384, 1280, 192, 128,
                                jnp.bfloat16)[:2] == (128, 256)
    for shape, word in (((24, 1024, 24, 128), "queries"),
                        ((256, 1000, 192, 128), "keys"),
                        ((256, 1024, 24, 16), "value width"),
                        ((256, 1 << 17, 192, 128), "VMEM")):
        assert word in mp.mla_prefill_requirements(*shape, jnp.bfloat16)


def test_full_layer_chunked_prefill_on_the_kernel_equals_the_whole_pass(
        monkeypatch):
    """A full layer whose shapes tile (values 128 wide, chunks of 128
    tokens, pages of 128), through `forward` with its cache: two chunks
    on `mla_flash_prefill` (interpret mode; the platform steered here,
    as on the chip) against the cache-less pass over all the tokens in
    XLA. The second chunk's switch takes its wider branch and the
    indexer drops keys (index_topk 96 < 256)."""
    import functools
    from paddle_tpu import monitor
    from paddle_tpu.core import place
    from paddle_tpu.kernels import mla_prefill as mp
    from paddle_tpu.text.models.dots3_note import (FULL,
                                                   Dots3LatentAttention)
    paddle.seed(3)
    cfg = Dots3NoteConfig.tiny(v_head_dim=128, index_topk=96,
                               prefill_query_block=64,
                               prefill_key_block=128)
    attn = Dots3LatentAttention(cfg, FULL)
    n, bs_ = 256, 128
    x = paddle.to_tensor(np.random.default_rng(4).normal(
        size=(1, n, cfg.hidden_size)).astype("float32"))
    flash, xla = (monitor.counter(f"kernels.prefill.mla_{name}")
                  for name in ("flash", "xla"))
    n_xla = xla.get()
    want = unwrap(attn(x))
    assert xla.get() == n_xla + 1
    monkeypatch.setattr(place, "accelerator_available", lambda: True)
    monkeypatch.setattr(mp, "mla_flash_prefill", functools.partial(
        mp.mla_flash_prefill, interpret=True))
    cache = tuple(jnp.zeros((3, bs_, w), jnp.float32)
                  for w in attn.cache_rows()) \
        + (jnp.asarray([[1, 2]], jnp.int32),)
    n_flash, n_xla, got = flash.get(), xla.get(), []
    for p0 in (0, 128):
        out, cache = attn(x[:, p0:p0 + 128], kv_cache=cache,
                          cache_index=jnp.asarray([p0], jnp.int32))
        got.append(unwrap(out))
    # one bump a branch of the switch over key lengths (128, 256), a call
    assert (flash.get(), xla.get()) == (n_flash + 4, n_xla)
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, atol=TOL)


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
        # the reference, given the same share, gives the same part
        sl = slice(index * held, (index + 1) * held)
        w_part = dict(w, **{f"mlp.experts.{n}": w[f"mlp.experts.{n}"][sl]
                            for n in ("w1", "w3", "w2")})
        np.testing.assert_allclose(
            unwrap(part(paddle.to_tensor(z))),
            ref.moe_ffn(jnp.asarray(z), w_part, model, (index, of),
                        shared=False), atol=TOL)
        held_picks, picks, touched, slabs = np.asarray(
            unwrap(part.last_stats))
        assert picks == 19 * cfg.num_experts_per_tok
        assert 0 <= held_picks <= picks and touched <= held
        # a new layer is in training mode: every slab of the 38 picks
        assert slabs == 2
    np.testing.assert_allclose(total, want, atol=TOL)


def test_dead_tokens_claim_no_expert(tiny):
    layer = tiny[1].layers[1].mlp
    z = paddle.to_tensor(np.random.default_rng(6).normal(
        size=(1, 6, 64)).astype("float32"))
    mask = jnp.asarray([[True, False, True, True, False, True]])
    masked = unwrap(layer(z, token_mask=mask, decode_mode=True))
    alone = unwrap(layer(z))
    assert np.asarray(unwrap(layer.last_stats))[1] == 6 * 2
    layer(z, token_mask=mask, decode_mode=True)
    assert np.asarray(unwrap(layer.last_stats))[1] == 4 * 2
    np.testing.assert_allclose(masked[0, [0, 2, 3, 5]],
                               alone[0, [0, 2, 3, 5]], atol=1e-6)
    # a dead token gets the shared expert only
    np.testing.assert_allclose(
        masked[0, 1], unwrap(layer.shared_experts(z))[0, 1], atol=1e-6)


def test_sigmoid_routing_bias_steers_the_choice_not_the_weight():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    idx, w = sigmoid_topk_routing(logits, jnp.zeros(4), 2)
    assert idx.tolist() == [[0, 1]]
    p = np.asarray(jax.nn.sigmoid(logits[0]))
    np.testing.assert_allclose(w[0], p[:2] / p[:2].sum(), rtol=1e-6)
    idx, w = sigmoid_topk_routing(logits, jnp.asarray([0, 0, 0, 5.0]), 2)
    assert sorted(idx[0].tolist()) == [0, 3]
    np.testing.assert_allclose(sorted(w[0].tolist()),
                               sorted((p[[0, 3]] / p[[0, 3]].sum())
                                      .tolist()), rtol=1e-6)


@pytest.mark.parametrize("k", [1, 5, 8, 40])
def test_topk_mask_is_lax_top_k_with_ties_to_the_lower_index(k):
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(7, 33)).astype("float32")
    scores[0, :] = 0.5                       # all tied
    scores[1, 10:] = -np.inf                 # fewer finite than k
    scores[2, ::3] = scores[2, 1]            # ties at some value
    scores[3] = np.abs(scores[3]) * -1.0     # all negative
    got = np.asarray(topk_mask(jnp.asarray(scores), k))
    _, idx = jax.lax.top_k(jnp.asarray(scores), min(k, 33))
    want = np.zeros_like(got)
    np.put_along_axis(want, np.asarray(idx), True, axis=1)
    assert (got == want).all()


def test_parameters_are_created_in_the_configured_dtype():
    net = Dots3NoteForCausalLM(Dots3NoteConfig.tiny(dtype="bfloat16"))
    assert {str(unwrap(p).dtype) for _, p in net.named_parameters()} == \
        {"bfloat16"}
    # outside the model's constructor nothing changed
    assert str(unwrap(paddle.nn.Linear(2, 2).weight).dtype) == "float32"


def test_serving_spec_gives_the_cache_per_layer(tiny):
    cfg, net, _, _ = tiny
    spec = net.serving_spec()
    assert [layer["window"] for layer in spec["cache_layers"]] == \
        [None, None, 5]
    assert spec["index_topk"] == 8 and spec["window"] == 5
    assert "kv_heads" not in spec and "moe_layer" not in spec
    # at the published widths: 576 -> 640, 1088 -> 1152, padding < 1/8
    big = Dots3NoteConfig(num_hidden_layers=5)
    rows = {"full": -(-(big.kv_lora_rank + big.qk_rope_head_dim) // 128)
            * 128, "swa": -(-(big.swa_kv_lora_rank
                             + big.swa_qk_rope_head_dim) // 128) * 128}
    assert rows == {"full": 640, "swa": 1152}
    assert big.layer_types == ("full_attention", "full_attention",
                               "sliding_attention", "sliding_attention",
                               "sliding_attention")
    full = Dots3NoteConfig()
    assert (full.layer_types.count("full_attention"),
            full.layer_types.count("sliding_attention")) == (13, 33)
    assert full.layer_types[-1] == "full_attention"


def test_decode_span_arguments_and_window_gauge(tiny):
    from paddle_tpu import monitor
    eng = Engine(tiny[1], max_slots=2, page_size=8, prefill_bucket=8,
                 max_context=48)
    try:
        eng._pos[:] = [20, 3]
        # lane 1 rides the tick in flight: the program reads it one
        # position past the host's mirror
        args = eng._dispatch_span_args([(0, None, 0), (1, None, 1)],
                                       "greedy")
        assert args == {"variant": "greedy", "slots": 2, "ticks": 1,
                        "inflight": 0, "ctx_tokens": 20 + 4,
                        "sel_tokens": 8 + 5, "win_tokens": 5 + 5}
        eng.add_request(_ids(30), SamplingParams(max_new_tokens=3))
        eng.step()
        eng.step()
        # 30+ tokens written, window 5: positions < written - 4 are out
        assert monitor.snapshot()[
            "serving.cache.swa_pages_outside_window"] == (31 - 4) // 8
        while not eng.idle:
            eng.step()
    finally:
        eng.close()
