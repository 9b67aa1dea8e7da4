"""Compile-only tests for the chip: the Pallas kernels of the main path, at
the real widths, handed to the TPU compiler for a described (not attached)
``v5e:2x2``. Interpret mode cannot see what Mosaic refuses — an unaligned
slice, a shape cast, too much VMEM — and a refused kernel raises on the
chip now that nothing reroutes it, so these guard every later PR at no
chip time. Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture (only one process
may hold the TPU library, and only after a test of THIS file has started:
never at import, in a skipif, in parametrize or in conftest.py), and every
compile happens in this process with the persistent cache off (an entry
written for a described chip cannot be read back without one).
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.kernels import moe
from paddle_tpu.kernels.flash_attention import flash_attention_arrays
from paddle_tpu.kernels.paged_attention import (gather_page_scales,
                                                gather_pages, gather_rows,
                                                paged_decode_pallas,
                                                paged_mla_decode,
                                                paged_pallas_requirements,
                                                paged_write_arrays,
                                                paged_write_quant_arrays,
                                                paged_write_rows,
                                                window_pages)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # one described chip, so no hybrid mesh: an earlier test file of this
    # worker may have left its 8-CPU-device mesh as the paddle global,
    # and the flash route would split the call over it
    mesh_was, mesh_mod._global_mesh = mesh_mod.get_mesh(), None
    yield SingleDeviceSharding(topo.devices[0])
    mesh_mod._global_mesh = mesh_was
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash(q, k, v):
    return flash_attention_arrays(q, k, v, causal=True, force_pallas=True)


def _flash_grad(q, k, v):
    return jax.grad(lambda *a: _flash(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))(q, k, v)


# q/k/v [batch, seq, heads, head_dim]: the LLaMA-7B-width trainer's call
# (bench_llama_1b, chip_smoke.py) and the 64-wide BERT-base geometry that
# `_head_dim_ok` admits without a probe
FLASH_SHAPES = {"llama7b-width": (12, 1024, 32, 128),
                "bert-base-d64": (2, 512, 12, 64)}


@pytest.mark.parametrize("shape", FLASH_SHAPES.values(),
                         ids=FLASH_SHAPES.keys())
@pytest.mark.parametrize("fn,n_kernels", [(_flash, 1), (_flash_grad, 3)],
                         ids=["fwd", "grad"])
def test_flash_attention_compiles(one_chip, shape, fn, n_kernels):
    text = _compiled_text(fn, one_chip, *[(shape, jnp.bfloat16)] * 3)
    assert text.count("tpu_custom_call") == n_kernels


def _paged_shapes(pool_dtype, page, slots=16, heads=32, d=128, pages=4):
    nb = slots * pages + 1
    shapes = [((slots, heads, d), jnp.bfloat16),
              ((nb, heads, page, d), pool_dtype),
              ((nb, heads, page, d), pool_dtype),
              ((slots, pages), jnp.int32), ((slots,), jnp.int32)]
    if pool_dtype == jnp.int8:
        shapes += [((nb, heads, page), jnp.float32)] * 2
    return shapes


def _paged(q, kc, vc, bt, cl, ks=None, vs=None):
    return paged_decode_pallas(q, kc, vc, bt, cl, k_scale=ks, v_scale=vs)


# every page geometry `paged_pallas_requirements` calls eligible must be
# one the compiler takes: the engine's shape (16 slots x 32 heads x d128,
# page 128) per pool dtype, and each dtype's smallest eligible page
@pytest.mark.parametrize("pool_dtype,page", [
    (jnp.bfloat16, 128), (jnp.bfloat16, 16), (jnp.float32, 8),
    (jnp.int8, 128), (jnp.int8, 256)])
def test_paged_decode_compiles(one_chip, pool_dtype, page):
    assert paged_pallas_requirements(128, page, pool_dtype) is None
    text = _compiled_text(_paged, one_chip,
                          *_paged_shapes(pool_dtype, page))
    assert text.count("tpu_custom_call") == 1


def test_paged_decode_int8_narrow_page_is_refused(one_chip):
    """What the eligibility rule for int8 pools rests on: a scale row
    narrower than a lane tile is refused ("Slice shape along dimension 3
    must be aligned to tiling (128), but is 32"), so the predicate names
    that geometry ineligible and the engine routes it to the XLA gather
    by decision. When the compiler starts taking it, this fails and the
    rule can be loosened."""
    assert "128 lanes" in paged_pallas_requirements(128, 32, jnp.int8)
    with pytest.raises(Exception, match="aligned to tiling"):
        _compiled_text(_paged, one_chip, *_paged_shapes(jnp.int8, 32))


# the cells' decode calls, each its own walk over a slot's live chunks:
# (query heads, kv heads or None for a latent pool, row width, value width,
# slots, pages a slot, keep-mask); pages of 128, bfloat16
WALK_CALLS = {
    # Falcon-H1: 20 / 4 heads of 128 (a group of 5), 96 slots x 21 pages
    "reply96": (20, 4, 128, 128, 96, 21, False),
    # DeepSeek-V2: 128 heads on 640-lane latent rows, 64 slots x 104
    "docs64": (128, None, 640, 512, 64, 104, False),
    # dots3-note's full layer: the same row under the selected-set mask
    "notes48-masked": (128, None, 640, 512, 48, 40, True),
}


@pytest.mark.parametrize("cell", WALK_CALLS)
def test_live_walk_compiles_at_the_cells_geometries(one_chip, cell):
    """The decode kernel's walk (a (slot, head block) grid, a loop over
    the slot's live chunks, the next-live-slot table) is taken by the
    TPU compiler at each cell's geometry: one Pallas call under the
    kernel's own name, counted once in `kernels.decode.paged_live_walk`."""
    from paddle_tpu import monitor
    heads, kv, w, dv, slots, pages, masked = WALK_CALLS[cell]
    nb = slots * pages + 1
    shapes = [((slots, heads, w), jnp.bfloat16),
              ((nb, 128, w) if kv is None else (nb, kv, 128, w),
               jnp.bfloat16),
              ((slots, pages), jnp.int32), ((slots,), jnp.int32)]
    if kv is not None:
        shapes.insert(2, ((nb, kv, 128, w), jnp.bfloat16))
    if masked:
        shapes.append(((slots, pages * 128), jnp.int32))

    def call(q, *rest):
        if kv is not None:
            return paged_decode_pallas(q, *rest)
        pool, bt, cl, *mask = rest
        return paged_mla_decode(q, pool, bt, cl, dv, 0.07,
                                mask=mask[0] if mask else None)

    walks = monitor.counter("kernels.decode.paged_live_walk")
    before = walks.get()
    text = _compiled_text(call, one_chip, *shapes)
    name = "paged_decode" if kv is not None else "paged_mla_decode"
    assert text.count("tpu_custom_call") == 1 and f"%{name}" in text
    assert walks.get() == before + 1


# the serve cell's pools (benchmark/configs/mistral-7b-serve-8l.json):
# 48 slots x 18 pages + the scratch page, 8 kv heads, page 128, d 128
POOL, BT_WIDTH, Q_HEADS = (865, 8, 128, 128), 18, 32


def _write_then_attend(q, k, v, bt, pos, *pools):
    """One layer's cache step as `_paged_cached_attention` runs it: the
    write, then the decode kernel for one token a slot or the page
    gather for a prefill chunk; the pools go back out (donated)."""
    if len(pools) == 4:
        pools = paged_write_quant_arrays(k, v, *pools, bt, pos)
    else:
        pools = paged_write_arrays(k, v, *pools, bt, pos)
    kc, vc, *scales = pools
    if k.ndim == 3:
        ks, vs = scales or (None, None)
        out = paged_decode_pallas(q, kc, vc, bt, pos + 1,
                                  k_scale=ks, v_scale=vs)
    else:
        out = [gather_pages(kc, bt), gather_pages(vc, bt)] \
            + [gather_page_scales(s, bt) for s in scales]
    return out, pools


@pytest.mark.parametrize("tokens", [None, 512],
                         ids=["one-token-48-slots", "chunk-512"])
@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8-and-scales"])
def test_paged_write_updates_the_pool_in_place(one_chip, pool_dtype,
                                               tokens):
    """The KV write must land in the donated pool where it lies: no
    `copy` of a pool's shape in the compiled program, temporaries under
    a tenth of one pool, both pools aliased. Fails on the write as it
    was up to PR 26 (`pool.at[page, :, slot].set(x)`: the scatter
    straddles the head dimension, gets an operand layout with page and
    slot major, and the compiler transposes the whole pool there and
    back: 2 copies a pool, temp_size_in_bytes = one pool, 22 ms of a 31
    ms decode tick on the chip)."""
    b = 48 if tokens is None else 1
    kv = (b, POOL[1], POOL[3]) if tokens is None \
        else (b, tokens, POOL[1], POOL[3])
    pools = [(POOL, pool_dtype)] * 2
    if pool_dtype == jnp.int8:
        pools += [(POOL[:3], jnp.float32)] * 2
    shapes = [((b, Q_HEADS, POOL[3]), jnp.bfloat16), (kv, jnp.bfloat16),
              (kv, jnp.bfloat16), ((b, BT_WIDTH), jnp.int32),
              ((b,), jnp.int32)] + pools
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(_write_then_attend,
                       donate_argnums=tuple(range(5, len(args)))
                       ).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (1 if tokens is None else 0)
    pages, h_kv, page, d = POOL             # value pools and scale pools
    pool_copy = re.compile(
        rf"= \w+\[{pages},{h_kv},{page}(,{d})?\]\{{[^}}]*\}} copy\(")
    assert [line.strip()[:120] for line in text.splitlines()
            if pool_copy.search(line)] == []
    one_pool = math.prod(POOL) * jnp.dtype(pool_dtype).itemsize
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < one_pool / 10
    assert mem.alias_size_in_bytes >= 2 * one_pool


# the latent cell's pools (benchmark/configs/dots3-note-serve-5l.json):
# 48 slots x 40 pages + the scratch page, page 128, one vector a token:
# a full layer's 640-wide row and its indexer's 128-wide key, a sliding
# layer's 1152-wide row read through its 5 window pages
LATENT_PAGES, LATENT_BT = 1921, 40
LATENT_LAYERS = {
    "full": dict(heads=128, rows=(640, 128), dv=512, window=None),
    "sliding": dict(heads=64, rows=(1152,), dv=1024, window=513),
}


def _latent_write_then_attend(kind, q, new, bt, pos, mask, *pools):
    """One latent layer's cache step as Dots3LatentAttention runs it:
    the head-less write, then the latent decode kernel for one token a
    slot (a full layer under the selected-set mask, a sliding one over
    its window's pages) or the gathers of a prefill chunk."""
    cfg = LATENT_LAYERS[kind]
    pools = paged_write_rows(new, pools, bt, pos)
    window, page = cfg["window"], pools[0].shape[1]
    s = new[0].shape[1]
    bt_r, base = bt, jnp.zeros_like(pos)
    if window is not None:
        first = jnp.maximum(pos - (window - 1), 0) // page
        n_win = (window + s - 3) // page + 2
        bt_r, base = window_pages(bt, first, n_win), first * page
    if s == 1:
        out = paged_mla_decode(
            q, pools[0], bt_r, pos + 1 - base, cfg["dv"], 0.07,
            window=window, mask=mask if window is None else None,
            pages_per_chunk=None if window is None else bt_r.shape[1])
        out = [out] + [gather_rows(p, bt) for p in pools[1:]]
    else:
        out = [gather_rows(pools[0], bt_r)] \
            + [gather_rows(p, bt) for p in pools[1:]]
    return out, pools


@pytest.mark.parametrize("tokens", [1, 512, 1024],
                         ids=["one-token-48-slots", "chunk-512",
                              "chunk-1024"])
@pytest.mark.parametrize("kind", list(LATENT_LAYERS))
def test_latent_layer_cache_step_compiles_in_place(one_chip, kind, tokens):
    """`paged_mla_decode` compiles at the published widths (128 heads on
    a 640-wide row under a mask, 64 heads on a 1152-wide row over the
    window's 5 pages) and the head-less write lands in the donated pools
    where they lie: no copy of a pool's shape, decode or prefill chunk.
    (With the pools laid out [pages, 1, page, width] the engine's
    compiled prefill programs copied every pool at the write's `cond`:
    the size-1 head dimension gives the compiler two names for one
    layout. PERF.md section 6, PR 29.)"""
    import functools
    cfg = LATENT_LAYERS[kind]
    b = 48 if tokens == 1 else 1
    pools = [((LATENT_PAGES, 128, w), jnp.bfloat16) for w in cfg["rows"]]
    shapes = [((b, cfg["heads"], cfg["rows"][0]), jnp.bfloat16),
              [((b, tokens, w), jnp.bfloat16) for w in cfg["rows"]],
              ((b, LATENT_BT), jnp.int32), ((b,), jnp.int32),
              ((b, LATENT_BT * 128), jnp.int32)] + pools
    def struct(sd):
        return jax.ShapeDtypeStruct(sd[0], sd[1], sharding=one_chip)
    args = [[struct(x) for x in sd] if isinstance(sd, list) else struct(sd)
            for sd in shapes]
    compiled = jax.jit(
        functools.partial(_latent_write_then_attend, kind),
        donate_argnums=tuple(range(5, 5 + len(pools)))).lower(*args).compile()
    text = compiled.as_text()
    assert ("%paged_mla_decode" in text) == (tokens == 1)
    pool_copy = re.compile(
        rf"= \w+\[{LATENT_PAGES},(1,)?128,\d+\]\{{[^}}]*\}} copy\(")
    assert [line.strip()[:120] for line in text.splitlines()
            if pool_copy.search(line)] == []
    mem = compiled.memory_analysis()
    all_pools = sum(math.prod(s) * 2 for s, _ in pools)
    assert mem.alias_size_in_bytes >= all_pools


@pytest.mark.parametrize("tokens,keys,counter", [
    (2048, 2688, "band"), (256, 896, "whole")],
    ids=["chunk-2048-banded", "chunk-256-whole"])
def test_sliding_layer_chunk_scores_a_band_of_its_keys(one_chip, tokens,
                                                       keys, counter):
    """A sliding layer's prefill chunk at the published widths (64 heads,
    blocks of 256 queries, window 513, pages of 128): the chunk gathers
    `keys` rows, and a block's float32 scores span 896 of them, the band
    its window can keep, never the 2,688 a 2,048-token chunk gathers.
    (Scoring all of them made the sliding layers' loops 18.7% of that
    chunk's program: PERF.md section 6, PR 32.)"""
    from paddle_tpu import monitor
    from paddle_tpu.jit.functional import functional_call, get_params
    from paddle_tpu.nn.initializer.lazy_init import LazyGuard
    from paddle_tpu.nn.layer.layers import param_dtype
    from paddle_tpu.text.models.dots3_note import (SLIDING,
                                                   Dots3LatentAttention,
                                                   Dots3NoteConfig)
    with LazyGuard(), param_dtype("bfloat16"):
        attn = Dots3LatentAttention(Dots3NoteConfig(), SLIDING)

    def chunk_step(params, u, pos0, bt, pool):
        (out, cache), _ = functional_call(
            attn, params, {}, (u,),
            dict(kv_cache=(pool, bt), cache_index=pos0))
        return out, cache[0]

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = ({k: struct(v.shape, v.dtype)
             for k, v in get_params(attn).items()},
            struct((1, tokens, 5120), jnp.bfloat16), struct((1,), jnp.int32),
            struct((1, LATENT_BT), jnp.int32),
            struct((LATENT_PAGES, 128, 1152), jnp.bfloat16))
    count = monitor.counter(f"kernels.prefill.swa_{counter}")
    before = count.get()
    text = jax.jit(chunk_step, donate_argnums=(4,)).lower(
        *args).compile().as_text()
    assert count.get() == before + 1
    # the compiler drops the size-1 batch dimension of the score block
    scores = set(re.findall(r"f32\[(?:1,)?64,256,(\d+)\]", text))
    assert scores == {"896"}, scores
    assert f"bf16[1,{keys},64,256]" in text      # K, expanded once a chunk


@pytest.mark.parametrize("keys", [2048, 4096])
def test_full_layer_chunk_attends_in_one_flash_call(monkeypatch, one_chip,
                                                    keys):
    """A full layer's 2,048-token prefill chunk at the published widths
    (128 heads of 128 + 64, blocks of 256 queries, pages of 128) through
    a block table `keys` wide: every branch of the switch over key
    lengths (1,024 ... `keys`) holds ONE `mla_flash_prefill` call, and no
    float32 score block [128 heads, 256 queries, L] is left in the
    program. (XLA wrote, read twice and re-read that block for each of
    8 query blocks: 13% of the MXU's peak, 46% of the cell's mean chunk:
    PERF.md section 6, PR 39.)"""
    from paddle_tpu import monitor
    from paddle_tpu.core import place
    from paddle_tpu.jit.functional import functional_call, get_params
    from paddle_tpu.nn.initializer.lazy_init import LazyGuard
    from paddle_tpu.nn.layer.layers import param_dtype
    from paddle_tpu.text.models.dots3_note import (FULL,
                                                   Dots3LatentAttention,
                                                   Dots3NoteConfig)
    # the layer asks the platform which route to take: steer it here
    monkeypatch.setattr(place, "accelerator_available", lambda: True)
    with LazyGuard(), param_dtype("bfloat16"):
        attn = Dots3LatentAttention(Dots3NoteConfig(), FULL)

    def chunk_step(params, u, pos0, bt, pool, ki_pool):
        (out, cache), _ = functional_call(
            attn, params, {}, (u,),
            dict(kv_cache=(pool, ki_pool, bt), cache_index=pos0))
        return out, cache[0], cache[1]

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = ({k: struct(v.shape, v.dtype)
             for k, v in get_params(attn).items()},
            struct((1, 2048, 5120), jnp.bfloat16), struct((1,), jnp.int32),
            struct((1, keys // 128), jnp.int32),
            struct((LATENT_PAGES, 128, 640), jnp.bfloat16),
            struct((LATENT_PAGES, 128, 128), jnp.bfloat16))
    flash, xla = (monitor.counter(f"kernels.prefill.mla_{n}")
                  for n in ("flash", "xla"))
    n_flash, n_xla = flash.get(), xla.get()
    text = jax.jit(chunk_step, donate_argnums=(4, 5)).lower(
        *args).compile().as_text()
    branches = keys // 1024
    assert (flash.get(), xla.get()) == (n_flash + branches, n_xla)
    assert text.count("tpu_custom_call") == branches
    assert "mla_flash_prefill" in text
    assert re.findall(r"f32\[(?:1,)?128,256,\d+\]", text) == []
    # K and V, expanded once a branch, heads-major for the kernel
    assert f"bf16[1,128,{keys},192]" in text


# the linear-attention cell: 96 slots, 64 heads of 128; the GQA layer's
# pools 96 slots x 22 pages + the scratch page, 8 KV heads, page 128
STATE_SLOTS, KV_PAGES, KV_BT = 96, 2113, 22


def _solar_layer_step(monkeypatch, one_chip, gqa, tokens):
    """The compiled text and memory analysis of one Solar-Open2 mixing
    layer's cache step at the cell's sizes, built as the engine's
    programs build it: the decode program's one token a slot (`slots`
    None, row i is slot i) or a prefill chunk of one slot."""
    from paddle_tpu.core import place
    from paddle_tpu.jit.functional import functional_call, get_params
    from paddle_tpu.nn.initializer.lazy_init import LazyGuard
    from paddle_tpu.nn.layer.layers import param_dtype
    from paddle_tpu.text.models.solar_open2 import (SolarGQAttention,
                                                    SolarKDAttention,
                                                    SolarOpen2Config)
    # the model asks the platform which kernel to take: steer it here
    monkeypatch.setattr(place, "accelerator_available", lambda: True)
    with LazyGuard(), param_dtype("bfloat16"):
        attn = (SolarGQAttention if gqa else SolarKDAttention)(
            SolarOpen2Config())
    b = STATE_SLOTS if tokens == 1 else 1

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if gqa:
        pools = [struct((KV_PAGES, 8, 128, 128), jnp.bfloat16)] * 2
        rest = (struct((b, KV_BT), jnp.int32),)
    else:
        pools = [struct((STATE_SLOTS, 64, 128, 128), jnp.float32)] \
            + [struct((STATE_SLOTS, 24576), jnp.bfloat16)] * 3
        rest = (None if tokens == 1 else struct((b,), jnp.int32),
                struct((b,), jnp.int32))

    def step(params, u, pos0, rest, *pools):
        (out, cache), _ = functional_call(
            attn, params, {}, (u,),
            dict(kv_cache=tuple(pools) + tuple(rest), cache_index=pos0))
        return out, cache[:len(pools)]

    compiled = jax.jit(step, donate_argnums=tuple(
        range(4, 4 + len(pools)))).lower(
        {k: struct(v.shape, v.dtype) for k, v in get_params(attn).items()},
        struct((b, tokens, 4096), jnp.bfloat16), struct((b,), jnp.int32),
        rest, *pools).compile()
    return compiled.as_text(), compiled.memory_analysis()


@pytest.mark.parametrize("tokens", [1, 2048],
                         ids=["one-token-96-slots", "chunk-2048"])
def test_kda_layer_state_step_compiles_in_place(monkeypatch, one_chip,
                                                tokens):
    """`kda_decode` compiles at the published widths (64 heads of 128, 96
    slots) as a Mosaic call that takes the donated state where it lies,
    and a prefill chunk reads and writes one slot's rows of it: no copy
    of a layer's whole state array (0.4 GB) in either program."""
    text, mem = _solar_layer_step(monkeypatch, one_chip, False, tokens)
    assert ("%kda_decode" in text and "tpu_custom_call" in text) \
        == (tokens == 1)
    state_copy = re.compile(
        rf"= \w+\[{STATE_SLOTS},(64,128,128|24576)\]\{{[^}}]*\}} copy\(")
    assert [line.strip()[:120] for line in text.splitlines()
            if state_copy.search(line)] == []
    assert mem.alias_size_in_bytes >= STATE_SLOTS * (64 * 128 * 128 * 4
                                                     + 3 * 24576 * 2)


@pytest.mark.parametrize("tokens", [1, 2048],
                         ids=["one-token-96-slots", "chunk-2048"])
def test_gqa_layer_cache_step_compiles_in_place(monkeypatch, one_chip,
                                                tokens):
    """The gated NoPE GQA layer on the paged pools with heads (8 x 128,
    a group of 8): `paged_decode` on a one-token step, gathered pages in
    blocks of 256 queries on a chunk, the write in place either way."""
    text, mem = _solar_layer_step(monkeypatch, one_chip, True, tokens)
    assert ("%paged_decode" in text) == (tokens == 1)
    pool_copy = re.compile(
        rf"= \w+\[{KV_PAGES},(8,128|1024),128\]\{{[^}}]*\}} copy\(")
    assert [line.strip()[:120] for line in text.splitlines()
            if pool_copy.search(line)] == []
    assert mem.alias_size_in_bytes >= 2 * KV_PAGES * 8 * 128 * 128 * 2
    if tokens > 1:
        # a block's float32 scores: 256 queries x the 22 pages' keys
        assert re.search(r"f32\[(1,)?8,8,256,2816\]", text)


# the window cell: 128 slots, each sliding layer a ring of 128 keys and
# values a slot (8 KV heads of 128): one page of the paged layout
RING_SLOTS, RING = 128, (128, 8, 128, 128)


@pytest.mark.parametrize("tokens", [1, 2048],
                         ids=["one-token-128-slots", "chunk-2048"])
def test_ring_layer_step_compiles_in_place(monkeypatch, one_chip, tokens):
    """A K-EXAONE sliding layer on its per-slot rings at the published
    widths (64 query / 8 KV heads of 128, window 128): a one-token step
    is `paged_decode` on the rings as a pool of one page a slot, 128
    lanes at a group of 8; a chunk scores blocks of 256 queries against
    the band of 384 keys their windows reach, never the chunk's 2,176;
    either way the donated rings are written where they lie."""
    from paddle_tpu import monitor
    from paddle_tpu.core import place
    from paddle_tpu.jit.functional import functional_call, get_params
    from paddle_tpu.nn.initializer.lazy_init import LazyGuard
    from paddle_tpu.nn.layer.layers import param_dtype
    from paddle_tpu.text.models.k_exaone import (SLIDING, KExaoneAttention,
                                                 KExaoneConfig)
    monkeypatch.setattr(place, "accelerator_available", lambda: True)
    with LazyGuard(), param_dtype("bfloat16"):
        attn = KExaoneAttention(KExaoneConfig(), SLIDING)
    b = RING_SLOTS if tokens == 1 else 1

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(params, u, pos0, slots, n_valid, kr, vr):
        (out, cache), _ = functional_call(
            attn, params, {}, (u,),
            dict(kv_cache=(kr, vr, slots, n_valid), cache_index=pos0))
        return out, cache

    band = monitor.counter("kernels.prefill.gqa_band")
    before = band.get()
    compiled = jax.jit(step, donate_argnums=(5, 6)).lower(
        {k: struct(v.shape, v.dtype) for k, v in get_params(attn).items()},
        struct((b, tokens, 6144), jnp.bfloat16), struct((b,), jnp.int32),
        None if tokens == 1 else struct((b,), jnp.int32),
        struct((b,), jnp.int32), struct(RING, jnp.bfloat16),
        struct(RING, jnp.bfloat16)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert ("%paged_decode" in text) == (tokens == 1)
    ring_copy = re.compile(
        rf"= \w+\[{RING_SLOTS},(8,128|1024),128\]\{{[^}}]*\}} copy\(")
    assert [line.strip()[:120] for line in text.splitlines()
            if ring_copy.search(line)] == []
    assert mem.alias_size_in_bytes >= 2 * math.prod(RING) * 2
    assert band.get() == before + (tokens > 1)
    if tokens > 1:
        scores = set(re.findall(r"f32\[(?:1,)?8,8,256,(\d+)\]", text))
        assert scores == {"384"}, scores


# the state-space cell: 96 slots, every block a mixer state [32, 128, 256]
# float32 and a tail of 3 x 5120 a slot BESIDE paged pools of 4 KV heads
# (96 slots x 21 pages + the scratch page, page 128)
SSM_SLOTS, SSM_PAGES, SSM_BT = 96, 2017, 21


def _falcon_step(monkeypatch, one_chip, build, tokens, blocks=1):
    """The compiled text and memory analysis of a Falcon-H1 layer's (or,
    `blocks` > 1, the whole decoder's) cache step at the cell's sizes,
    built as the engine's programs build it: the decode program's one
    token a slot (`slots` None, row i is slot i) or a prefill chunk of
    one slot. `build(config)` gives the layer and which caches it takes
    ("kv", "state" or both)."""
    from paddle_tpu.core import place
    from paddle_tpu.jit.functional import functional_call, get_params
    from paddle_tpu.nn.initializer.lazy_init import LazyGuard
    from paddle_tpu.text.models.falcon_h1 import FalconH1Config
    monkeypatch.setattr(place, "accelerator_available", lambda: True)
    # the FFN and the vocabulary are cut here (no kernel, no cache of
    # theirs is looked at); every width of the two mixers is published
    config = FalconH1Config(num_hidden_layers=blocks, intermediate_size=256,
                            vocab_size=512, dtype="bfloat16")
    with LazyGuard():
        layer, kinds = build(config)
    b = SSM_SLOTS if tokens == 1 else 1

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    caches = {
        "kv": ([struct((SSM_PAGES, 4, 128, 128), jnp.bfloat16)] * 2,
               (struct((b, SSM_BT), jnp.int32),)),
        "state": ([struct((SSM_SLOTS, 32, 128, 256), jnp.float32)]
                  + [struct((SSM_SLOTS, 5120), jnp.bfloat16)] * 3,
                  (None if tokens == 1 else struct((b,), jnp.int32),
                   struct((b,), jnp.int32)))}
    pools = [caches[k][0] for k in kinds] * blocks
    rests = [caches[k][1] for k in kinds] * blocks
    whole = blocks > 1

    def step(params, u, pos0, rests, pools):
        full = [tuple(p) + tuple(r) for p, r in zip(pools, rests)]
        kw = dict(kv_caches=full) if whole else dict(kv_cache=full[0])
        (out, cache), _ = functional_call(layer, params, {}, (u,),
                                          dict(kw, cache_index=pos0))
        cache = cache if whole else [cache]
        return out, [c[:len(p)] for c, p in zip(cache, pools)]

    u = struct((b, tokens), jnp.int32) if whole else \
        struct((b, tokens, 5120), jnp.bfloat16)
    compiled = jax.jit(step, donate_argnums=(4,)).lower(
        {k: struct(v.shape, v.dtype) for k, v in get_params(layer).items()},
        u, struct((b,), jnp.int32), rests, pools).compile()
    return compiled.as_text(), compiled.memory_analysis()


def _state_passes(text):
    """The instructions of a compiled program that produce a block's
    whole state array (a fusion or a copy; the TPU compiler leaves no
    other array op outside a fusion): each is one pass over 0.4 GB."""
    made = re.compile(rf"^\s*(?:ROOT )?%[\w.\-]+ = \(?[^=]*?"
                      rf"f32\[{SSM_SLOTS},32,128,256\]\{{[^}}]*\}}[^=]*? "
                      rf"(fusion|copy)\(", re.M)
    return [m.group(1) for m in made.finditer(text)]


@pytest.mark.parametrize("tokens", [1, 1024],
                         ids=["one-token-96-slots", "chunk-1024"])
def test_ssm_mixer_state_step_compiles_in_place(monkeypatch, one_chip,
                                                tokens):
    """The mixer's cache step at the published widths (32 heads of 128
    on a state of 256 in 2 groups, 96 slots). One token a slot:
    `ssd_step_arrays` becomes ONE fusion that reads the donated state
    and writes it where it lies, `S'` and y out of the same pass (what a
    kernel with `S` aliased would give; no Pallas call is there). A
    prefill chunk reads and writes one slot's rows. No copy of a block's
    whole state array (0.4 GB) in either program."""
    from paddle_tpu.text.models.falcon_h1 import FalconH1Mixer
    text, mem = _falcon_step(monkeypatch, one_chip,
                             lambda c: (FalconH1Mixer(c), ["state"]), tokens)
    assert "tpu_custom_call" not in text
    if tokens == 1:
        assert _state_passes(text) == ["fusion"]
    state_copy = re.compile(
        rf"= \w+\[{SSM_SLOTS},(32,128,256|5120)\]\{{[^}}]*\}} copy\(")
    assert [line.strip()[:120] for line in text.splitlines()
            if state_copy.search(line)] == []
    assert mem.alias_size_in_bytes >= SSM_SLOTS * (32 * 128 * 256 * 4
                                                   + 3 * 5120 * 2)


def test_paged_decode_lowers_at_a_group_of_five(monkeypatch, one_chip):
    """Falcon-H1's attention on the paged pools at the published 20 query
    / 4 KV heads of 128: `paged_decode` with an ODD number of query rows
    a KV head, the write in place."""
    from paddle_tpu.text.models.falcon_h1 import FalconH1Attention
    text, mem = _falcon_step(monkeypatch, one_chip,
                             lambda c: (FalconH1Attention(c), ["kv"]), 1)
    assert "%paged_decode" in text and "tpu_custom_call" in text
    assert mem.alias_size_in_bytes >= 2 * SSM_PAGES * 4 * 128 * 128 * 2


def test_ssm_decode_program_updates_every_block_in_one_pass(monkeypatch,
                                                            one_chip):
    """The six-block decoder's one-token step: six `paged_decode` calls
    and six passes over a state array, one a block, in ONE program, every
    block's pools and state taken where they lie."""
    from paddle_tpu.text.models.falcon_h1 import FalconH1ForCausalLM
    text, mem = _falcon_step(
        monkeypatch, one_chip,
        lambda c: (FalconH1ForCausalLM(c), ["kv", "state"]), 1, blocks=6)
    calls = re.findall(r"^\s*%(\w+?)[.\d]* = .*custom-call\(.*"
                       r"tpu_custom_call", text, re.M)
    assert calls == ["paged_decode"] * 6
    assert _state_passes(text) == ["fusion"] * 6
    assert mem.alias_size_in_bytes >= 6 * (
        SSM_SLOTS * (32 * 128 * 256 * 4 + 3 * 5120 * 2)
        + 2 * SSM_PAGES * 4 * 128 * 128 * 2)


# the full-context latent cell (benchmark/configs/deepseek-v2-serve-5l.json):
# 64 slots x 104 pages + the scratch page, page 128, one 640-wide row a
# token a layer
DSV2_SLOTS, DSV2_PAGES, DSV2_BT = 64, 6657, 104


def _dsv2_step(monkeypatch, one_chip, build, b, tokens):
    """The compiled text and memory analysis of DeepSeek-V2's cache step
    at the cell's sizes: `build(config)` gives a latent layer or the
    whole decoder (every layer's pool then), the decode program's one
    token a slot or a prefill chunk of one slot."""
    from paddle_tpu.core import place
    from paddle_tpu.jit.functional import functional_call, get_params
    from paddle_tpu.nn.initializer.lazy_init import LazyGuard
    from paddle_tpu.text.models import DeepseekV2Config
    monkeypatch.setattr(place, "accelerator_available", lambda: True)
    # the dense FFN and the vocabulary are cut here (no kernel, no cache
    # of theirs is looked at); every width of attention and experts is
    # published, and 20 of 160 experts held
    config = DeepseekV2Config(num_hidden_layers=5, intermediate_size=256,
                              vocab_size=512, expert_share=(0, 8),
                              dtype="bfloat16")
    with LazyGuard():
        layer = build(config)
    whole = not hasattr(layer, "cache_rows")

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pools = [struct((DSV2_PAGES, 128, 640), jnp.bfloat16)] * (
        5 if whole else 1)

    def step(params, u, pos0, bt, pools):
        caches = [(p, bt) for p in pools]
        kw = dict(kv_caches=caches) if whole else dict(kv_cache=caches[0])
        (out, cache), _ = functional_call(layer, params, {}, (u,),
                                          dict(kw, cache_index=pos0))
        return out, [c[0] for c in (cache if whole else [cache])]

    u = struct((b, tokens), jnp.int32) if whole else \
        struct((b, tokens, 5120), jnp.bfloat16)
    compiled = jax.jit(step, donate_argnums=(4,)).lower(
        {k: struct(v.shape, v.dtype) for k, v in get_params(layer).items()},
        u, struct((b,), jnp.int32), struct((b, DSV2_BT), jnp.int32),
        pools).compile()
    return compiled.as_text(), compiled.memory_analysis()


def test_full_context_decode_program_reads_every_row(monkeypatch, one_chip):
    """The five-layer decoder's one-token step at 64 slots of 104 pages:
    five `paged_mla_decode` calls with no keep-mask (no [64, 1, 13312]
    operand: every row of the context is read), the expert layers on
    `moe_gmm`, every layer's pool written where it lies."""
    from paddle_tpu.text.models import DeepseekV2ForCausalLM
    text, mem = _dsv2_step(monkeypatch, one_chip, DeepseekV2ForCausalLM,
                           DSV2_SLOTS, 1)
    calls = re.findall(r"^\s*%(\w+?)[.\d]* = .*custom-call\(.*"
                       r"tpu_custom_call", text, re.M)
    assert calls.count("paged_mla_decode") == 5
    assert set(calls) == {"paged_mla_decode", "moe_gmm"}
    assert f"s32[{DSV2_SLOTS},1,{DSV2_BT * 128}]" not in text
    assert mem.alias_size_in_bytes >= 5 * DSV2_PAGES * 128 * 640 * 2


def test_full_context_chunk_attends_in_one_flash_call(monkeypatch,
                                                      one_chip):
    """A DeepSeek-V2 latent layer's 2,048-token prefill chunk through a
    block table of 104 pages: every branch of the switch over key lengths
    (1,024 ... 13,312) holds ONE `mla_flash_prefill` call under a plain
    causal mask, no float32 score block [128 heads, 256 queries, L] is
    left in the program, and K is expanded once a branch, heads-major."""
    from paddle_tpu import monitor
    from paddle_tpu.text.models.deepseek_v2 import latent_attention
    flash, xla = (monitor.counter(f"kernels.prefill.mla_{n}")
                  for n in ("flash", "xla"))
    n_flash, n_xla = flash.get(), xla.get()
    text, _ = _dsv2_step(monkeypatch, one_chip, latent_attention, 1, 2048)
    assert (flash.get(), xla.get()) == (n_flash + 13, n_xla)
    assert text.count("tpu_custom_call") == 13
    assert re.findall(r"f32\[(?:1,)?128,256,\d+\]", text) == []
    assert "bf16[1,128,13312,192]" in text


# an expert layer of each expert cell at the cell's widths: hidden, expert
# width, experts routed over, tokens (a prefill bucket, or the decode
# program's lanes); each holds share 0 of 8 and routes top-8
EXPERT_LAYERS = {"notes48-chunk-2048": (5120, 1536, 256, 2048),
                 "chat96-prefill-1024": (4096, 1280, 320, 1024),
                 "chat96-decode-96-lanes": (4096, 1280, 320, 96),
                 "mixed128-chunk-1536": (6144, 2048, 128, 1536),
                 "mixed128-decode-128-lanes": (6144, 2048, 128, 128)}


def _pallas_calls(jaxpr):
    from jax._src import core
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("h,f,experts,tokens", EXPERT_LAYERS.values(),
                         ids=EXPERT_LAYERS.keys())
def test_sorted_dispatch_works_on_slabs_of_the_held_rows(monkeypatch,
                                                         one_chip, h, f,
                                                         experts, tokens):
    """`MoELayer._forward_sorted` on one chip's share of the experts: a
    slab of 2/8 of the N x 8 picks in an odd number of row tiles (128
    rows; 32 for the decode program) goes through two `moe_gmm` kernel
    calls (gate and up in one, then down) and no `ragged-dot`; a call's
    row block is [row tile, K] and its weight block [K, column tile]
    with K WHOLE, so a group's row tiles share one fetch of its matrix
    (XLA's ragged-dot tiles K by 512 and fetched it once a tile: 1.5-2.2
    times the bytes, PERF.md section 6, PR 37); and no array has N x 8
    rows by the hidden or the expert width (sized for every pick, and
    tiled by 512 rows, the 12 calls of a prefill program were 20.6% and
    36% of it: PERF.md section 6, PR 35)."""
    from paddle_tpu import monitor
    from paddle_tpu.core import place
    from paddle_tpu.core.dispatch import unwrap
    from paddle_tpu.incubate.distributed.models.moe import (MoELayer,
                                                            moe_layer)
    from paddle_tpu.jit.functional import (functional_call, get_buffers,
                                           get_params)
    from paddle_tpu.nn.initializer.lazy_init import LazyGuard
    from paddle_tpu.nn.layer.layers import param_dtype
    with LazyGuard(), param_dtype("bfloat16"):
        layer = MoELayer(h, f, experts, gate="sigmoid_topk", top_k=8,
                         activation="swiglu", expert_share=(0, 8))
    layer.eval()
    monkeypatch.setattr(place, "accelerator_available", lambda: True)

    def step(params, buffers, x):
        out, _ = functional_call(layer, params, buffers, (x,), {})
        return out, unwrap(layer.last_stats)

    def struct(tree):
        return {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
                for k, v in tree.items()}
    counts = [monitor.counter(name) for name in (
        "kernels.moe.sorted.slab", "kernels.moe.gmm_pallas",
        "kernels.moe.gmm_fallback")]
    before = [c.get() for c in counts]
    traced = jax.jit(step).trace(
        struct(get_params(layer)), struct(get_buffers(layer)),
        jax.ShapeDtypeStruct((tokens, h), jnp.bfloat16, sharding=one_chip))
    text = traced.lower().compile().as_text()
    assert [c.get() - b for c, b in zip(counts, before)] == [1, 1, 0]
    picks = tokens * 8
    slab = moe_layer._slab_rows(picks, 8)
    tile = moe.gmm_row_tile(slab)
    assert tile == (128 if tokens > 128 else 32)
    assert 0 <= slab - picks // 4 <= 2 * tile and slab % (2 * tile) == tile
    assert "ragged-dot" not in text
    calls = re.findall(r"= bf16\[(\d+),(\d+)\]\S* custom-call\([^\n]*"
                       r"tpu_custom_call[^\n]*moe_gmm", text)
    assert calls == [(str(slab), str(f)), (str(slab), str(h))], calls
    blocks = [[tuple(getattr(d, "block_size", None) for d in bm.block_shape)
               for bm in eqn.params["grid_mapping"].block_mappings]
              for eqn in _pallas_calls(traced.jaxpr.jaxpr)]
    tn_up = moe.gmm_tiles(slab, h, f, jnp.bfloat16, 2)[1]
    assert blocks == [
        [(tile, h), (None, h, tn_up), (None, h, tn_up), (tile, tn_up)],
        [(tile, f), (None, f, h), (tile, h)]], blocks
    wide = re.findall(
        rf"\w+\[(?:{picks}|{tokens},8),(?:{h}|{f})\]", text)
    assert wide == []


def _moe_shapes(e=8, cap=8192, h=768, dff=3072):
    return [((e, cap, h), jnp.bfloat16), ((e, h, dff), jnp.bfloat16),
            ((e, 1, dff), jnp.float32), ((e, dff, h), jnp.bfloat16),
            ((e, 1, h), jnp.float32), ((e, cap, 1), jnp.float32),
            ((e,), jnp.int32)]


def _moe_fwd(*args):
    return moe.grouped_ffn(*args, force_pallas=True)


def _moe_grad(*args):
    return jax.grad(lambda *a: _moe_fwd(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2, 3, 4, 5))(*args)


# 8 experts x capacity 8192, 768 -> 3072 bf16 (the ERNIE-MoE cell's
# grouped FFN); the grad pulls the dx/dwslot/db2 and dw1/db1/dw2 kernels
@pytest.mark.parametrize("fn,n_kernels", [(_moe_fwd, 1), (_moe_grad, 2)],
                         ids=["fwd", "grad"])
def test_moe_grouped_ffn_compiles(one_chip, fn, n_kernels):
    text = _compiled_text(fn, one_chip, *_moe_shapes())
    assert text.count("tpu_custom_call") == n_kernels
