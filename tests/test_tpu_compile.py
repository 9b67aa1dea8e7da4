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
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.kernels import moe
from paddle_tpu.kernels.flash_attention import flash_attention_arrays
from paddle_tpu.kernels.paged_attention import (paged_decode_pallas,
                                                paged_pallas_requirements)


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
