"""Compile the main-path Pallas kernels for a TPU v5e at llama-350m widths.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
a described (not attached) ``v5e:2x2`` topology, which refuses what the
chip would refuse — block shapes off the (8, 128) tiling, too much VMEM
— at no chip time.  The widths are llama-350m's (d=1024, 16 heads,
hd=64, d_ff 2736, vocab 32000; 8 slots, max-seq 1024, 16-row pages).

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every test
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as da
from repro.kernels import masked_adam as ma
from repro.kernels import scatter_apply as sa

B, H, KV, HD, C, PS = 8, 16, 16, 64, 1024, 16
D, D_FF, VOCAB, LAYERS = 1024, 2736, 32000, 24


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_hlo(fn, sharding, *shapes, **kw):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(lambda *a: fn(*a, **kw)).lower(*args).compile().as_text()


@pytest.mark.parametrize("heads,kv_heads,head_dim", [
    (H, KV, HD),        # llama-350m, MHA
    (8, 1, 256),        # GQA with one kv head (gemma-2b shape)
])
def test_decode_attention_compiles(one_chip, no_persistent_cache, heads,
                                   kv_heads, head_dim):
    cache = ((B, C, kv_heads, head_dim), jnp.bfloat16)
    hlo = _compile_hlo(da.decode_attention_fwd, one_chip,
                       ((B, 1, heads, head_dim), jnp.float32), cache, cache,
                       ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("heads,kv_heads,head_dim", [
    (H, KV, HD),
    (8, 1, 256),
])
def test_paged_decode_attention_compiles(one_chip, no_persistent_cache,
                                         heads, kv_heads, head_dim):
    np_ = C // PS
    pool = ((B * np_ + 1, PS, kv_heads, head_dim), jnp.bfloat16)
    row = ((B, kv_heads, head_dim), jnp.float32)
    hlo = _compile_hlo(da.paged_decode_attention_fwd, one_chip,
                       ((B, 1, heads, head_dim), jnp.float32), row, row,
                       pool, pool, ((B,), jnp.int32), ((B, np_), jnp.int32),
                       ((B,), jnp.bool_))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("mask_dtype", [jnp.bool_, jnp.float32])
def test_masked_adam_compiles(one_chip, no_persistent_cache, mask_dtype):
    t = ((LAYERS * D, D_FF), jnp.float32)      # stacked MLP leaf, 2-D view
    hlo = _compile_hlo(ma.masked_adam_2d, one_chip, t, t, t, t,
                       ((LAYERS * D, D_FF), mask_dtype),
                       ((ma.N_SCALARS,), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_masked_adam_q8_compiles(one_chip, no_persistent_cache):
    nb = LAYERS * D * D_FF // 256
    f, q, s = (((nb, 256), jnp.float32), ((nb, 256), jnp.int8),
               ((nb, 1), jnp.float32))
    hlo = _compile_hlo(ma.masked_adam_q8_2d, one_chip, f, f, q, s, q, s,
                       ((nb, 256), jnp.bool_),
                       ((ma.N_SCALARS,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rows,width,k", [
    (VOCAB, D, 64),             # embedding
    (LAYERS, D * D_FF, 3),      # stacked MLP leaf, 2-D view
    (LAYERS, D, 5),             # stacked norm scales
])
def test_scatter_swap_compiles(one_chip, no_persistent_cache, rows, width,
                               k):
    hlo = _compile_hlo(sa.scatter_swap_2d, one_chip,
                       ((rows, width), jnp.float32), ((k,), jnp.int32),
                       ((k, width), jnp.float32))
    assert "tpu_custom_call" in hlo
