"""Compile the served path's Pallas kernels for a TPU v5e that is described,
not attached, at Qwen2-1.5B widths (H=12, K=2, head_dim=128).

The TPU compiler refuses what interpret mode accepts: tiles not aligned to
the chip's layout, kernels that ask for more VMEM than the chip has. These
compiles run nothing; they guard the kernels' lowering on every change.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
each import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.decode_attn import (flash_decode_attention,
                                       flash_verify_attention)
from repro.kernels.flash_prefill import flash_prefill_attention
from repro.models import model as M
from repro.models.segments import SegmentedPrefill
from test_segments import cache_aliased, copied_shapes

CFG = get_config("qwen2_1_5b")
H, K, HD = CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("sq", [512, 20])   # a full chunk; a ragged tail
def test_prefill_kernel_compiles(one_chip, dtype, sq):
    T = 4096
    q = _spec((1, sq, H, HD), dtype, one_chip)
    kv = _spec((1, T, K, HD), dtype, one_chip)
    off = _spec((), jnp.int32, one_chip)
    compiled = jax.jit(flash_prefill_attention).lower(
        q, kv, kv, off, off).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("dtype", DTYPES)
def test_decode_kernel_compiles(one_chip, dtype):
    B, T = 4, 2048
    q = _spec((B, H, HD), dtype, one_chip)
    kv = _spec((B, T, K, HD), dtype, one_chip)
    lens = _spec((B,), jnp.int32, one_chip)
    compiled = jax.jit(flash_decode_attention).lower(q, kv, kv, lens).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("dtype", DTYPES)
def test_verify_kernel_compiles(one_chip, dtype):
    B, S, T = 4, 5, 2048
    q = _spec((B, S, H, HD), dtype, one_chip)
    kv = _spec((B, T, K, HD), dtype, one_chip)
    lens = _spec((B,), jnp.int32, one_chip)
    compiled = jax.jit(flash_verify_attention).lower(q, kv, kv, lens).compile()
    _assert_kernel(compiled)


def test_prefill_attn_segment_compiles(one_chip):
    """The whole `attn` operator segment of the served SegmentedPrefill
    (RoPE, KV-cache write, Pallas kernel) for one 512-token chunk of a
    4096-token prompt, fitted to one chip's memory."""
    max_seq, chunk, B = 4096, 512, 1
    ex = SegmentedPrefill(None, CFG, max_seq=max_seq, granularity="op",
                          chunk_tokens=chunk, attn_impl="pallas")
    f32 = jnp.float32
    layers = jax.tree.map(lambda s: _spec(s, f32, one_chip),
                          M.model_shapes(CFG)["layers"],
                          is_leaf=lambda x: isinstance(x, tuple))
    cache = _spec((CFG.num_layers, B, max_seq, K, HD), f32, one_chip)
    caches = {
        "k_cache": cache,
        "v_cache": cache,
        "h_full": _spec((B, max_seq, CFG.d_model), f32, one_chip),
    }
    act = {
        "h": _spec((B, chunk, CFG.d_model), f32, one_chip),
        "tmp": (_spec((B, chunk, H, HD), f32, one_chip),
                _spec((B, chunk, K, HD), f32, one_chip),
                _spec((B, chunk, K, HD), f32, one_chip)),
    }
    scalar = _spec((), jnp.int32, one_chip)
    attn = ex._segments[ex.ops.index("attn")]
    compiled = attn.lower(layers, caches, act, scalar, scalar).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    # the cache is written in place: donated, aliased, never copied
    assert cache_aliased(compiled)
    assert not {cache.shape, caches["h_full"].shape} & copied_shapes(compiled)
