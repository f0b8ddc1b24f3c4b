"""The main path's Pallas kernels compile for a TPU v5e at Llama-3-8B
widths (32/8 heads of 128).

Each test compiles ahead of time for a described (not attached) v5e
chip, which runs the TPU compiler's own checks — block tiling, VMEM,
layouts — that interpret mode never sees, and asserts the kernel is in
the compiled program as a ``tpu_custom_call``. Nothing runs.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.chunk_attention.ops import chunk_attention
from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention)
from repro.kernels.rope.ops import rope

H, HKV, D = 32, 8, 128
i32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache off
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_chunk_attention_compiles(one_chip):
    A, S = 512, 2048
    text = _compiled_text(
        lambda *a: chunk_attention(*a, num_chunks=16, interpret=False),
        one_chip, ((A, H, D), jnp.float32), ((S, HKV, D), jnp.float32),
        ((S, HKV, D), jnp.float32), ((A,), i32), ((S,), i32), ((S,), i32))
    assert "tpu_custom_call" in text


def test_decode_attention_compiles(one_chip):
    B, S = 8, 2048
    text = _compiled_text(
        lambda *a: decode_attention(*a, interpret=False),
        one_chip, ((B, H, D), jnp.float32), ((B, S, HKV, D), jnp.float32),
        ((B, S, HKV, D), jnp.float32), ((B,), i32), ((B, S), i32))
    assert "tpu_custom_call" in text


def test_paged_decode_attention_compiles(one_chip):
    B, NB, BS, NBMAX = 8, 1024, 16, 128
    text = _compiled_text(
        lambda *a: paged_decode_attention(*a, interpret=False),
        one_chip, ((B, H, D), jnp.float32),
        ((NB, BS, HKV, D), jnp.float32), ((NB, BS, HKV, D), jnp.float32),
        ((NB, BS), i32), ((B, NBMAX), i32), ((B,), i32))
    assert "tpu_custom_call" in text


def test_rope_compiles(one_chip):
    T = 2048
    text = _compiled_text(
        lambda x, p: rope(x, p, theta=500_000.0, interpret=False),
        one_chip, ((T, HKV, D), jnp.float32), ((T,), i32))
    assert "tpu_custom_call" in text
