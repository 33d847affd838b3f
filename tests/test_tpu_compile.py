"""The Pallas kernels compile for a TPU v5e at the widths they serve.

Interpret-mode tests (test_kernels.py) check what a kernel computes; they
cannot see what the TPU compiler refuses (unaligned blocks, primitives
Mosaic does not lower).  These tests compile each kernel for one chip of a
described v5e:2x2 topology -- no chip attached, nothing runs -- at
internlm2-1.8b's widths (16 heads, 8 KV heads, head_dim 128, d_model
2048, vocab 92,544), and check that the program holds the kernel
(``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_decode import (flash_decode_paged_pallas,
                                        flash_decode_pallas)
from repro.kernels.layernorm import norm_pallas
from repro.kernels.sampling import sample_pallas
from repro.kernels.softmax import softmax_pallas

H, KV, DH, D_MODEL, VOCAB = 16, 8, 128, 2048, 92_544
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", [F32, BF16])
def test_sampling_kernel_compiles(one_chip, dtype):
    text = compiled_text(sample_pallas, one_chip, ((8, VOCAB), dtype),
                         ((8,), F32), ((8,), I32), ((8,), F32),
                         ((8, 64), F32))
    assert "tpu_custom_call" in text


def test_flash_decode_paged_compiles(one_chip):
    pool = ((1025, 16, KV, DH), BF16)           # 16-token blocks
    text = compiled_text(flash_decode_paged_pallas, one_chip,
                         ((8, H, DH), BF16), pool, pool, ((8, 64), I32),
                         ((8,), I32))
    assert "tpu_custom_call" in text


def test_flash_decode_compiles(one_chip):
    cache = ((8, KV, 2048, DH), BF16)
    text = compiled_text(flash_decode_pallas, one_chip, ((8, H, DH), BF16),
                         cache, cache, ((8,), I32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    kv = ((1, KV, 1024, DH), BF16)
    text = compiled_text(flash_attention_pallas, one_chip,
                         ((1, H, 1024, DH), BF16), kv, kv, ((1,), I32))
    assert "tpu_custom_call" in text


def test_softmax_compiles(one_chip):
    # one prompt's attention scores: every head's rows of a 1024 context
    text = compiled_text(softmax_pallas, one_chip, ((H * 1024, 1024), F32),
                         ((H * 1024,), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rms", [True, False])
def test_norm_compiles(one_chip, rms):
    fn = functools.partial(norm_pallas, rms=rms)
    vec = ((D_MODEL,), BF16)
    shapes = [((1024, D_MODEL), BF16), vec] + ([] if rms else [vec])
    text = compiled_text(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [128, 4096])         # a decode tick, a pack
@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)])
def test_grouped_matmul_compiles(one_chip, m, k, n):
    """OLMoE's experts (d_model 2048, width 1024), 8 layers of 64 read in
    place: the kernel, with no copy of the stack beside it."""
    fn = functools.partial(ops.grouped_matmul, impl="pallas")
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
            (((m, k), BF16), ((8 * 64, k, n), BF16), ((8 * 64,), I32))]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * k * n * 2
