"""Compile every Pallas kernel for a described TPU v5e chip at qwen3-1.7b
widths (head_dim 128, 16 query heads over 8 KV heads, T=2048; decode
B=8 against S=4096). Interpret mode accepts block shapes that Mosaic
refuses; this catches them here, with no chip attached.

The topology is described inside a module-scoped fixture, never while the
module is imported: only one process may load the TPU library at a time,
and pytest-xdist workers each import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rms

B, T, HQ, HKV, D = 2, 2048, 16, 8, 128
DEC_B, DEC_S = 8, 4096
D_MODEL = 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_flash_forward_compiles(one_chip):
    qkv = ((B, HQ, T, D), jnp.bfloat16)
    _compile(lambda q, k, v: fa.flash_attention_fwd(q, k, v), one_chip,
             qkv, qkv, qkv)


def test_flash_backward_compiles(one_chip):
    qkv = ((B, HQ, T, D), jnp.bfloat16)
    lse = ((B, HQ, T, 1), jnp.float32)
    _compile(lambda q, k, v, o, l, do: fa.flash_attention_bwd(q, k, v, o, l, do),
             one_chip, qkv, qkv, qkv, qkv, lse, qkv)


def test_decode_compiles(one_chip):
    cache = ((DEC_B, DEC_S, HQ, D), jnp.bfloat16)
    _compile(lambda q, k, v, n: dec.decode_attention_splits(q, k, v, n),
             one_chip, ((DEC_B, HQ, D), jnp.bfloat16), cache, cache,
             ((DEC_B,), jnp.int32))


def test_rmsnorm_compiles(one_chip):
    _compile(lambda x, s: rms.rmsnorm(x, s), one_chip,
             ((DEC_B, 512, D_MODEL), jnp.bfloat16), ((D_MODEL,), jnp.float32))
