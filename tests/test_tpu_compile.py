"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e.

The TPU compiler ships with jaxlib's TPU support and compiles for a chip
that is described, not attached, so these tests check here what interpret
mode cannot: Mosaic's block-shape and memory rules at the widths the chip
serves. Widths are llama-paper-1b's (32 MHA heads of 64, bf16) and a GQA
group of 4 (llama3.2-1b's 32 q / 8 kv heads), on the serving shapes of
chip_smoke.py: 8 slots, 16-token pages, 1024-token slots, 256-token
prefill chunks.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the worker that runs this file
keeps it until it exits. Where it cannot be described, every test here
skips.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B, PAGE, MAX_LEN, CHUNK, POOL_PAGES = 8, 16, 1024, 256, 512
M = MAX_LEN // PAGE                      # logical pages per slot
HD = 64
WIDTHS = {"mha32": (32, 32), "gqa4": (32, 8)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # noqa: BLE001 - any failure skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the kernel is in
    return compiled


def _pool(hkv):
    return (hkv, POOL_PAGES + 1, PAGE, HD), jnp.bfloat16


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_decode_attention(one_chip, width):
    hq, hkv = WIDTHS[width]
    _compile(lambda q, k, v, qp, kp: ops.decode_attention(
        q, k, v, qp, kp, impl="pallas"), one_chip,
        ((B, hq, HD), jnp.bfloat16), ((B, hkv, MAX_LEN, HD), jnp.bfloat16),
        ((B, hkv, MAX_LEN, HD), jnp.bfloat16), ((B,), jnp.int32),
        ((B, MAX_LEN), jnp.int32))


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_paged_decode_attention(one_chip, width):
    hq, hkv = WIDTHS[width]
    _compile(lambda q, k, v, t, qp, kp: ops.paged_decode_attention(
        q, k, v, t, qp, kp, impl="pallas"), one_chip,
        ((B, hq, HD), jnp.bfloat16), _pool(hkv), _pool(hkv),
        ((B, M), jnp.int32), ((B,), jnp.int32), ((B, MAX_LEN), jnp.int32))


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_chunked_prefill_attention(one_chip, width):
    hq, hkv = WIDTHS[width]
    _compile(lambda q, k, v, t, qp, kp: ops.chunked_prefill_attention(
        q, k, v, t, qp, kp, impl="pallas"), one_chip,
        ((1, hq, CHUNK, HD), jnp.bfloat16), _pool(hkv), _pool(hkv),
        ((1, M), jnp.int32), ((1, CHUNK), jnp.int32),
        ((1, MAX_LEN), jnp.int32))


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_flash_attention(one_chip, width):
    hq, hkv = WIDTHS[width]
    _compile(lambda q, k, v, qp, kp: ops.flash_attention(
        q, k, v, qp, kp, impl="pallas"), one_chip,
        ((B, hq, 2 * CHUNK, HD), jnp.bfloat16),
        ((B, hkv, 2 * CHUNK, HD), jnp.bfloat16),
        ((B, hkv, 2 * CHUNK, HD), jnp.bfloat16),
        ((B, 2 * CHUNK), jnp.int32), ((B, 2 * CHUNK), jnp.int32))


def test_rmsnorm(one_chip):
    _compile(lambda x, s: ops.rmsnorm(x, s, impl="pallas"), one_chip,
             ((CHUNK, 2048), jnp.bfloat16), ((2048,), jnp.bfloat16))
