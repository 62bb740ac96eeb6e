"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at qwen2.5-14b's published widths.

Interpret mode accepts kernels the chip's compiler refuses (tiling that
breaks the (8, 128) rule, blocks that overflow VMEM), so every kernel of
the serving path is compiled here for the real target and must come out
as a Mosaic ``tpu_custom_call``. The topology is described inside a
fixture — never at import — because only one process may load the TPU
library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_decode import (paged_decode, paged_decode_quant,
                                        paged_verify)
from repro.kernels.paged_prefill import paged_prefill
from repro.kernels.q4_matmul import q4_matmul

# qwen2.5-14b: 40 query heads, 8 KV heads of 128; 16-token pages
B, H, HKV, D = 8, 40, 8, 128
PAGE, N_PAGES, MAX_PAGES = 16, 512, 64
D_MODEL, D_FF = 5120, 13824


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def _pages(s, dtype):
    return _spec(s, (N_PAGES, PAGE, HKV, D), dtype)


def _table(s, batch):
    return (_spec(s, (batch, MAX_PAGES), jnp.int32),
            _spec(s, (batch,), jnp.int32))


@pytest.mark.parametrize("n_draft", [1, 4])
def test_paged_verify_compiles(one_chip, n_draft):
    s = one_chip
    q = _spec(s, (B, n_draft, H, D), jnp.bfloat16)
    _assert_kernel(paged_verify.lower(
        q, _pages(s, jnp.bfloat16), _pages(s, jnp.bfloat16),
        *_table(s, B), interpret=False))


@pytest.mark.parametrize("n_draft", [1, 4])
@pytest.mark.parametrize("batch,heads,n_pages", [
    (16, 40, 2705),          # the qwen2.5-14b-l12 chat cell's engine
    (26, 32, 4315),          # the mixtral-8x7b-l4 chat cell's engine
], ids=["qwen", "mixtral"])
def test_paged_verify_compiles_at_cell_shapes(one_chip, batch, heads,
                                              n_pages, n_draft):
    """The served shapes: 160-page tables over the cells' whole pools,
    which the kernel reads in place through its page DMAs."""
    s = one_chip
    q = _spec(s, (batch, n_draft, heads, D), jnp.bfloat16)
    pages = _spec(s, (n_pages, PAGE, HKV, D), jnp.bfloat16)
    table = _spec(s, (batch, 160), jnp.int32)
    _assert_kernel(paged_verify.lower(
        q, pages, pages, table, _spec(s, (batch,), jnp.int32),
        interpret=False))


def test_paged_decode_compiles(one_chip):
    s = one_chip
    q = _spec(s, (B, H, D), jnp.bfloat16)
    _assert_kernel(paged_decode.lower(
        q, _pages(s, jnp.bfloat16), _pages(s, jnp.bfloat16),
        *_table(s, B), interpret=False))


def test_paged_decode_quant_compiles(one_chip):
    s = one_chip
    q = _spec(s, (B, H, D), jnp.bfloat16)
    scale = _spec(s, (N_PAGES, PAGE, HKV), jnp.bfloat16)
    _assert_kernel(paged_decode_quant.lower(
        q, _pages(s, jnp.int8), _pages(s, jnp.int8), scale, scale,
        *_table(s, B), interpret=False))


def test_paged_prefill_compiles(one_chip):
    s = one_chip
    q = _spec(s, (1, 256, H, D), jnp.bfloat16)
    _assert_kernel(paged_prefill.lower(
        q, _pages(s, jnp.bfloat16), _pages(s, jnp.bfloat16),
        *_table(s, 1), interpret=False))


@pytest.mark.parametrize("K,N", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
def test_q4_matmul_compiles(one_chip, K, N):
    s = one_chip
    group = 64
    x = _spec(s, (8, K), jnp.bfloat16)
    packed = _spec(s, (K // 2, N), jnp.int8)
    scale = _spec(s, (K // group, N), jnp.bfloat16)
    _assert_kernel(q4_matmul.lower(x, packed, scale, group=group,
                                   interpret=False))
