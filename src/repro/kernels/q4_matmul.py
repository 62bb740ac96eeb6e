"""Pallas TPU kernel: W4A16 grouped-quantized matmul, dequant-in-kernel.

The paper's hot loop is Q4K matvec/matmul on CPU/CUDA; the TPU-native
adaptation streams int4-packed weights HBM->VMEM (half the bytes of bf16,
which matters because decode is weight-bandwidth-bound) and dequantizes
tile-by-tile in VMEM right before feeding the MXU.

Layout: x (M, K) activations; packed (K/2, N) int8 (two int4 per byte along
the contraction axis); scale (K/group, N). x is split into its even and odd
columns outside the kernel, which pair with the low and high nibbles. Block
sizes keep every tile MXU-aligned (multiples of 128 on the matmul dims),
the scale tile at 8 sublanes or more (``k_block``), and the working set
within VMEM:

  x tiles 2 x (bm, bk/2) bf16     : bm*bk*2
  packed tile (bk/2, bn) int8     : bk*bn/2 (unpacked in int32/f32)
  scale tile (bk/g, bn)           : small
  out tile (bm, bn) f32 (+acc)    : bm*bn*4

Group 64, bm=8 (decode), (bk, bn) = (512, 512): the unpacked f32 nibbles
and their scales are 3 x 256*512*4 ~ 1.5 MiB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def k_block(group: int) -> int:
    """Contraction tile: 256, or 8 scale rows (8 * group) where that is
    more, so the scale tile fills the 8 sublanes of a TPU tile. A K no
    larger than this is taken whole (a tile equal to the full dim is
    always legal)."""
    return max(256, 8 * group)


def _kernel(xe_ref, xo_ref, packed_ref, scale_ref, out_ref, *, group: int):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    # unpack two int4 per byte in int32 (the chip has no int8 shifts):
    # the low nibble is weight row 2i, the high nibble row 2i + 1, both
    # sign-extended by the arithmetic shifts
    p = packed_ref[...].astype(jnp.int32)            # (bk/2, bn)
    lo = (p << 28) >> 28
    hi = p >> 4

    # rows 2i and 2i + 1 share scale row 2i // group = i // (group / 2)
    scale = scale_ref[...].astype(jnp.float32)       # (bk/g, bn)
    g_rows, bn = scale.shape
    half = group // 2
    scale_rows = jnp.broadcast_to(scale[:, None, :], (g_rows, half, bn)
                                  ).reshape(g_rows * half, bn)

    # x's even and odd columns meet the low and high nibbles: no row
    # interleave of the weight tile is needed
    out_ref[...] += (
        jnp.dot(xe_ref[...].astype(jnp.float32),
                lo.astype(jnp.float32) * scale_rows,
                preferred_element_type=jnp.float32)
        + jnp.dot(xo_ref[...].astype(jnp.float32),
                  hi.astype(jnp.float32) * scale_rows,
                  preferred_element_type=jnp.float32))


@functools.partial(jax.jit, static_argnames=("group", "block_m", "block_n",
                                             "interpret"))
def q4_matmul(x: jnp.ndarray, packed: jnp.ndarray, scale: jnp.ndarray, *,
              group: int = 64, block_m: int = 256, block_n: int = 512,
              interpret: bool = False) -> jnp.ndarray:
    """x: (M, K); packed: (K/2, N) int8; scale: (K/group, N). -> (M, N) f32.

    K is tiled by ``k_block(group)``; M and N by ``block_m``/``block_n``."""
    M, K = x.shape
    N = packed.shape[1]
    assert packed.shape[0] * 2 == K
    assert scale.shape == (K // group, N), (scale.shape, K, group, N)
    bm = min(block_m, M)
    bn = min(block_n, N)
    bk = min(k_block(group), K)
    assert K % bk == 0 and bk % group == 0, (K, bk, group)
    assert M % bm == 0 and N % bn == 0
    grid = (M // bm, N // bn, K // bk)
    x_spec = pl.BlockSpec((bm, bk // 2), lambda i, j, k: (i, k))
    return pl.pallas_call(
        functools.partial(_kernel, group=group),
        grid=grid,
        in_specs=[
            x_spec, x_spec,
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // group, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x[:, 0::2], x[:, 1::2], packed, scale)
