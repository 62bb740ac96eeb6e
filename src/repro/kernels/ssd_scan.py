"""Pallas TPU kernel: Mamba-2 SSD chunked scan.

Grid (B, nh, S/chunk): the chunk axis is sequential; the running state
(P, N) lives in VMEM scratch and flows across chunk steps. Each program
computes the intra-chunk quadratic part on the MXU and folds the
inter-chunk recurrence — the TPU-native shape of the paper's "split the
work into blocks small enough for fast memory" insight applied to SSD.

Block working set (chunk=128, P=64, N=128):
  x (chunk, P), B/C (chunk, N), L mask (chunk, chunk), state (P, N):
  all f32 ~ 0.3 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hlast_ref, h_ref,
            *, chunk: int, n_chunks: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)              # (chunk, P)
    dt = dt_ref[0, 0].astype(jnp.float32)            # (chunk, 1)
    A = a_ref[0, 0]                                  # scalar (1,1) f32
    Bm = b_ref[0].astype(jnp.float32)                # (chunk, N)
    Cm = c_ref[0].astype(jnp.float32)                # (chunk, N)

    dA = dt * A                                      # (chunk, 1) <= 0
    cum = jnp.cumsum(dA, axis=0)                     # (chunk, 1)

    # intra-chunk: y[t] = sum_{s<=t} exp(cum_t - cum_s) (C_t.B_s) dt_s x_s
    diff = cum - cum.T                               # (chunk, chunk)
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    Lmat = jnp.where(tri, jnp.exp(diff), 0.0)
    scores = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32) * Lmat
    y_intra = jnp.dot(scores, dt * x,
                      preferred_element_type=jnp.float32)   # (chunk, P)

    # inter-chunk: y[t] += exp(cum_t) C_t . h_prev
    h_prev = h_ref[...]                              # (P, N)
    y_inter = jnp.exp(cum) * jnp.dot(Cm, h_prev.T,
                                     preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h = exp(total) h_prev + sum_s exp(cum_end - cum_s) dt_s x_s B_s^T
    total = cum[-1:, :]                              # (1, 1)
    decay = jnp.exp(total - cum)                     # (chunk, 1)
    h_new = h_prev * jnp.exp(total) + jnp.dot(
        (decay * dt * x).T, Bm, preferred_element_type=jnp.float32)
    h_ref[...] = h_new

    @pl.when(c_idx == n_chunks - 1)
    def _done():
        hlast_ref[0, 0] = h_new.astype(hlast_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             Bmat: jnp.ndarray, Cmat: jnp.ndarray, *, chunk: int = 128,
             interpret: bool = False):
    """SSD over a sequence (zero initial state).

    x: (B, S, nh, P); dt: (B, S, nh); A: (nh,) <= 0; Bmat/Cmat: (B, S, N).
    Returns (y (B, S, nh, P), h_final (B, nh, P, N)).
    """
    Bsz, S, nh, P = x.shape
    N = Bmat.shape[-1]
    ck = min(chunk, S)
    assert S % ck == 0, (S, ck)
    n_chunks = S // ck

    xt = x.transpose(0, 2, 1, 3)                     # (B, nh, S, P)
    dtt = dt.transpose(0, 2, 1)[..., None]           # (B, nh, S, 1)
    a2 = jnp.broadcast_to(A[None, :, None, None].astype(jnp.float32),
                          (Bsz, nh, 1, 1))
    grid = (Bsz, nh, n_chunks)
    y, h_fin = pl.pallas_call(
        functools.partial(_kernel, chunk=ck, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, ck, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, ck, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b, h, c: (b, h, 0, 0)),
            pl.BlockSpec((1, ck, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, ck, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, ck, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, nh, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, nh, P, N), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, a2, Bmat, Cmat)
    return y.transpose(0, 2, 1, 3), h_fin
