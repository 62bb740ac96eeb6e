"""Pallas TPU kernels: paged decode/verify attention (block-table gather).

The paged KV cache (``runtime.kvcache``) stores K/V in a global pool of
fixed-size token pages, ``(P, bs, h_kv, D)``; each sequence addresses its
pages through a per-slot block table of ``nb`` entries.

``paged_verify`` walks each slot's *live* pages only. Grid step (b, j)
covers ``ppb`` consecutive logical pages of slot b (positions
``j*ppb*bs ..``) with every KV head at once. The pools stay in HBM in
their stored layout; the step copies the block's pages into VMEM with
one DMA per page through the scalar-prefetched table. A block that no
draft row can see — wholly past ``kv_len``, or wholly before the
sliding window — is skipped: no DMA and no compute. Each live step
starts the DMAs of the next live block (of this slot, or of the next
slot that has one) before it waits on its own, so page reads overlap
the math; the grid therefore runs in order.

A page is ``bs * h_kv`` rows of D, token-major, so one block is
``(ppb*bs*h_kv, D)`` with row ``token * h_kv + head``. The scores of all
heads come from one matmul, (h_kv * T * n_rep, D) x (ppb*bs*h_kv, D)^T,
in which a query row keeps only the columns of its own KV head: the
mask that selects them is the same one that applies causality and the
window. Reading a head out of the interleaved block costs more than the
extra MXU work: on a TPU v5e at qwen2.5-14b's chat shapes a layer took
135 us this way against 234-358 us with per-head strided reads. q, K and V are upcast to float32 and the
online softmax runs in float32 across blocks, as in the contiguous
``flash_decode.flash_verify``.

``paged_verify_quant`` (int8 pages) keeps the older grid of
``(slots, kv heads, table pages)`` over pools transposed to
``(P, h_kv, bs, D)``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: tokens a grid step of ``paged_verify`` covers, at most
_BLOCK_TOKENS = 256
#: VMEM a grid step's working set may take, at most
_BLOCK_VMEM = 8 << 20


def _block_pages(page_tokens: int, max_pages: int, h_kv: int, d: int,
                itemsize: int, rows: int) -> int:
    """Pages per grid step of ``paged_verify``: about ``_BLOCK_TOKENS``
    tokens and no more than the table holds, with the step's working set
    within ``_BLOCK_VMEM``: K and V double-buffered in the pool's dtype,
    their float32 upcasts, and the (rows * h_kv)-row scores and weights
    over each page's ``page_tokens * h_kv`` columns."""
    cols = page_tokens * h_kv
    page = cols * (4 * d * itemsize + 2 * d * 4 + 2 * rows * h_kv * 4)
    fit = max(1, _BLOCK_VMEM // page)
    return max(1, min(_BLOCK_TOKENS // page_tokens, max_pages, fit))


def _paged_verify_kernel(kv_len_ref, table_ref, q_ref, k_hbm, v_hbm,
                         out_ref, k_buf, v_buf, sem, state, acc_ref, m_ref,
                         l_ref, *, ppb: int, page_tokens: int,
                         window: Optional[int], n_draft: int, n_rep: int):
    """Grid step (b, j): the T draft rows of slot b, every KV head,
    against logical pages ``j*ppb .. j*ppb + ppb - 1``. q rows are
    ``head * T * n_rep + t * n_rep + rep``; block rows (the matmul's
    columns) are ``token * h_kv + head``."""
    b, j = pl.program_id(0), pl.program_id(1)
    n_slots, n_blk = pl.num_programs(0), pl.num_programs(1)
    bs = page_tokens
    rows_per_page = k_hbm.shape[1]                   # bs * h_kv
    h_kv = rows_per_page // bs
    blk = ppb * bs                                   # tokens per block
    n_cols = ppb * rows_per_page
    rows = n_draft * n_rep                           # q rows per KV head

    def bounds(s):
        """Live blocks [lo, hi) of slot s."""
        n = kv_len_ref[s]
        hi = jnp.minimum((n + blk - 1) // blk, n_blk)
        if window is None:
            return jnp.int32(0), hi
        # the oldest position any row sees is kv_len - T - window + 1
        lo = jnp.maximum(n - n_draft - window + 1, 0) // blk
        return jnp.minimum(lo, hi), hi

    def dma(s, jj, slot, wait: bool):
        """Start (or wait for) the copies of the pages of block jj of
        slot s that hold positions below its kv_len; none past them."""
        first = jj * ppb
        n = jnp.clip(jnp.minimum((kv_len_ref[s] + bs - 1) // bs,
                                 table_ref.shape[1]) - first, 0, ppb)

        def page(i, carry):
            pid = table_ref[s, first + i]
            at = pl.ds(i * rows_per_page, rows_per_page)
            for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                copy = pltpu.make_async_copy(hbm.at[pid], buf.at[slot, at],
                                             sem.at[slot])
                if wait:
                    copy.wait()
                else:
                    copy.start()
            return carry

        jax.lax.fori_loop(0, n, page, 0)

    @pl.when((b == 0) & (j == 0))
    def _first():
        state[0] = 0                                 # block j's DMA started
        state[1] = 0                                 # the buffer it fills

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    lo, hi = bounds(b)

    @pl.when((j >= lo) & (j < hi))
    def _compute():
        slot = state[1]

        @pl.when(state[0] == 0)
        def _():
            dma(b, j, slot, wait=False)

        # the next live block: this slot's next, else the first live
        # block of the next slot that has one
        def empty(s):
            s_lo, s_hi = bounds(jnp.minimum(s, n_slots - 1))
            return (s < n_slots) & (s_lo >= s_hi)

        s_next = jax.lax.while_loop(empty, lambda s: s + 1, b + 1)
        same = j + 1 < hi
        nxt_b = jnp.where(same, b, s_next)
        nxt_j = jnp.where(same, j + 1,
                          bounds(jnp.minimum(s_next, n_slots - 1))[0])
        more = nxt_b < n_slots

        @pl.when(more)
        def _():
            dma(nxt_b, nxt_j, 1 - slot, wait=False)

        state[0] = more.astype(jnp.int32)
        state[1] = 1 - slot
        dma(b, j, slot, wait=True)

        kv_len = kv_len_ref[b]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, n_cols), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (h_kv * rows, 1), 0)
        pos = j * blk + col // h_kv
        qpos = kv_len - n_draft + (row % rows) // n_rep
        mask = ((col % h_kv) == row // rows) & (pos <= qpos)
        if window is not None:
            mask &= pos > (qpos - window)
        # pages past kv_len were never copied: zero V there, so stale
        # bytes (NaN included) cannot reach the output through p == 0
        v_ok = (j * blk + jax.lax.broadcasted_iota(
            jnp.int32, (n_cols, 1), 0) // h_kv) < kv_len
        v = jnp.where(v_ok, v_buf[slot].astype(jnp.float32), 0.0)
        k = k_buf[slot].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32) * (1.0 / math.sqrt(q_ref.shape[-1]))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(mask, s, -jnp.inf)             # (h_kv*rows, n_cols)

        m_prev = m_ref[...]                          # (h_kv*rows, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_blk - 1)
    def _done():
        out_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_verify(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                 table: jnp.ndarray, kv_len: jnp.ndarray, *,
                 window: Optional[int] = None,
                 interpret: bool = False) -> jnp.ndarray:
    """q: (B, T, H, D); k_pages/v_pages: (P, bs, h_kv, D);
    table: (B, nb) int32 page ids; kv_len: (B,) -> (B, T, H, D).

    Scores T draft positions against a paged KV cache in one pass.
    ``kv_len`` counts valid positions *including* the T draft tokens the
    caller already wrote through the table, so T = 1 is ordinary paged
    decode attention. Table entries past ``ceil(kv_len/bs)`` may be any
    page id (sink/stale): those pages are never read.
    """
    B, T, H, D = q.shape
    P, bs, h_kv = k_pages.shape[:3]
    nb = table.shape[1]
    n_rep = H // h_kv
    rows = T * n_rep
    ppb = _block_pages(bs, nb, h_kv, D, k_pages.dtype.itemsize, rows)
    # (B, h_kv * T * n_rep, D) with row = head * T * n_rep + t * n_rep + rep
    qg = q.reshape(B, T, h_kv, n_rep, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, h_kv * rows, D)
    q_spec = pl.BlockSpec((1, h_kv * rows, D),
                          lambda b, j, kv_len, tab: (b, 0, 0))
    block = (2, ppb * bs * h_kv, D)                  # two buffers
    out = pl.pallas_call(
        functools.partial(_paged_verify_kernel, ppb=ppb, page_tokens=bs,
                          window=window, n_draft=T, n_rep=n_rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                   # kv_len, block table
            grid=(B, pl.cdiv(nb, ppb)),
            in_specs=[q_spec,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM(block, k_pages.dtype),
                pltpu.VMEM(block, v_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((h_kv * rows, D), jnp.float32),
                pltpu.VMEM((h_kv * rows, 1), jnp.float32),
                pltpu.VMEM((h_kv * rows, 1), jnp.float32),
            ],
        ),
        # the DMA chain runs from one grid step into the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        out_shape=jax.ShapeDtypeStruct((B, h_kv * rows, D), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), table.astype(jnp.int32), qg,
      # a page as bs * h_kv rows of D: the stored bytes, not a copy
      k_pages.reshape(P, bs * h_kv, D), v_pages.reshape(P, bs * h_kv, D))
    return out.reshape(B, h_kv, T, n_rep, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, T, H, D)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                 table: jnp.ndarray, kv_len: jnp.ndarray, *,
                 window: Optional[int] = None,
                 interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, D) -> (B, H, D): the T = 1 slice of ``paged_verify``."""
    return paged_verify(q[:, None], k_pages, v_pages, table, kv_len,
                        window=window, interpret=interpret)[:, 0]


def _paged_verify_quant_kernel(kv_len_ref, table_ref, q_ref, k_ref, v_ref,
                               ks_ref, vs_ref, out_ref, acc_ref, m_ref,
                               l_ref, *, block_s: int,
                               window: Optional[int], n_chunks: int,
                               n_draft: int, n_rep: int):
    """``_paged_verify_kernel`` over int8 pages: K/V blocks arrive packed
    (one byte per element) plus a per-(position, kv-head) scale block;
    dequantization is fused into the f32 upcast the attention math does
    anyway, so the only HBM traffic for KV is the quantized bytes."""
    b = pl.program_id(0)
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    rows = n_draft * n_rep
    q = q_ref[0, 0]                                  # (rows, D)
    k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]   # (bs, D) * (bs, 1)
    v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
    kv_len = kv_len_ref[b]

    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.dot(q.astype(jnp.float32) * scale, k.T,
                preferred_element_type=jnp.float32)  # (rows, bs)

    pos = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32,
                                                     (1, block_s), 1)
    t_row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // n_rep
    qpos = kv_len - n_draft + t_row                  # (rows, 1)
    mask = pos <= qpos                               # (rows, bs)
    if window is not None:
        mask &= pos > (qpos - window)
    s = jnp.where(mask, s, -jnp.inf)

    m_prev = m_ref[...]                              # (rows, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s_idx == n_chunks - 1)
    def _done():
        out_ref[0, 0] = (acc_ref[...]
                         / jnp.maximum(l_ref[...], 1e-30)
                         ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_verify_quant(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, k_scale: jnp.ndarray,
                       v_scale: jnp.ndarray, table: jnp.ndarray,
                       kv_len: jnp.ndarray, *,
                       window: Optional[int] = None,
                       interpret: bool = False) -> jnp.ndarray:
    """``paged_verify`` over int8 pages. k_pages/v_pages: (P, bs, h_kv, D)
    int8; k_scale/v_scale: (P, bs, h_kv) per-(position, kv-head) scales
    (``layers.quantize_kv`` convention: amax/127). Dequant happens inside
    the kernel — the pages are never inflated in HBM."""
    B, T, H, D = q.shape
    bs, h_kv = k_pages.shape[1], k_pages.shape[2]
    nb = table.shape[1]
    n_rep = H // h_kv
    rows = T * n_rep
    qg = q.reshape(B, T, h_kv, n_rep, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, h_kv, rows, D)
    kt = k_pages.transpose(0, 2, 1, 3)               # (P, h_kv, bs, D)
    vt = v_pages.transpose(0, 2, 1, 3)
    kst = k_scale.transpose(0, 2, 1)[..., None] \
        .astype(jnp.float32)                         # (P, h_kv, bs, 1)
    vst = v_scale.transpose(0, 2, 1)[..., None].astype(jnp.float32)

    page_spec = pl.BlockSpec((1, 1, bs, D),
                             lambda b, h, j, kv_len, tab:
                             (tab[b, j], h, 0, 0))
    scale_spec = pl.BlockSpec((1, 1, bs, 1),
                              lambda b, h, j, kv_len, tab:
                              (tab[b, j], h, 0, 0))
    grid = (B, h_kv, nb)
    out = pl.pallas_call(
        functools.partial(_paged_verify_quant_kernel, block_s=bs,
                          window=window, n_chunks=nb, n_draft=T,
                          n_rep=n_rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                   # kv_len, block table
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, rows, D),
                             lambda b, h, j, kv_len, tab: (b, h, 0, 0)),
                page_spec, page_spec, scale_spec, scale_spec,
            ],
            out_specs=pl.BlockSpec((1, 1, rows, D),
                                   lambda b, h, j, kv_len, tab:
                                   (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, D), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, h_kv, rows, D), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), table.astype(jnp.int32), qg, kt, vt,
      kst, vst)
    return out.reshape(B, h_kv, T, n_rep, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, T, H, D)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_quant(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, k_scale: jnp.ndarray,
                       v_scale: jnp.ndarray, table: jnp.ndarray,
                       kv_len: jnp.ndarray, *,
                       window: Optional[int] = None,
                       interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, D) -> (B, H, D): the T = 1 slice of
    ``paged_verify_quant``."""
    return paged_verify_quant(q[:, None], k_pages, v_pages, k_scale,
                              v_scale, table, kv_len, window=window,
                              interpret=interpret)[:, 0]
