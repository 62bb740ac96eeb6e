"""Layer library: every mixer/FFN variant needed by the assigned archs.

Pure ``jnp`` functions over explicit parameter dicts. Distribution is
layered on top: the GSPMD path (train/prefill) relies on sharding
constraints outside these functions; the explicit shard_map ring path
passes ``tp_axis`` so projections psum over the tensor-parallel axis.

Conventions:
  x          : (B, S, d) activations
  attn cache : k/v (B, S_max, h_kv, hd)  [+ int8 scales if quantized]
  positions  : (B, S) int32 absolute positions (M-RoPE: (3, B, S))
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..configs.base import ModelConfig

Params = Dict[str, Any]


# --------------------------------------------------------------------------- #
#  basics
# --------------------------------------------------------------------------- #

def qmm(x: jnp.ndarray, w) -> jnp.ndarray:
    """Matmul against a weight that may still be packed.

    Plain arrays take the ordinary ``@``. A 2-D q4 ``QuantizedTensor``
    (the shape the streamed layer-wise path pulls from a v2 store)
    dispatches the fused ``kernels.ops.q4_matmul`` — dequantization
    happens tile-by-tile in VMEM instead of materializing the bf16 weight
    in HBM first. Ineligible quantized leaves (q2, 3-D expert stacks,
    tile-misaligned dims) fall back to dequantize-then-matmul, which is
    bit-identical at these sizes (both paths accumulate f32).
    """
    from ..quant.grouped import QuantizedTensor, dequantize_leaf

    if not isinstance(w, QuantizedTensor):
        return x @ w
    *lead, K = x.shape
    M = int(np.prod(lead, dtype=np.int64)) if lead else 1
    # the kernel's row tile is min(256, M): M must divide into it
    if q4_fused_eligible(w) and (M <= 256 or M % 256 == 0):
        from ..kernels import ops

        out = ops.q4_matmul(x.reshape(M, K), w.packed, w.scale,
                            group=w.group)
        return out.reshape(*lead, out.shape[-1]).astype(x.dtype)
    return x @ dequantize_leaf(w, jnp.float32).astype(x.dtype)


def q4_fused_eligible(w) -> bool:
    """Whether a QuantizedTensor fits ``kernels.q4_matmul``'s layout:
    2-D q4 packing whose dims divide the kernel's MXU-aligned blocks."""
    if w.bits != 4 or w.packed.ndim != 2:
        return False
    from ..kernels.q4_matmul import k_block

    K, N = w.packed.shape[0] * 2, w.packed.shape[1]
    if K % w.group or 256 % w.group:
        return False
    bk = k_block(w.group)
    return (K <= bk or K % bk == 0) and (N <= 512 or N % 512 == 0)


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5
             ) -> jnp.ndarray:
    """RMSNorm with f32 statistics but width-preserving dtype: the (B,S,d)
    intermediates stay in x.dtype so activation collectives (and their
    gradients) move half the bytes (see EXPERIMENTS §Perf HC1)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv = lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


def swish(x):
    return x * jax.nn.sigmoid(x)


# --------------------------------------------------------------------------- #
#  rotary embeddings (standard / partial / M-RoPE)
# --------------------------------------------------------------------------- #

def rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float
               ) -> jnp.ndarray:
    """x: (B, S, h, d); positions: (B, S). Trig in f32, rotation applied in
    x.dtype (keeps the head-wide tensors — and their gradients/collectives
    — at bf16 width)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, d/2)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mrope_sections(head_dim: int) -> Tuple[int, int, int]:
    """Qwen2-VL 3-D rotary sections (t, h, w) summing to head_dim // 2."""
    half = head_dim // 2
    t = half // 4
    h = (half - t) // 2
    w = half - t - h
    return (t, h, w)


def apply_mrope(x: jnp.ndarray, positions3: jnp.ndarray, theta: float
                ) -> jnp.ndarray:
    """M-RoPE: positions3 (3, B, S) — temporal/height/width streams.

    Frequency layout matches standard RoPE; each frequency index is driven
    by one of the three position streams according to its section.
    """
    d = x.shape[-1]
    half = d // 2
    freqs = rope_freqs(d, theta)                       # (half,)
    sec = mrope_sections(d)
    sec_id = jnp.concatenate([
        jnp.full((sec[0],), 0), jnp.full((sec[1],), 1),
        jnp.full((sec[2],), 2)]).astype(jnp.int32)      # (half,)
    # pos per freq index: (B, S, half)
    pos = jnp.take(positions3.astype(jnp.float32), sec_id, axis=0)  # (half,B,S)
    pos = jnp.moveaxis(pos, 0, -1)                      # (B, S, half)
    ang = pos * freqs
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# --------------------------------------------------------------------------- #
#  attention — chunked causal (train/prefill) and cached decode
# --------------------------------------------------------------------------- #

def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, h_kv, d) -> (B, S, h_kv*n_rep, d) (GQA broadcast)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def chunked_causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                             *, window: Optional[int] = None,
                             q_offset: int = 0,
                             chunk: int = 512) -> jnp.ndarray:
    """Flash-style double-chunked causal attention (pure jnp oracle).

    q: (B, Sq, H, D); k, v: (B, Sk, h_kv, D). Scans KV chunks with an online
    softmax, so peak memory is O(chunk^2) per head instead of O(S^2). This
    is also the reference for the Pallas flash kernel.
    ``window``: sliding-window size (None = full causal).
    ``q_offset``: absolute position of q[0] relative to k[0] (prefill: 0).
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    n_rep = H // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = 1.0 / math.sqrt(D)
    qc = chunk
    kc = chunk
    n_q = -(-Sq // qc)
    n_k = -(-Sk // kc)
    q_pad = n_q * qc - Sq
    k_pad = n_k * kc - Sk
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0), (0, 0)))
    # (B, H, nq, qc, D) / (B, H, nk, kc, D)
    qb = q.reshape(B, n_q, qc, H, D).transpose(0, 3, 1, 2, 4) * scale
    kb = k.reshape(B, n_k, kc, H, D).transpose(0, 3, 1, 2, 4)
    vb = v.reshape(B, n_k, kc, H, D).transpose(0, 3, 1, 2, 4)

    q_pos = q_offset + jnp.arange(n_q * qc)
    k_pos = jnp.arange(n_k * kc)

    def q_chunk_body(qi, q_tile):
        # online softmax over kv chunks
        acc0 = jnp.zeros((B, H, qc, D), jnp.float32)
        m0 = jnp.full((B, H, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, qc), jnp.float32)

        def kv_body(carry, ki):
            acc, m, l = carry
            k_tile = kb[:, :, ki]
            v_tile = vb[:, :, ki]
            s = jnp.einsum("bhqd,bhkd->bhqk", q_tile, k_tile,
                           preferred_element_type=jnp.float32)
            qp = lax.dynamic_slice_in_dim(q_pos, qi * qc, qc)
            kp = lax.dynamic_slice_in_dim(k_pos, ki * kc, kc)
            mask = qp[:, None] >= kp[None, :]
            if window is not None:
                mask &= (qp[:, None] - kp[None, :]) < window
            mask &= kp[None, :] < Sk  # kv padding
            s = jnp.where(mask[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            # guard fully-masked rows
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, None], p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, v_tile,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None

        (acc, m, l), _ = lax.scan(kv_body, (acc0, m0, l0),
                                  jnp.arange(n_k))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out

    def outer(qi):
        return q_chunk_body(qi, qb[:, :, qi])

    out = lax.map(outer, jnp.arange(n_q))              # (nq, B, H, qc, D)
    out = out.transpose(1, 0, 3, 2, 4).reshape(B, n_q * qc, H, D)
    return out[:, :Sq].astype(q.dtype)


def verify_attention_stats(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, kv_len: jnp.ndarray,
                           *, window: Optional[int] = None,
                           pos_offset=0
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Multi-query decode attention stats (speculative draft verification).

    q: (B, T, H, D) — T draft positions scored in one pass. Query t sits at
    absolute position ``kv_len - T + t`` (``kv_len`` counts valid cache
    entries *including* the T draft tokens, so T = 1 reduces to ordinary
    decode) and attends causally: cache positions <= its own.
    k_cache/v_cache: (B, S_local, h_kv, D); ``pos_offset``: absolute
    position of this shard's slot 0 (sequence-sharded ring runtime).
    Returns acc (B, H, T, D) [unnormalized], m (B, H, T), l (B, H, T) for
    ``merge_attention_stats`` (psum/pmax over the TP axis).
    """
    B, T, H, D = q.shape
    S = k_cache.shape[1]
    n_rep = H // k_cache.shape[2]
    k = _repeat_kv(k_cache, n_rep)
    v = _repeat_kv(v_cache, n_rep)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))               # (B, H, T, S)
    pos = jnp.arange(S) + pos_offset                    # (S,)
    qpos = kv_len[:, None] - T + jnp.arange(T)[None, :]  # (B, T)
    mask = pos[None, None, :] <= qpos[:, :, None]       # (B, T, S)
    if window is not None:
        mask &= pos[None, None, :] > (qpos[:, :, None] - window)
    s = jnp.where(mask[:, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1)                             # (B, H, T)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(mask[:, None], jnp.exp(s - m_safe[..., None]), 0.0)
    l = p.sum(-1)                                       # (B, H, T)
    acc = jnp.einsum("bhts,bshd->bhtd", p, v.astype(jnp.float32))
    return acc, m, l


def verify_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, kv_len: jnp.ndarray,
                     *, window: Optional[int] = None) -> jnp.ndarray:
    """Multi-position attention against a cache: (B, T, H, D) -> same.

    The pure-jnp oracle for the Pallas ``flash_verify`` kernel; see
    ``verify_attention_stats`` for the causal-among-drafts semantics.
    """
    acc, m, l = verify_attention_stats(q, k_cache, v_cache, kv_len,
                                       window=window)
    out = acc / jnp.maximum(l[..., None], 1e-30)        # (B, H, T, D)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def decode_attention_stats(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, kv_len: jnp.ndarray,
                           *, window: Optional[int] = None,
                           pos_offset=0
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-query stats — the T = 1 slice of ``verify_attention_stats``.

    q: (B, 1, H, D) -> acc (B, H, D) [unnormalized], m (B, H), l (B, H).
    """
    acc, m, l = verify_attention_stats(q, k_cache, v_cache, kv_len,
                                       window=window, pos_offset=pos_offset)
    return acc[:, :, 0], m[:, :, 0], l[:, :, 0]


def merge_attention_stats(acc, m, l, axis_name: str) -> jnp.ndarray:
    """Combine per-shard online-softmax stats across ``axis_name``."""
    m_g = lax.pmax(m, axis_name)
    m_safe = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_g = lax.psum(l * corr, axis_name)
    acc_g = lax.psum(acc * corr[..., None], axis_name)
    return acc_g / jnp.maximum(l_g[..., None], 1e-30)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, kv_len: jnp.ndarray,
                     *, window: Optional[int] = None) -> jnp.ndarray:
    """Single-position attention against a cache.

    q: (B, 1, H, D); k_cache/v_cache: (B, S_max, h_kv, D);
    kv_len: (B,) number of valid cache entries (current token included).
    """
    acc, m, l = decode_attention_stats(q, k_cache, v_cache, kv_len,
                                       window=window)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out[:, None].astype(q.dtype)


# --------------------------------------------------------------------------- #
#  standard attention block (GQA / SWA / M-RoPE), with optional QKV bias
# --------------------------------------------------------------------------- #

def init_attn(cfg: ModelConfig, key, dtype) -> Params:
    d = cfg.d_model
    H, hk, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(k1, (d, H * hd), dtype) * s,
        "wk": jax.random.normal(k2, (d, hk * hd), dtype) * s,
        "wv": jax.random.normal(k3, (d, hk * hd), dtype) * s,
        "wo": jax.random.normal(k4, (H * hd, d), dtype) * s,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((hk * hd,), dtype)
        p["bv"] = jnp.zeros((hk * hd,), dtype)
    return p


def set_qkv_constraint(fn) -> None:
    """Optional hook pinning (B,S,H,hd) tensors (set by the runtime)."""
    global _QKV_CONSTRAINT
    _QKV_CONSTRAINT = fn


_QKV_CONSTRAINT = None

#: hook pinning MoE (E, C, d/f) dispatch buffers — without it GSPMD can
#: replicate the capacity buffer (21 GB/chip at 32k prefill, mixtral).
_MOE_CONSTRAINT = None


def set_moe_constraint(fn) -> None:
    global _MOE_CONSTRAINT
    _MOE_CONSTRAINT = fn


def _constrain_heads(t):
    if _QKV_CONSTRAINT is not None:
        return _QKV_CONSTRAINT(t)
    return t


def attn_qkv(p: Params, cfg: ModelConfig, x: jnp.ndarray, positions,
             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    B, S, _ = x.shape
    H, hk, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = qmm(x, p["wq"])
    k = qmm(x, p["wk"])
    v = qmm(x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = _constrain_heads(q.reshape(B, S, H, hd))
    k = _constrain_heads(k.reshape(B, S, hk, hd))
    v = _constrain_heads(v.reshape(B, S, hk, hd))
    if not cfg.use_rope:
        return q, k, v
    if cfg.mrope:
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def quantize_kv(t: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(token, head) symmetric int8 quantization: (B,S,h,d) -> int8+scale."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)     # (B,S,h)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(dtype)


def attn_block(p: Params, cfg: ModelConfig, x: jnp.ndarray, positions,
               *, cache: Optional[Dict] = None,
               decode: bool = False, tp_axis: Optional[str] = None,
               cross_kv: Optional[Tuple] = None,
               causal: bool = True) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Full attention block: qkv -> attention -> o-proj.

    ``cache``: {"k": (B,Smax,hk,hd), "v": ..., "len": (B,)}. In decode mode
    the new token is written at position ``len`` (rolling for SWA) and
    attention runs against the cache; otherwise full causal attention over
    ``x`` (and the cache is filled if provided).
    If the cache carries ``k_scale``/``v_scale`` the K/V tensors are stored
    int8 (quantize-on-write, dequantize-on-read) — used by MHA archs whose
    32k bf16 cache would overflow the per-chip HBM budget.
    ``cross_kv``: (k, v) from an encoder — skips qkv for k/v (whisper).
    """
    B, S, _ = x.shape
    if cross_kv is not None:
        H, hd = cfg.n_heads, cfg.head_dim
        q = (x @ p["wq"]).reshape(B, S, H, hd)
        if cfg.qkv_bias:
            q = q + p["bq"].reshape(H, hd)
        k, v = cross_kv
        out = chunked_causal_attention(q, k, v, chunk=256) if causal else \
            _full_attention(q, k, v)
        o = out.reshape(B, S, -1) @ p["wo"]
        if tp_axis:
            o = lax.psum(o, tp_axis)
        return o, cache

    q, k, v = attn_qkv(p, cfg, x, positions)
    window = cfg.attn_window
    quantized = cache is not None and "k_scale" in cache
    new_cache = cache
    if decode:
        assert cache is not None
        kc, vc, ln = cache["k"], cache["v"], cache["len"]
        Smax = kc.shape[1]
        rolling = window is not None and Smax == window
        # T > 1 (speculative verify) needs position-addressable slots for
        # causal masking among the draft tokens; a rolling SWA buffer
        # permutes positions, so multi-token decode is gated off there.
        assert S == 1 or not rolling, "multi-token decode needs Smax > window"
        if quantized:
            k_wr, ksc = quantize_kv(k)
            v_wr, vsc = quantize_kv(v)
        else:
            k_wr, v_wr = k.astype(kc.dtype), v.astype(vc.dtype)
        ks_c = cache.get("k_scale")
        vs_c = cache.get("v_scale")
        for t in range(S):                       # static, small (draft block)
            slot = ((ln + t) % window) if rolling \
                else jnp.minimum(ln + t, Smax - 1)
            kc = jax.vmap(lambda c, tt, i: lax.dynamic_update_slice(
                c, tt, (i, 0, 0)))(kc, k_wr[:, t:t + 1], slot)
            vc = jax.vmap(lambda c, tt, i: lax.dynamic_update_slice(
                c, tt, (i, 0, 0)))(vc, v_wr[:, t:t + 1], slot)
            if quantized:
                ks_c = jax.vmap(lambda c, tt, i: lax.dynamic_update_slice(
                    c, tt, (i, 0)))(ks_c, ksc[:, t:t + 1].astype(ks_c.dtype),
                                    slot)
                vs_c = jax.vmap(lambda c, tt, i: lax.dynamic_update_slice(
                    c, tt, (i, 0)))(vs_c, vsc[:, t:t + 1].astype(vs_c.dtype),
                                    slot)
        new_cache = {"k": kc, "v": vc, "len": ln + S}
        if quantized:
            new_cache["k_scale"] = ks_c
            new_cache["v_scale"] = vs_c
            k_at = dequantize_kv(kc, ks_c, q.dtype)
            v_at = dequantize_kv(vc, vs_c, q.dtype)
        else:
            k_at = kc.astype(q.dtype)
            v_at = vc.astype(q.dtype)
        kv_len = jnp.minimum(ln + S, Smax) if window is not None else ln + S
        out = verify_attention(q, k_at, v_at, kv_len, window=window)
    else:
        out = chunked_causal_attention(q, k, v, window=window) if causal \
            else _full_attention(q, k, v)
        if cache is not None:
            Smax = cache["k"].shape[1]
            if window is not None and Smax <= S:
                # rolling buffer: keep the trailing window; token t lives at
                # slot t % Smax so decode's rolling writes stay consistent.
                kk = jnp.roll(k[:, -Smax:], S % Smax, axis=1)
                vv = jnp.roll(v[:, -Smax:], S % Smax, axis=1)
            else:
                kk = k[:, :Smax]
                vv = v[:, :Smax]
            pad_s = Smax - kk.shape[1]
            if pad_s > 0:
                kk = jnp.pad(kk, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
                vv = jnp.pad(vv, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
            new_cache = {"len": cache["len"] + S}
            if quantized:
                kq, ksc = quantize_kv(kk)
                vq, vsc = quantize_kv(vv)
                new_cache.update(
                    k=kq, v=vq,
                    k_scale=ksc.astype(cache["k_scale"].dtype),
                    v_scale=vsc.astype(cache["v_scale"].dtype))
            else:
                new_cache.update(k=kk.astype(cache["k"].dtype),
                                 v=vv.astype(cache["v"].dtype))
    o = qmm(out.reshape(B, S, -1), p["wo"])
    if tp_axis:
        o = lax.psum(o, tp_axis)
    return o, new_cache


# --------------------------------------------------------------------------- #
#  paged KV cache: block-table gather / scatter + paged attention
# --------------------------------------------------------------------------- #

def gather_pages(pages: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """(P, bs, ...) page pool + (B, nb) block table -> (B, nb*bs, ...).

    Row ``b``'s gathered axis-1 order IS its sequence order: table entry
    ``j`` covers absolute positions ``j*bs .. (j+1)*bs - 1``. Entries past
    a sequence's length may point anywhere valid (the sink page, a stale
    page) — those positions are >= ``len`` and masked by the caller.
    """
    g = jnp.take(pages, table, axis=0)               # (B, nb, bs, ...)
    B, nb, bs = g.shape[:3]
    return g.reshape(B, nb * bs, *g.shape[3:])


def write_pages(pages: jnp.ndarray, table: jnp.ndarray, ln: jnp.ndarray,
                vals: jnp.ndarray) -> jnp.ndarray:
    """Scatter T new cache lines at positions ``ln .. ln+T-1`` through the
    block table. pages: (P, bs, ...); vals: (B, T, ...); ln: (B,).

    Distinct live slots own distinct pages, so cross-batch scatter indices
    never collide except on the sink page (freed slots), whose content is
    never read unmasked.
    """
    B, T = vals.shape[:2]
    bs, nb = pages.shape[1], table.shape[1]
    bidx = jnp.arange(B)
    for t in range(T):                       # static, small (draft block)
        pos = ln + t
        blk = jnp.minimum(pos // bs, nb - 1)
        pid = table[bidx, blk]
        pages = pages.at[pid, pos % bs].set(vals[:, t].astype(pages.dtype))
    return pages


def paged_verify_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, table: jnp.ndarray,
                           kv_len: jnp.ndarray, *,
                           window: Optional[int] = None) -> jnp.ndarray:
    """Multi-position attention against a paged cache (pure-jnp oracle for
    the Pallas ``paged_verify`` kernel).

    q: (B, T, H, D); k_pages/v_pages: (P, bs, h_kv, D); table: (B, nb);
    kv_len: (B,) valid positions *including* the T current tokens. The
    gather materializes (B, nb*bs, h_kv, D) sequences whose extra
    positions are masked exactly like unused dense-cache slots, so paged
    and dense attention agree bit-for-bit.
    """
    k = gather_pages(k_pages, table)
    v = gather_pages(v_pages, table)
    return verify_attention(q, k, v, kv_len, window=window)


def paged_prefill_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                            v_pages: jnp.ndarray, table: jnp.ndarray,
                            kv_len: jnp.ndarray, *,
                            window: Optional[int] = None) -> jnp.ndarray:
    """Chunk-vs-pages causal attention (pure-jnp oracle for the Pallas
    ``paged_prefill`` kernel).

    q: (B, S, H, D) — one prompt chunk whose KV the caller already wrote
    through the table; ``kv_len`` includes it, so chunk position t sits
    at absolute position ``kv_len - S + t``. The gathered sequence runs
    through ``chunked_causal_attention`` — the *same* function the dense
    prefill path uses — so a chunk-prefilled slot's activations (and the
    first token they produce) are byte-identical to one-shot dense
    prefill. Chunked admission runs one slot at a time, so all batch
    rows share the offset (``kv_len[0]`` is used).
    """
    S = q.shape[1]
    k = gather_pages(k_pages, table).astype(q.dtype)
    v = gather_pages(v_pages, table).astype(q.dtype)
    return chunked_causal_attention(q, k, v, window=window,
                                    q_offset=kv_len[0] - S)


def _paged_attention(q: jnp.ndarray, pages: Dict, table: jnp.ndarray,
                     kv_len: jnp.ndarray, *, window: Optional[int],
                     prefill: bool) -> jnp.ndarray:
    """Dispatch paged attention: fused Pallas kernel when compiled
    kernels are live (TPU), the pure-jnp oracle elsewhere. ``pages`` may
    carry int8 K/V plus ``k_scale``/``v_scale`` — the kernel reads the
    quantized pages directly; the jnp path dequantizes the (gathered)
    sequence first."""
    from ..kernels import ops
    if "k_scale" in pages:
        if ops.kernels_active():
            return ops.paged_verify_quant(
                q, pages["k"], pages["v"], pages["k_scale"],
                pages["v_scale"], table, kv_len, window=window)
        k = dequantize_kv(gather_pages(pages["k"], table),
                          gather_pages(pages["k_scale"], table), q.dtype)
        v = dequantize_kv(gather_pages(pages["v"], table),
                          gather_pages(pages["v_scale"], table), q.dtype)
        if prefill:
            S = q.shape[1]
            return chunked_causal_attention(q, k, v, window=window,
                                            q_offset=kv_len[0] - S)
        return verify_attention(q, k, v, kv_len, window=window)
    if prefill:
        if ops.kernels_active():
            return ops.paged_prefill(q, pages["k"], pages["v"], table,
                                     kv_len, window=window)
        return paged_prefill_attention(q, pages["k"], pages["v"], table,
                                       kv_len, window=window)
    if ops.kernels_active():
        return ops.paged_verify(q, pages["k"], pages["v"], table, kv_len,
                                window=window)
    return paged_verify_attention(q, pages["k"], pages["v"], table, kv_len,
                                  window=window)


def attn_block_paged(p: Params, cfg: ModelConfig, x: jnp.ndarray, positions,
                     pages: Dict, table: jnp.ndarray, ln: jnp.ndarray,
                     *, tp_axis: Optional[str] = None,
                     prefill: bool = False, write: bool = True
                     ) -> Tuple[jnp.ndarray, Dict]:
    """Decode-mode attention block over one layer's page pool.

    ``pages``: {"k": (P, bs, h_kv, hd), "v": ...} — plus
    ``k_scale``/``v_scale`` (P, bs, h_kv) for int8 pools, in which case
    new lines quantize on write (``quantize_kv``) and attention reads
    the quantized pages (dequant fused into the kernel on TPU).
    ``ln``: (B,) valid lengths BEFORE this step. Writes the T new lines
    through the block table, then attends over the gathered pages — the
    same per-position math as ``attn_block``'s decode path (T >= 1
    verify included), so the paged cache changes where KV lives, never
    what attention computes.

    ``prefill``: chunked-admission mode — attention mirrors the dense
    prefill math (``chunked_causal_attention``) instead of the decode
    path, keeping chunk-prefilled activations byte-identical to one-shot
    dense prefill. ``write=False`` skips the page writes (a fully
    prefix-shared prompt re-derives its last-token logits from pages it
    must not touch).
    """
    B, S, _ = x.shape
    q, k, v = attn_qkv(p, cfg, x, positions)
    quantized = "k_scale" in pages
    if not write:
        new_pages = pages
    elif quantized:
        kq, ksc = quantize_kv(k)
        vq, vsc = quantize_kv(v)
        new_pages = {
            "k": write_pages(pages["k"], table, ln, kq),
            "v": write_pages(pages["v"], table, ln, vq),
            "k_scale": write_pages(pages["k_scale"], table, ln, ksc),
            "v_scale": write_pages(pages["v_scale"], table, ln, vsc),
        }
    else:
        new_pages = {"k": write_pages(pages["k"], table, ln, k),
                     "v": write_pages(pages["v"], table, ln, v)}
    out = _paged_attention(q, new_pages, table, ln + S,
                           window=cfg.attn_window, prefill=prefill)
    o = qmm(out.reshape(B, S, -1), p["wo"])
    if tp_axis:
        o = lax.psum(o, tp_axis)
    return o, new_pages


def mla_block_paged(p: Params, cfg: ModelConfig, x: jnp.ndarray, positions,
                    pages: Dict, table: jnp.ndarray, ln: jnp.ndarray,
                    *, tp_axis: Optional[str] = None,
                    prefill: bool = False, write: bool = True
                    ) -> Tuple[jnp.ndarray, Dict]:
    """MLA decode against paged latent storage (absorbed form).

    ``pages``: {"latent": (P, bs, r_kv + qk_rope_dim)}. Mirrors the
    absorbed decode branch of ``mla_block`` with the latent gathered
    through the block table instead of sliced from a dense cache.
    The S > 1 masking is already chunk-causal (position ``ln + t``
    attends at-or-before itself), so chunked admission reuses this path
    unchanged — ``prefill`` is accepted for signature parity and
    ``write=False`` skips the latent write (fully prefix-shared
    prompts).
    """
    del prefill
    B, S, d = x.shape
    H = cfg.n_heads
    r_kv = cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    scale = 1.0 / math.sqrt(dn + dr)

    q_lat = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q = (q_lat @ p["wq_b"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = x @ p["wkv_a"]
    latent = rms_norm(kv[..., :r_kv], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., r_kv:][:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0]
    lat_cat = jnp.concatenate([latent, k_rope], -1)

    lp = write_pages(pages["latent"], table, ln, lat_cat) if write \
        else pages["latent"]
    lc = gather_pages(lp, table)                      # (B, S_eff, r + dr)
    lat_all = lc[..., :r_kv].astype(x.dtype)
    rope_all = lc[..., r_kv:].astype(x.dtype)
    S_eff = lc.shape[1]
    pos_idx = jnp.arange(S_eff)
    qpos = ln[:, None] + jnp.arange(S)[None, :]       # (B, S)
    mask = pos_idx[None, None, :] <= qpos[:, :, None]  # (B, S, S_eff)

    wk = p["wk_b"].reshape(r_kv, H, dn)
    q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, wk)
    s_nope = jnp.einsum("bqhr,bsr->bhqs", q_abs, lat_all,
                        preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bqhd,bsd->bhqs", q_rope, rope_all,
                        preferred_element_type=jnp.float32)
    s_all = (s_nope + s_rope) * scale
    s_all = jnp.where(mask[:, None], s_all, -jnp.inf)
    pr = jax.nn.softmax(s_all, axis=-1)
    o_lat = jnp.einsum("bhqs,bsr->bqhr", pr, lat_all.astype(jnp.float32))
    wv = p["wv_b"].reshape(r_kv, H, dv)
    out = jnp.einsum("bqhr,rhv->bqhv", o_lat.astype(x.dtype), wv)

    o = qmm(out.reshape(B, S, H * dv), p["wo"])
    if tp_axis:
        o = lax.psum(o, tp_axis)
    return o, {"latent": lp}


def _full_attention(q, k, v):
    """Bidirectional full attention (whisper encoder / cross-attn)."""
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# --------------------------------------------------------------------------- #
#  MLA — multi-head latent attention (MiniCPM3 / DeepSeek-V2 style)
# --------------------------------------------------------------------------- #

def init_mla(cfg: ModelConfig, key, dtype) -> Params:
    d = cfg.d_model
    H = cfg.n_heads
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    s = 1.0 / math.sqrt(d)
    return {
        "wq_a": jax.random.normal(ks[0], (d, r_q), dtype) * s,
        "q_norm": jnp.ones((r_q,), dtype),
        "wq_b": jax.random.normal(ks[1], (r_q, H * (dn + dr)), dtype)
        / math.sqrt(r_q),
        "wkv_a": jax.random.normal(ks[2], (d, r_kv + dr), dtype) * s,
        "kv_norm": jnp.ones((r_kv,), dtype),
        "wk_b": jax.random.normal(ks[3], (r_kv, H * dn), dtype)
        / math.sqrt(r_kv),
        "wv_b": jax.random.normal(ks[4], (r_kv, H * dv), dtype)
        / math.sqrt(r_kv),
        "wo": jax.random.normal(ks[5], (H * dv, d), dtype)
        / math.sqrt(H * dv),
    }


def mla_block(p: Params, cfg: ModelConfig, x: jnp.ndarray, positions,
              *, cache: Optional[Dict] = None, decode: bool = False,
              tp_axis: Optional[str] = None,
              absorbed: bool = True) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """MLA attention. Cache holds the compressed latent (r_kv + rope dims).

    Decode uses the *absorbed* form by default (W_UK folded into the query,
    scores computed in latent space) — the serving-side optimization that
    keeps per-step FLOPs proportional to r_kv instead of H*(dn+dv).
    ``absorbed=False`` decodes via naive latent expansion (the paper-free
    baseline used in EXPERIMENTS §Perf).
    """
    B, S, d = x.shape
    H = cfg.n_heads
    r_kv = cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    scale = 1.0 / math.sqrt(dn + dr)

    q_lat = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q = (q_lat @ p["wq_b"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = x @ p["wkv_a"]                                  # (B, S, r_kv + dr)
    latent = rms_norm(kv[..., :r_kv], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., r_kv:][:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0]          # (B, S, dr)
    lat_cat = jnp.concatenate([latent, k_rope], -1)       # cache line

    new_cache = cache
    if decode:
        assert cache is not None
        lc, ln = cache["latent"], cache["len"]
        Smax = lc.shape[1]
        for t in range(S):                   # static, small (draft block)
            slot = jnp.minimum(ln + t, Smax - 1)
            lc = jax.vmap(lambda c, tt, i: lax.dynamic_update_slice(
                c, tt, (i, 0)))(lc, lat_cat[:, t:t + 1].astype(lc.dtype),
                                slot)
        new_cache = {"latent": lc, "len": ln + S}
        lat_all = lc[..., :r_kv].astype(x.dtype)          # (B, Smax, r)
        rope_all = lc[..., r_kv:].astype(x.dtype)         # (B, Smax, dr)
        # query t sits at absolute position ln + t; causal among drafts
        pos_idx = jnp.arange(Smax)
        qpos = ln[:, None] + jnp.arange(S)[None, :]       # (B, S)
        mask = pos_idx[None, None, :] <= qpos[:, :, None]  # (B, S, Smax)
        if absorbed:
            # fold W_UK: q_lat[h] = q_nope[h] @ wk_b[:, h]^T  -> (B,1,H,r)
            wk = p["wk_b"].reshape(r_kv, H, dn)
            q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, wk)
            s_nope = jnp.einsum("bqhr,bsr->bhqs", q_abs, lat_all,
                                preferred_element_type=jnp.float32)
            s_rope = jnp.einsum("bqhd,bsd->bhqs", q_rope, rope_all,
                                preferred_element_type=jnp.float32)
            s_all = (s_nope + s_rope) * scale
            s_all = jnp.where(mask[:, None], s_all, -jnp.inf)
            pr = jax.nn.softmax(s_all, axis=-1)
            # output in latent space, then expand with W_UV
            o_lat = jnp.einsum("bhqs,bsr->bqhr", pr, lat_all.astype(
                jnp.float32))
            wv = p["wv_b"].reshape(r_kv, H, dv)
            out = jnp.einsum("bqhr,rhv->bqhv", o_lat.astype(x.dtype), wv)
        else:
            k_nope = jnp.einsum("bsr,rhd->bshd", lat_all,
                                p["wk_b"].reshape(r_kv, H, dn))
            vv = jnp.einsum("bsr,rhv->bshv", lat_all,
                            p["wv_b"].reshape(r_kv, H, dv))
            kk = jnp.concatenate(
                [k_nope, jnp.broadcast_to(rope_all[:, :, None, :],
                                          (*k_nope.shape[:3], dr))], -1)
            qq = jnp.concatenate([q_nope, q_rope], -1)
            s_all = jnp.einsum("bqhd,bshd->bhqs", qq, kk,
                               preferred_element_type=jnp.float32) * scale
            s_all = jnp.where(mask[:, None], s_all, -jnp.inf)
            pr = jax.nn.softmax(s_all, axis=-1)
            out = jnp.einsum("bhqs,bshv->bqhv", pr, vv.astype(jnp.float32)
                             ).astype(x.dtype)
    else:
        k_nope = jnp.einsum("bsr,rhd->bshd", latent,
                            p["wk_b"].reshape(r_kv, H, dn))
        vv = jnp.einsum("bsr,rhv->bshv", latent,
                        p["wv_b"].reshape(r_kv, H, dv))
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (*k_nope.shape[:3], dr))], -1)
        qq = jnp.concatenate([q_nope, q_rope], -1)
        # pad V up to qk head dim so the flash oracle can run, slice after
        pad = (dn + dr) - dv
        v_p = jnp.pad(vv, ((0, 0), (0, 0), (0, 0), (0, pad))) if pad else vv
        out = chunked_causal_attention(qq, kk, v_p)[..., :dv]
        if cache is not None:
            Smax = cache["latent"].shape[1]
            lc = lat_cat[:, :Smax]
            if lc.shape[1] < Smax:
                lc = jnp.pad(lc, ((0, 0), (0, Smax - lc.shape[1]), (0, 0)))
            new_cache = {"latent": lc.astype(cache["latent"].dtype),
                         "len": cache["len"] + S}
    o = qmm(out.reshape(B, S, H * dv), p["wo"])
    if tp_axis:
        o = lax.psum(o, tp_axis)
    return o, new_cache


# --------------------------------------------------------------------------- #
#  FFN: gated GLU and MoE top-k with capacity dispatch
# --------------------------------------------------------------------------- #

def init_glu(cfg: ModelConfig, key, dtype, d_ff: Optional[int] = None
             ) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": jax.random.normal(k1, (d, f), dtype) / math.sqrt(d),
        "w_up": jax.random.normal(k2, (d, f), dtype) / math.sqrt(d),
        "w_down": jax.random.normal(k3, (f, d), dtype) / math.sqrt(f),
    }


def glu_ffn(p: Params, x: jnp.ndarray, tp_axis: Optional[str] = None
            ) -> jnp.ndarray:
    h = swish(qmm(x, p["w_gate"])) * qmm(x, p["w_up"])
    out = qmm(h, p["w_down"])
    if tp_axis:
        out = lax.psum(out, tp_axis)
    return out


def init_moe(cfg: ModelConfig, key, dtype) -> Params:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": jax.random.normal(k1, (d, E), dtype) / math.sqrt(d),
        "w_gate": jax.random.normal(k2, (E, d, f), dtype) / math.sqrt(d),
        "w_up": jax.random.normal(k3, (E, d, f), dtype) / math.sqrt(d),
        "w_down": jax.random.normal(k4, (E, f, d), dtype) / math.sqrt(f),
    }


def moe_ffn(p: Params, cfg: ModelConfig, x: jnp.ndarray,
            *, lossless: bool = False,
            tp_axis: Optional[str] = None) -> jnp.ndarray:
    """Top-k MoE with capacity-bounded sort-free dispatch.

    Tokens are scattered into per-expert capacity buckets (overflow
    dropped, standard practice), experts run as one batched matmul over
    (E, C, d), and outputs gather back weighted by router gates. FLOPs are
    ~ top_k * T * (3 d f) * capacity_factor — proportional to *active*
    parameters, not total (no dense-dispatch waste).

    ``lossless`` (or ``cfg.moe_capacity_factor is None``) sets capacity to
    T — an exact upper bound (a token contributes each expert at most
    once), so no token is ever dropped. Decode always runs lossless: T = B
    is small, and the extra dispatch rows are negligible next to streaming
    the expert weights.
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T_full = B * S
    # chunk the dispatch: the (E, C, d) capacity buffer scales with the
    # chunk, not the step — at 1M-token prefill an unchunked buffer costs
    # ~21 GiB/chip (found via dry-run memory_analysis). Per-chunk capacity
    # is standard practice and preserves losslessness when C = T_chunk.
    MAX_CHUNK = 65_536
    n_chunks = max(-(-T_full // MAX_CHUNK), 1)
    if S % n_chunks == 0 and n_chunks > 1:
        xs = x.reshape(B, n_chunks, S // n_chunks, d).transpose(1, 0, 2, 3)
        out = lax.map(
            lambda xc: moe_ffn(p, cfg, xc, lossless=lossless,
                               tp_axis=tp_axis), xs)
        return out.transpose(1, 0, 2, 3).reshape(B, S, d)
    T = T_full
    xt = x.reshape(T, d)
    logits = (xt @ p["router"]).astype(jnp.float32)         # (T, E)
    gates, idx = lax.top_k(jax.nn.softmax(logits, -1), K)   # (T, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    cf = cfg.moe_capacity_factor
    if lossless or cf is None:
        C = T
    else:
        C = min(max(int(K * T / E * cf), 1), T)
    constrain = _MOE_CONSTRAINT or (lambda t: t)
    flat_e = idx.reshape(-1)                                # (T*K,)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)         # (T*K, E)
    pos_in_e = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1    # (T*K,)
    keep = pos_in_e < C
    slot = jnp.where(keep, flat_e * C + pos_in_e, E * C)    # drop -> pad row

    buf = jnp.zeros((E * C + 1, d), x.dtype)
    x_rep = jnp.repeat(xt, K, axis=0)                       # (T*K, d)
    buf = buf.at[slot].set(x_rep)
    xe = constrain(buf[:E * C].reshape(E, C, d))

    h = constrain(
        swish(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]))
        * jnp.einsum("ecd,edf->ecf", xe, p["w_up"]))
    ye = constrain(jnp.einsum("ecf,efd->ecd", h, p["w_down"]))
    if tp_axis:
        ye = lax.psum(ye, tp_axis)

    ye_flat = jnp.concatenate([ye.reshape(E * C, d),
                               jnp.zeros((1, d), ye.dtype)], 0)
    y_tok = ye_flat[slot]                                    # (T*K, d)
    y = (y_tok.reshape(T, K, d)
         * gates.astype(y_tok.dtype)[..., None]).sum(1)
    return y.reshape(B, S, d)


# --------------------------------------------------------------------------- #
#  RG-LRU recurrent block (RecurrentGemma / Griffin)
# --------------------------------------------------------------------------- #

def init_rglru(cfg: ModelConfig, key, dtype) -> Params:
    d = cfg.d_model
    w = cfg.lru_width or d
    ks = jax.random.split(key, 4)
    # forget-rate init: a in (~0.9, ~0.999)
    lam = jnp.log(jnp.expm1(
        jnp.linspace(4.0, 9.0, w)))                     # softplus^-1 spread
    return {
        "w_x": jax.random.normal(ks[0], (d, w), dtype) / math.sqrt(d),
        "w_y": jax.random.normal(ks[1], (d, w), dtype) / math.sqrt(d),
        "conv_w": jax.random.normal(ks[2], (cfg.conv_width, w), dtype) * 0.1,
        "gate_i": jnp.zeros((w,), dtype),
        "gate_r": jnp.zeros((w,), dtype),
        "lambda": lam.astype(dtype),
        "w_out": jax.random.normal(ks[3], (w, d), dtype) / math.sqrt(w),
    }


def _causal_conv1d(x: jnp.ndarray, w: jnp.ndarray,
                   state: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv. x: (B, S, C), w: (K, C).

    Returns (y, new_state) where state is the trailing K-1 inputs.
    """
    K = w.shape[0]
    B, S, C = x.shape
    if state is None:
        state = jnp.zeros((B, K - 1, C), x.dtype)
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)  # (B, S+K-1, C)
    y = sum(xp[:, i:i + S] * w[i] for i in range(K))
    new_state = xp[:, -(K - 1):] if K > 1 else jnp.zeros((B, 0, C), x.dtype)
    return y, new_state


def rglru_block(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                *, cache: Optional[Dict] = None, decode: bool = False,
                tp_axis: Optional[str] = None
                ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Griffin recurrent block: conv + RG-LRU gated linear recurrence.

    cache: {"h": (B, w) recurrent state, "conv": (B, K-1, w)}.
    """
    B, S, d = x.shape
    w_dim = (cfg.lru_width or d)
    branch_y = swish(x @ p["w_y"])                          # gating branch
    u = x @ p["w_x"]
    conv_state = cache["conv"] if cache is not None else None
    u, new_conv = _causal_conv1d(u, p["conv_w"], conv_state)

    # RG-LRU
    c = 8.0
    i_gate = jax.nn.sigmoid(u * p["gate_i"])
    r_gate = jax.nn.sigmoid(u * p["gate_r"])
    log_a = -c * r_gate * jax.nn.softplus(p["lambda"])       # (B, S, w) <= 0
    a = jnp.exp(log_a)
    gated_x = u * i_gate
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6)) * gated_x

    h0 = cache["h"] if cache is not None else jnp.zeros((B, w_dim), x.dtype)
    if decode:
        assert S == 1
        h = a[:, 0] * h0.astype(a.dtype) + b[:, 0]
        y_seq = h[:, None]
    else:
        # associative scan: h_t = a_t h_{t-1} + b_t, with h_{-1} = h0
        def comb(l, r):
            al, bl = l
            ar, br = r
            return (al * ar, bl * ar + br)
        a_s, b_s = jax.lax.associative_scan(comb, (a, b), axis=1)
        y_seq = a_s * h0[:, None].astype(a.dtype) + b_s
        h = y_seq[:, -1]
    out = (y_seq.astype(x.dtype) * branch_y) @ p["w_out"]
    if tp_axis:
        out = lax.psum(out, tp_axis)
    new_cache = None
    if cache is not None:
        new_cache = {"h": h.astype(cache["h"].dtype), "conv": new_conv}
    return out, new_cache


# --------------------------------------------------------------------------- #
#  Mamba-2 SSD block
# --------------------------------------------------------------------------- #

def init_ssd(cfg: ModelConfig, key, dtype) -> Params:
    d, di, N = cfg.d_model, cfg.d_inner, cfg.ssm_state
    P = cfg.ssm_head_dim
    nh = di // P
    ks = jax.random.split(key, 4)
    return {
        "in_proj": jax.random.normal(
            ks[0], (d, 2 * di + 2 * N + nh), dtype) / math.sqrt(d),
        "conv_w": jax.random.normal(ks[1], (cfg.conv_width, di + 2 * N),
                                    dtype) * 0.1,
        "dt_bias": jnp.zeros((nh,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(dtype),
        "d_skip": jnp.ones((nh,), dtype),
        "norm": jnp.ones((di,), dtype),
        "out_proj": jax.random.normal(ks[3], (di, d), dtype) / math.sqrt(di),
    }


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                Bmat: jnp.ndarray, Cmat: jnp.ndarray,
                h0: Optional[jnp.ndarray] = None,
                chunk: int = 128
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """State-space-duality chunked scan (Mamba-2 alg. 1), pure jnp.

    x: (B, S, nh, P); dt: (B, S, nh); A: (nh,) < 0;
    Bmat/Cmat: (B, S, N); h0: (B, nh, P, N).
    Returns (y (B,S,nh,P), h_final).
    This function is also the oracle for the Pallas ``ssd_scan`` kernel.
    """
    Bsz, S, nh, P = x.shape
    N = Bmat.shape[-1]
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0)))
    Sp = nc * chunk
    dA = dt * A[None, None, :]                                # (B, Sp, nh) <=0
    xr = x.reshape(Bsz, nc, chunk, nh, P)
    dtr = dt.reshape(Bsz, nc, chunk, nh)
    dAr = dA.reshape(Bsz, nc, chunk, nh)
    Br = Bmat.reshape(Bsz, nc, chunk, N)
    Cr = Cmat.reshape(Bsz, nc, chunk, N)

    cum = jnp.cumsum(dAr, axis=2)                             # within chunk
    seg_total = cum[:, :, -1]                                 # (B, nc, nh)

    # --- intra-chunk (quadratic attention-like) --------------------------
    # L[t, s] = exp(cum[t] - cum[s]) for t >= s. Clamp the masked (t < s)
    # entries BEFORE exp: exp(+big) -> inf makes the where() gradient NaN.
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # (B,nc,t,s,nh)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = jnp.where(tri[None, None, :, :, None], diff, -jnp.inf)
    Lmat = jnp.exp(diff)
    GB = jnp.einsum("bcsn,bcsh,bcshp->bcshpn", Br, dtr, xr)   # dt-weighted
    scores = jnp.einsum("bctn,bcsn->bcts", Cr, Br)            # (B,nc,t,s)
    y_intra = jnp.einsum("bcts,bctsh,bcsh,bcshp->bcthp",
                         scores, Lmat, dtr, xr)

    # --- inter-chunk state recurrence -------------------------------------
    # chunk state: sum_s exp(cum_end - cum_s) dt_s x_s B_s^T
    decay_to_end = jnp.exp(seg_total[:, :, None, :] - cum)    # (B,nc,s,nh)
    chunk_state = jnp.einsum("bcsh,bcsh,bcshp,bcsn->bchpn",
                             decay_to_end, dtr, xr, Br)       # (B,nc,nh,P,N)

    def scan_fn(h, inp):
        st, tot = inp                                         # (B,nh,P,N),(B,nh)
        h_new = h * jnp.exp(tot)[:, :, None, None] + st
        return h_new, h

    if h0 is None:
        h0 = jnp.zeros((Bsz, nh, P, N), x.dtype)
    h_fin, h_prev = lax.scan(
        scan_fn, h0.astype(jnp.float32),
        (chunk_state.swapaxes(0, 1).astype(jnp.float32),
         seg_total.swapaxes(0, 1).astype(jnp.float32)))
    h_prev = h_prev.swapaxes(0, 1)                            # (B,nc,nh,P,N)

    y_inter = jnp.einsum("bctn,bcth,bchpn->bcthp",
                         Cr, jnp.exp(cum), h_prev.astype(cum.dtype))
    y = (y_intra + y_inter).reshape(Bsz, Sp, nh, P)[:, :S]
    return y.astype(x.dtype), h_fin.astype(x.dtype)


def ssd_block(p: Params, cfg: ModelConfig, x: jnp.ndarray,
              *, cache: Optional[Dict] = None, decode: bool = False,
              tp_axis: Optional[str] = None
              ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Mamba-2 block: in-proj -> conv -> SSD -> gated norm -> out-proj.

    cache: {"conv": (B, K-1, di+2N), "state": (B, nh, P, N)}.
    """
    B, S, d = x.shape
    di, N, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_head_dim
    nh = di // P
    zxbcdt = qmm(x, p["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * N], axis=-1)
    conv_state = cache["conv"] if cache is not None else None
    xbc, new_conv = _causal_conv1d(xbc, p["conv_w"], conv_state)
    xbc = swish(xbc)
    xs, Bmat, Cmat = jnp.split(xbc, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,nh)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))                  # (nh,)
    xh = xs.reshape(B, S, nh, P)

    h0 = cache["state"] if cache is not None else None
    if decode:
        assert S == 1 and cache is not None
        dA = jnp.exp(dt[:, 0] * A[None])                      # (B, nh)
        dBx = jnp.einsum("bh,bhp,bn->bhpn", dt[:, 0],
                         xh[:, 0].astype(jnp.float32),
                         Bmat[:, 0].astype(jnp.float32))
        h = h0.astype(jnp.float32) * dA[:, :, None, None] + dBx
        y = jnp.einsum("bn,bhpn->bhp", Cmat[:, 0].astype(jnp.float32), h)
        y = y[:, None].reshape(B, 1, nh, P).astype(x.dtype)
        h_fin = h.astype(x.dtype)
    else:
        y, h_fin = ssd_chunked(xh, dt, A, Bmat, Cmat,
                               h0=None if h0 is None else h0)
    y = y + xh * p["d_skip"][None, None, :, None].astype(x.dtype)
    y = y.reshape(B, S, di)
    y = rms_norm(y * swish(z), p["norm"], cfg.norm_eps)
    out = qmm(y, p["out_proj"])
    if tp_axis:
        out = lax.psum(out, tp_axis)
    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv.astype(cache["conv"].dtype),
                     "state": h_fin.astype(cache["state"].dtype)}
    return out, new_cache
