"""The plain reference: the published forward pass in float32.

Qwen2 and Mixtral decoder layers as their papers and Hugging Face
modelling code describe them: RMSNorm, q/k/v projections (with bias for
Qwen2), rotary embeddings (rotate-half, inverse frequencies
theta^(-2i/d)), grouped-query causal attention, SiLU-gated FFN, or for
Mixtral a softmax router whose top-k gates are renormalised and weight
the chosen experts' SiLU-gated FFNs. Matrix products run at the highest
precision. It reads the benchmark's weight tree and imports nothing of
the serving program.

Memory: one layer (for MoE one expert) is upcast at a time, attention
runs over blocks of queries, and the output head over blocks of the
vocabulary, so the reference fits beside the bf16 weights on one chip.

``mode="fp8"`` is the control: every operand of every matrix product is
rounded to float8 e4m3 with a per-tensor scale, the precision one step
below the bf16 the configurations state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .model_spec import Dims

F32 = jnp.float32
Q_BLOCK = 512
V_BLOCK = 16384


def _round(x, mode):
    if mode != "fp8":
        return x.astype(F32)
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, b, mode):
    return jnp.matmul(_round(a, mode), _round(b, mode),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos[:, :, None, None].astype(F32) * inv          # (B,S,1,d/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer_slice(tree, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                        tree)


@partial(jax.jit, static_argnames=("dm", "mode"))
def _attention(blocks, i, x, dm: Dims, mode):
    """x + attention(x) for layer ``i``; returns also the FFN's input."""
    p = _layer_slice(blocks, i)
    a = p["attn"]
    B, S, _ = x.shape
    H, hk, hd = dm.heads, dm.kv_heads, dm.head_dim
    h = _rms(x, p["attn_norm"], dm.norm_eps)
    q, k, v = (_mm(h, a[w], mode) for w in ("wq", "wk", "wv"))
    if dm.qkv_bias:
        q, k, v = q + a["bq"].astype(F32), k + a["bk"].astype(F32), \
            v + a["bv"].astype(F32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    q = _rope(q.reshape(B, S, H, hd), pos, dm.rope_theta)
    k = _rope(k.reshape(B, S, hk, hd), pos, dm.rope_theta)
    v = v.reshape(B, S, hk, hd)
    rep = H // hk
    k = jnp.repeat(k, rep, axis=2)           # query head j reads kv head j//rep
    v = jnp.repeat(v, rep, axis=2)
    kpos = jnp.arange(S)
    outs = []
    for lo in range(0, S, Q_BLOCK):
        qb = q[:, lo:lo + Q_BLOCK]
        qpos = kpos[lo:lo + Q_BLOCK]
        s = jnp.einsum("bqhd,bkhd->bhqk", _round(qb, mode), _round(k, mode),
                       precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
        mask = kpos[None, :] <= qpos[:, None]
        if dm.window:
            mask &= kpos[None, :] > qpos[:, None] - dm.window
        s = jnp.where(mask, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", _round(w, mode),
                               _round(v, mode),
                               precision=jax.lax.Precision.HIGHEST))
    o = jnp.concatenate(outs, 1).reshape(B, S, H * hd)
    x = x + _mm(o, a["wo"], mode)
    return x, _rms(x, p["ffn_norm"], dm.norm_eps)


def _glu(h, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(h, wg, mode)) * _mm(h, wu, mode), wd, mode)


@partial(jax.jit, static_argnames=("mode",))
def _dense_ffn(blocks, i, x, h, mode):
    f = _layer_slice(blocks["ffn"], i)
    return x + _glu(h, f["w_gate"], f["w_up"], f["w_down"], mode)


@partial(jax.jit, static_argnames=("dm", "mode"))
def _router(blocks, i, h, dm: Dims, mode):
    """(B, S, E) gate of every expert: the renormalised top-k softmax
    probabilities, zero for the experts a token does not use."""
    r = jax.lax.dynamic_index_in_dim(blocks["moe"]["router"], i, 0, False)
    probs = jax.nn.softmax(_mm(h, r, mode), -1)
    top, idx = jax.lax.top_k(probs, dm.top_k)
    top = top / top.sum(-1, keepdims=True)
    return (jax.nn.one_hot(idx, dm.experts, dtype=F32) * top[..., None]
            ).sum(-2)


@partial(jax.jit, static_argnames=("mode",))
def _expert(blocks, i, e, x, h, gate, mode):
    m = blocks["moe"]
    w = [jax.lax.dynamic_index_in_dim(
        jax.lax.dynamic_index_in_dim(m[n], i, 0, False), e, 0, False)
        for n in ("w_gate", "w_up", "w_down")]
    g = jax.lax.dynamic_index_in_dim(gate, e, 2, False)[..., None]
    return x + g * _glu(h, *w, mode)


@partial(jax.jit, static_argnames=("dm",))
def _embed(params, tokens, dm: Dims):
    return jnp.take(params["embed"], tokens, axis=0).astype(F32)


@partial(jax.jit, static_argnames=("dm", "mode"))
def _final(params, x, dm: Dims, mode):
    return _rms(x, params["final_norm"], dm.norm_eps)


def hidden(params, dm: Dims, tokens, mode: str = "f32"):
    """(B, S) tokens -> (B, S, d) final-normed hidden states."""
    blocks = params["blocks"]
    x = _embed(params, jnp.asarray(tokens), dm)
    for i in range(dm.layers):
        x, h = _attention(blocks, i, x, dm, mode)
        if dm.experts:
            gate = _router(blocks, i, h, dm, mode)
            for e in range(dm.experts):
                x = _expert(blocks, i, e, x, h, gate, mode)
        else:
            x = _dense_ffn(blocks, i, x, h, mode)
    return _final(params, x, dm, mode)


@partial(jax.jit, static_argnames=("mode",))
def _head_block(h, w, mode):
    return _mm(h, w, mode)


def logit_stats(params, h, targets):
    """Per position: the largest logit and the logit of ``targets``
    (both (B, S)), over the whole vocabulary in blocks."""
    w = params["unembed"]
    best = at = None
    tg = jnp.asarray(targets)
    for lo in range(0, w.shape[1], V_BLOCK):
        lg = _head_block(h, w[:, lo:lo + V_BLOCK], "f32")
        m = lg.max(-1)
        best = m if best is None else jnp.maximum(best, m)
        inside = (tg >= lo) & (tg < lo + lg.shape[-1])
        got = jnp.take_along_axis(lg, jnp.clip(tg - lo, 0, lg.shape[-1] - 1)
                                  [..., None], -1)[..., 0]
        at = jnp.where(inside, got, -jnp.inf if at is None else at)
    return np.asarray(best), np.asarray(at)


def argmax_tokens(params, h, mode: str):
    """The token each position puts first, computed in ``mode``."""
    w = params["unembed"]
    best = arg = None
    for lo in range(0, w.shape[1], V_BLOCK):
        lg = _head_block(h, w[:, lo:lo + V_BLOCK], mode)
        m, a = lg.max(-1), lg.argmax(-1) + lo
        if best is None:
            best, arg = m, a
        else:
            arg = jnp.where(m > best, a, arg)
            best = jnp.maximum(best, m)
    return np.asarray(arg)


def pack(seqs, length: int) -> np.ndarray:
    """Right-pad token sequences to one fixed length (causal attention
    leaves earlier positions untouched), so every run uses one shape."""
    out = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        out[i, :len(s)] = s
    return out


def served_gaps(params, dm: Dims, samples, length: int,
                control: bool = False):
    """Per served token of ``samples`` (``[(prompt, served tokens)]``):
    the gap by which its logit lies below the reference's best at its
    position. With ``control``, also the same gap for the token the fp8
    computation puts first at each of those positions. Returns
    ``(gaps, control gaps or None)``, 1-D arrays."""
    seqs, targets, rows = [], np.zeros((len(samples), length), np.int64), []
    for b, (prompt, served) in enumerate(samples):
        S, n = len(prompt), len(served)
        seqs.append(np.concatenate([prompt, np.asarray(served[:-1], np.int32)]))
        targets[b, S - 1:S - 1 + n] = served
        rows.append((b, S - 1, S - 1 + n))
    toks = pack(seqs, length)
    h = hidden(params, dm, toks)

    def gaps_of(tg):
        best, at = logit_stats(params, h, tg)
        return np.concatenate([best[b, lo:hi] - at[b, lo:hi]
                               for b, lo, hi in rows])

    ctrl = None
    if control:
        hc = hidden(params, dm, toks, "fp8")
        tc = argmax_tokens(params, hc, "fp8")
        del hc
        ctrl = gaps_of(tc)
    return gaps_of(targets), ctrl


def statistic(gaps: np.ndarray, name: str) -> float:
    """``max``, or ``p<q>``: the q-th percentile (nearest rank)."""
    if not len(gaps):
        return float("inf")
    if name == "max":
        return float(gaps.max())
    v = np.sort(gaps)
    q = float(name[1:]) / 100.0
    return float(v[min(max(int(np.ceil(q * len(v))) - 1, 0), len(v) - 1)])
