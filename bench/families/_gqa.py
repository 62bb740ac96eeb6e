"""The grouped-query decoder that Qwen2 and Mixtral share, as one model
family: its sizes, weight leaves, program config, float32 reference and
work counts. ``qwen2.py`` and ``mixtral.py`` name it for their
``model_type``; the configuration's keys choose QKV bias, sliding
window and a dense or sparse-expert FFN.

The reference is the published forward pass in float32, as the papers
and Hugging Face modelling code describe it: RMSNorm, q/k/v projections
(with bias for Qwen2), rotary embeddings (rotate-half, inverse
frequencies theta^(-2i/d)), grouped-query causal attention, SiLU-gated
FFN, or for Mixtral a softmax router whose top-k gates are renormalised
and weight the chosen experts' SiLU-gated FFNs. Matrix products run at
the highest precision. It reads the benchmark's weight tree and imports
nothing of the serving program. One layer (for MoE one expert) is
upcast at a time and attention runs over blocks of queries, so the
reference fits beside the bf16 weights on one chip.
"""
from __future__ import annotations

import dataclasses
import json
import math
from functools import partial
from pathlib import Path
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import F32, layer_slice, mm, operand, rms
from bench.work import BF16, Work, active_experts

Q_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    model_type: str
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    experts: int = 0                # 0: dense FFN
    top_k: int = 0
    qkv_bias: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    window: int = 0                 # 0: full attention

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        """From a configuration file's Hugging Face keys."""
        heads = c["num_attention_heads"]
        window = 0
        if c.get("sliding_window") and c.get("use_sliding_window", True):
            window = int(c["sliding_window"])
        return cls(name=c["name"], model_type=c["model_type"],
                   layers=c["num_hidden_layers"],
                   d=c["hidden_size"], heads=heads,
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // heads,
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   experts=c.get("num_local_experts", 0),
                   top_k=c.get("num_experts_per_tok", 0),
                   qkv_bias=bool(c.get("qkv_bias", False)),
                   norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
                   window=window)

    @classmethod
    def load(cls, path) -> "Dims":
        return cls.from_config(json.loads(Path(path).read_text()))

    def scaled(self, **kw) -> "Dims":
        return dataclasses.replace(self, **kw)

    # -- bytes ------------------------------------------------------------- #

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 K and V over every layer."""
        return self.layers * 2 * self.kv_heads * self.head_dim * 2

    @property
    def layer_params(self) -> int:
        d, H, hk, hd, f = self.d, self.heads, self.kv_heads, self.head_dim, \
            self.d_ff
        attn = d * (H + 2 * hk) * hd + H * hd * d
        if self.qkv_bias:
            attn += (H + 2 * hk) * hd
        ffn = 3 * d * f * max(self.experts, 1)
        if self.experts:
            ffn += d * self.experts
        return attn + ffn + 2 * d

    @property
    def params(self) -> int:
        return self.layers * self.layer_params + 2 * self.vocab * self.d \
            + self.d


def dims(config: dict) -> Dims:
    return Dims.from_config(config)


# -- weights ---------------------------------------------------------------- #

def leaf_shapes(dm: Dims) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """``path -> (shape, kind, scale)`` (``weights.make_weights``)."""
    d, L, H, hk, hd = dm.d, dm.layers, dm.heads, dm.kv_heads, dm.head_dim
    f, V, E = dm.d_ff, dm.vocab, dm.experts
    s = {
        "embed": ((V, d), "normal", 0.02),
        "final_norm": ((d,), "norm", 0.05),
        "unembed": ((d, V), "normal", 1 / math.sqrt(d)),
        "blocks/attn_norm": ((L, d), "norm", 0.05),
        "blocks/ffn_norm": ((L, d), "norm", 0.05),
        "blocks/attn/wq": ((L, d, H * hd), "normal", 1 / math.sqrt(d)),
        "blocks/attn/wk": ((L, d, hk * hd), "normal", 1 / math.sqrt(d)),
        "blocks/attn/wv": ((L, d, hk * hd), "normal", 1 / math.sqrt(d)),
        "blocks/attn/wo": ((L, H * hd, d), "normal", 1 / math.sqrt(H * hd)),
    }
    if dm.qkv_bias:
        s["blocks/attn/bq"] = ((L, H * hd), "bias", 0.1)
        s["blocks/attn/bk"] = ((L, hk * hd), "bias", 0.1)
        s["blocks/attn/bv"] = ((L, hk * hd), "bias", 0.1)
    if E:
        s["blocks/moe/router"] = ((L, d, E), "normal", 1 / math.sqrt(d))
        s["blocks/moe/w_gate"] = ((L, E, d, f), "normal", 1 / math.sqrt(d))
        s["blocks/moe/w_up"] = ((L, E, d, f), "normal", 1 / math.sqrt(d))
        s["blocks/moe/w_down"] = ((L, E, f, d), "normal", 1 / math.sqrt(f))
    else:
        s["blocks/ffn/w_gate"] = ((L, d, f), "normal", 1 / math.sqrt(d))
        s["blocks/ffn/w_up"] = ((L, d, f), "normal", 1 / math.sqrt(d))
        s["blocks/ffn/w_down"] = ((L, f, d), "normal", 1 / math.sqrt(f))
    return s


# -- the serving program's config ------------------------------------------- #

def program_config(dm: Dims):
    """The serving program's config for these sizes."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=dm.name, family="moe" if dm.experts else "dense",
        n_layers=dm.layers, d_model=dm.d, n_heads=dm.heads,
        kv_heads=dm.kv_heads, head_dim=dm.head_dim, d_ff=dm.d_ff,
        vocab=dm.vocab, qkv_bias=dm.qkv_bias, norm_eps=dm.norm_eps,
        rope_theta=dm.rope_theta, n_experts=dm.experts, top_k=dm.top_k,
        moe_capacity_factor=None, attn_window=dm.window or None)


# -- the reference ---------------------------------------------------------- #

def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos[:, :, None, None].astype(F32) * inv          # (B,S,1,d/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@partial(jax.jit, static_argnames=("dm", "mode"))
def _attention(blocks, i, x, dm: Dims, mode):
    """x + attention(x) for layer ``i``; returns also the FFN's input."""
    p = layer_slice(blocks, i)
    a = p["attn"]
    B, S, _ = x.shape
    H, hk, hd = dm.heads, dm.kv_heads, dm.head_dim
    h = rms(x, p["attn_norm"], dm.norm_eps)
    q, k, v = (mm(h, a[w], mode) for w in ("wq", "wk", "wv"))
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
        s = jnp.einsum("bqhd,bkhd->bhqk", operand(qb, mode),
                       operand(k, mode),
                       precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
        mask = kpos[None, :] <= qpos[:, None]
        if dm.window:
            mask &= kpos[None, :] > qpos[:, None] - dm.window
        s = jnp.where(mask, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", operand(w, mode),
                               operand(v, mode),
                               precision=jax.lax.Precision.HIGHEST))
    o = jnp.concatenate(outs, 1).reshape(B, S, H * hd)
    x = x + mm(o, a["wo"], mode)
    return x, rms(x, p["ffn_norm"], dm.norm_eps)


def _glu(h, wg, wu, wd, mode):
    return mm(jax.nn.silu(mm(h, wg, mode)) * mm(h, wu, mode), wd, mode)


@partial(jax.jit, static_argnames=("mode",))
def _dense_ffn(blocks, i, x, h, mode):
    f = layer_slice(blocks["ffn"], i)
    return x + _glu(h, f["w_gate"], f["w_up"], f["w_down"], mode)


@partial(jax.jit, static_argnames=("dm", "mode"))
def _router(blocks, i, h, dm: Dims, mode):
    """(B, S, E) gate of every expert: the renormalised top-k softmax
    probabilities, zero for the experts a token does not use."""
    r = jax.lax.dynamic_index_in_dim(blocks["moe"]["router"], i, 0, False)
    probs = jax.nn.softmax(mm(h, r, mode), -1)
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
    return rms(x, params["final_norm"], dm.norm_eps)


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


# -- work: attention kernels (one layer) ------------------------------------ #

def paged_verify(dm: Dims, lengths: Sequence[int]) -> Work:
    """One decode position per sequence against its live cache.
    ``lengths`` counts the positions each attends to (the new one
    included). q and out once; K and V of every live position once."""
    H, hk, hd = dm.heads, dm.kv_heads, dm.head_dim
    n = sum(lengths)
    flops = 4.0 * H * hd * n                        # QK^T and PV
    byts = BF16 * (2 * hk * hd * n + 2 * len(lengths) * H * hd)
    return Work(flops, byts)


def paged_prefill(dm: Dims, prefix: int, chunk: int) -> Work:
    """A chunk of ``chunk`` queries after ``prefix`` cached positions,
    causal: query t sees prefix + t + 1 positions."""
    H, hk, hd = dm.heads, dm.kv_heads, dm.head_dim
    pairs = chunk * prefix + chunk * (chunk + 1) / 2
    flops = 4.0 * H * hd * pairs
    byts = BF16 * (2 * hk * hd * (prefix + chunk) + 2 * chunk * H * hd)
    return Work(flops, byts)


#: each attention kernel's trace name -> its per-layer work
kernels = {"paged_verify": paged_verify, "paged_prefill": paged_prefill}


# -- work: whole steps ------------------------------------------------------ #

def _dense_flops_per_token(dm: Dims) -> float:
    """Matrix-product operations per token outside attention scores,
    over all layers and the output head (top-k experts only)."""
    d, H, hk, hd, f = dm.d, dm.heads, dm.kv_heads, dm.head_dim, dm.d_ff
    proj = d * (H + 2 * hk) * hd + H * hd * d
    ffn = 3 * d * f * (dm.top_k if dm.experts else 1)
    router = d * dm.experts
    return 2.0 * (dm.layers * (proj + ffn + router) + d * dm.vocab)


def _weight_bytes(dm: Dims, tokens: int) -> float:
    """Weights a step of ``tokens`` tokens reads once: every dense
    weight, the experts it routes to, the output head and the tokens'
    embedding rows."""
    d, H, hk, hd, f = dm.d, dm.heads, dm.kv_heads, dm.head_dim, dm.d_ff
    proj = d * (H + 2 * hk) * hd + H * hd * d
    if dm.experts:
        ffn = 3 * d * f * active_experts(dm, tokens) + d * dm.experts
    else:
        ffn = 3 * d * f
    return BF16 * (dm.layers * (proj + ffn) + d * dm.vocab + tokens * d)


def decode_step(dm: Dims, lengths: Sequence[int]) -> Work:
    """One decode step of the live sequences (``lengths`` as for
    ``paged_verify``): weights once, attention per layer, logits out."""
    B = len(lengths)
    attn = paged_verify(dm, lengths).scale(dm.layers)
    kv_write = BF16 * dm.layers * 2 * dm.kv_heads * dm.head_dim * B
    logits = BF16 * B * dm.vocab
    return Work(_dense_flops_per_token(dm) * B,
                _weight_bytes(dm, B) + kv_write + logits) + attn


def prefill_chunk(dm: Dims, prefix: int, chunk: int) -> Work:
    """One prefill chunk of one sequence, with its full logits."""
    attn = paged_prefill(dm, prefix, chunk).scale(dm.layers)
    kv_write = BF16 * dm.layers * 2 * dm.kv_heads * dm.head_dim * chunk
    logits = BF16 * chunk * dm.vocab
    return Work(_dense_flops_per_token(dm) * chunk,
                _weight_bytes(dm, chunk) + kv_write + logits) + attn
