"""Random weights for a configuration, made on the device from the seed.

One jitted call makes every leaf in the type it is served in (bf16), in
the parameter layout the serving program reads. Large leaves are made a
layer (and an expert) at a time inside that call, so the generator's
float32 temporaries stay one piece large. The reference reads the same
tree by its leaf names; nothing here comes from the program.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .model_spec import Dims

#: largest piece made in one go, in elements (its float32 temporary)
PIECE = 1 << 26


def key_for(seed: int, stream: int = 0):
    """A PRNG key from any whole seed: its low and high 32 bits are both
    folded in, so seeds past 2**31 neither overflow nor collide."""
    seed = int(seed)
    k = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    k = jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(k, stream)


def leaf_shapes(dm: Dims) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """``path -> (shape, kind, scale)``: kind ``normal`` draws N(0, scale),
    ``norm`` draws 1 + N(0, scale) (an RMSNorm gain), ``bias`` N(0, scale).
    Paths are '/'-joined keys of the parameter tree."""
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


def _leaf(key, shape, kind, scale, dtype):
    lead = 0                       # leading axes made one piece at a time
    while math.prod(shape[lead:]) > PIECE and lead < len(shape) - 2:
        lead += 1
    outer, inner = shape[:lead], shape[lead:]

    def piece(k):
        x = jax.random.normal(k, inner, jnp.float32) * scale
        return (x + 1.0 if kind == "norm" else x).astype(dtype)

    if not outer:
        return piece(key)
    keys = jax.random.split(key, math.prod(outer))
    return jax.lax.map(piece, keys).reshape(shape)


def make_weights(dm: Dims, seed: int, dtype=jnp.bfloat16):
    """The whole parameter tree, made on the default device in one call."""
    spec = leaf_shapes(dm)

    def build(key):
        keys = jax.random.split(key, len(spec))
        tree: dict = {}
        for k, (path, (shape, kind, scale)) in zip(keys, sorted(spec.items())):
            node = tree
            *parents, name = path.split("/")
            for p in parents:
                node = node.setdefault(p, {})
            node[name] = _leaf(k, shape, kind, scale, dtype)
        return tree

    return jax.block_until_ready(jax.jit(build)(key_for(seed)))
