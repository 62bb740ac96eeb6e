"""Random weights for a configuration, made on the device from the seed.

One jitted call makes every leaf in the type it is served in (bf16), in
the parameter layout the serving program reads. The configuration's
model family lists the leaves (``leaf_shapes``: ``path -> (shape, kind,
scale)``, paths '/'-joined keys of the parameter tree): kind ``normal``
draws N(0, scale), ``norm`` draws 1 + N(0, scale) (an RMSNorm gain),
``bias`` N(0, scale). Large leaves are made a layer (and an expert) at
a time inside that call, so the generator's float32 temporaries stay
one piece large. The reference reads the same tree by its leaf names;
nothing here comes from the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import spec

#: largest piece made in one go, in elements (its float32 temporary)
PIECE = 1 << 26


def key_for(seed: int, stream: int = 0):
    """A PRNG key from any whole seed: its low and high 32 bits are both
    folded in, so seeds past 2**31 neither overflow nor collide."""
    seed = int(seed)
    k = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    k = jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(k, stream)


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


def make_weights(dm, seed: int, dtype=jnp.bfloat16):
    """The whole parameter tree, made on the default device in one call:
    one key per leaf of ``dm``'s family's ``leaf_shapes``, split in the
    order of their sorted paths."""
    leaves = spec.family(dm.model_type).leaf_shapes(dm)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        tree: dict = {}
        for k, (path, (shape, kind, scale)) in zip(keys,
                                                   sorted(leaves.items())):
            node = tree
            *parents, name = path.split("/")
            for p in parents:
                node = node.setdefault(p, {})
            node[name] = _leaf(k, shape, kind, scale, dtype)
        return tree

    return jax.block_until_ready(jax.jit(build)(key_for(seed)))
