"""Model assembly: init / forward / prefill / decode for every family.

All functions are pure and pjit-friendly; the explicit-collective ring
runtime passes ``tp_axis`` through to the layer library.

Parameter layout (scan-compatible — every per-layer leaf is stacked on a
leading layer axis):

  dense/moe/vlm : params["blocks"][leaf] : (L, ...)
  ssm           : params["blocks"][leaf] : (L, ...)
  hybrid        : params["groups"][bi][leaf] : (G, ...), params["tail"] : (T, ...)
  audio         : params["enc_blocks"], params["dec_blocks"] : (L, ...)

Cache layout mirrors the parameter stacking (leading layer axis), with a
single shared ``len`` (B,) counter.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig
from . import layers as ll

Params = Dict[str, Any]

#: Optional activation-sharding hook (set by the distributed runtime at
#: trace time). GSPMD otherwise propagates the embedding table's layout
#: into the activations — batch-replicated, d-sharded — which costs
#: hundreds of GB at scale (see EXPERIMENTS §Perf iteration log).
_ACT_CONSTRAINT = None


def set_activation_constraint(fn) -> None:
    global _ACT_CONSTRAINT
    _ACT_CONSTRAINT = fn


def _constrain(x):
    if _ACT_CONSTRAINT is not None and getattr(x, "ndim", 0) == 3:
        return _ACT_CONSTRAINT(x)
    return x


# --------------------------------------------------------------------------- #
#  init
# --------------------------------------------------------------------------- #

def _stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def _init_dense_block(cfg: ModelConfig, dtype):
    def init(key):
        k1, k2 = jax.random.split(key)
        p = {"attn_norm": jnp.ones((cfg.d_model,), dtype),
             "ffn_norm": jnp.ones((cfg.d_model,), dtype)}
        if cfg.mla:
            p["attn"] = ll.init_mla(cfg, k1, dtype)
        else:
            p["attn"] = ll.init_attn(cfg, k1, dtype)
        if cfg.n_experts:
            p["moe"] = ll.init_moe(cfg, k2, dtype)
        else:
            p["ffn"] = ll.init_glu(cfg, k2, dtype)
        return p
    return init


def _init_rglru_block(cfg: ModelConfig, dtype):
    def init(key):
        k1, k2 = jax.random.split(key)
        return {"mix_norm": jnp.ones((cfg.d_model,), dtype),
                "ffn_norm": jnp.ones((cfg.d_model,), dtype),
                "rglru": ll.init_rglru(cfg, k1, dtype),
                "ffn": ll.init_glu(cfg, k2, dtype)}
    return init


def _init_ssd_block(cfg: ModelConfig, dtype):
    def init(key):
        return {"norm": jnp.ones((cfg.d_model,), dtype),
                "ssd": ll.init_ssd(cfg, key, dtype)}
    return init


def _init_enc_block(cfg: ModelConfig, dtype):
    def init(key):
        k1, k2 = jax.random.split(key)
        return {"attn_norm": jnp.ones((cfg.d_model,), dtype),
                "ffn_norm": jnp.ones((cfg.d_model,), dtype),
                "attn": ll.init_attn(cfg, k1, dtype),
                "ffn": ll.init_glu(cfg, k2, dtype)}
    return init


def _init_dec_block(cfg: ModelConfig, dtype):
    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"attn_norm": jnp.ones((cfg.d_model,), dtype),
                "cross_norm": jnp.ones((cfg.d_model,), dtype),
                "ffn_norm": jnp.ones((cfg.d_model,), dtype),
                "attn": ll.init_attn(cfg, k1, dtype),
                "cross": ll.init_attn(cfg, k2, dtype),
                "ffn": ll.init_glu(cfg, k3, dtype)}
    return init


def hybrid_layout(cfg: ModelConfig) -> Tuple[int, int]:
    """(n_groups, n_tail) for hybrid block_pattern archs."""
    g = len(cfg.block_pattern)
    return cfg.n_layers // g, cfg.n_layers % g


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 8)
    params: Params = {
        "embed": jax.random.normal(ks[0], (cfg.vocab, cfg.d_model), dtype)
        * 0.02,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = jax.random.normal(
            ks[1], (cfg.d_model, cfg.vocab), dtype) / math.sqrt(cfg.d_model)

    if cfg.family in ("dense", "moe", "vlm"):
        params["blocks"] = _stack_init(_init_dense_block(cfg, dtype), ks[2],
                                       cfg.n_layers)
    elif cfg.family == "ssm":
        params["blocks"] = _stack_init(_init_ssd_block(cfg, dtype), ks[2],
                                       cfg.n_layers)
    elif cfg.family == "hybrid":
        G, T = hybrid_layout(cfg)
        groups = {}
        for bi, kind in enumerate(cfg.block_pattern):
            init = (_init_rglru_block(cfg, dtype) if kind == "rglru"
                    else _init_dense_block(cfg, dtype))
            groups[f"b{bi}"] = _stack_init(init, ks[3 + bi], G)
        params["groups"] = groups
        if T:
            # tail layers follow the pattern prefix (rglru for r-gemma)
            tail_kind = cfg.block_pattern[0]
            init = (_init_rglru_block(cfg, dtype) if tail_kind == "rglru"
                    else _init_dense_block(cfg, dtype))
            params["tail"] = _stack_init(init, ks[6], T)
    elif cfg.family == "audio":
        params["enc_blocks"] = _stack_init(_init_enc_block(cfg, dtype),
                                           ks[2], cfg.n_enc_layers)
        params["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
        params["dec_blocks"] = _stack_init(_init_dec_block(cfg, dtype),
                                           ks[3], cfg.n_layers)
    else:
        raise ValueError(cfg.family)
    return params


# --------------------------------------------------------------------------- #
#  caches
# --------------------------------------------------------------------------- #

def _kv_cache(cfg: ModelConfig, n: int, B: int, S: int, dtype):
    hk, hd = max(cfg.kv_heads, 1), cfg.head_dim
    if cfg.attn_window:
        S = min(S, cfg.attn_window)
    if cfg.kv_dtype == "int8":
        return {"k": jnp.zeros((n, B, S, hk, hd), jnp.int8),
                "v": jnp.zeros((n, B, S, hk, hd), jnp.int8),
                "k_scale": jnp.zeros((n, B, S, hk), jnp.bfloat16),
                "v_scale": jnp.zeros((n, B, S, hk), jnp.bfloat16)}
    return {"k": jnp.zeros((n, B, S, hk, hd), dtype),
            "v": jnp.zeros((n, B, S, hk, hd), dtype)}


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    B, L = batch, cfg.n_layers
    cache: Dict[str, Any] = {"len": jnp.zeros((B,), jnp.int32)}
    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.mla:
            cache["layers"] = {"latent": jnp.zeros(
                (L, B, max_len, cfg.kv_lora_rank + cfg.qk_rope_dim), dtype)}
        else:
            cache["layers"] = _kv_cache(cfg, L, B, max_len, dtype)
    elif cfg.family == "ssm":
        di, N, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_head_dim
        nh = di // P
        cache["layers"] = {
            "conv": jnp.zeros((L, B, cfg.conv_width - 1, di + 2 * N), dtype),
            "state": jnp.zeros((L, B, nh, P, N), dtype)}
    elif cfg.family == "hybrid":
        G, T = hybrid_layout(cfg)
        w = cfg.lru_width or cfg.d_model
        groups = {}
        for bi, kind in enumerate(cfg.block_pattern):
            if kind == "rglru":
                groups[f"b{bi}"] = {
                    "h": jnp.zeros((G, B, w), dtype),
                    "conv": jnp.zeros((G, B, cfg.conv_width - 1, w), dtype)}
            else:
                groups[f"b{bi}"] = _kv_cache(cfg, G, B, max_len, dtype)
        cache["groups"] = groups
        if T:
            cache["tail"] = {
                "h": jnp.zeros((T, B, w), dtype),
                "conv": jnp.zeros((T, B, cfg.conv_width - 1, w), dtype)}
    elif cfg.family == "audio":
        S = min(max_len, cfg.max_decode_len or max_len)
        cache["layers"] = _kv_cache(cfg, L, B, S, dtype)
        hk, hd = cfg.kv_heads, cfg.head_dim
        F = cfg.n_frontend_tokens
        cache["cross_k"] = jnp.zeros((L, B, F, hk, hd), dtype)
        cache["cross_v"] = jnp.zeros((L, B, F, hk, hd), dtype)
    return cache


# --------------------------------------------------------------------------- #
#  block application
# --------------------------------------------------------------------------- #

def _dense_block(cfg: ModelConfig, p, x, positions, cache, ln, *,
                 decode: bool, tp_axis: Optional[str]):
    h_in = ll.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    c = None if cache is None else {**cache, "len": ln}
    if cfg.mla:
        h, nc = ll.mla_block(p["attn"], cfg, h_in, positions, cache=c,
                             decode=decode, tp_axis=tp_axis)
    else:
        h, nc = ll.attn_block(p["attn"], cfg, h_in, positions, cache=c,
                              decode=decode, tp_axis=tp_axis)
    x = x + h
    g = ll.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    if cfg.n_experts:
        x = x + ll.moe_ffn(p["moe"], cfg, g, lossless=decode,
                           tp_axis=tp_axis)
    else:
        x = x + ll.glu_ffn(p["ffn"], g, tp_axis)
    if nc is not None:
        nc.pop("len", None)
    return x, nc


def _rglru_full_block(cfg: ModelConfig, p, x, cache, *, decode: bool,
                      tp_axis: Optional[str]):
    h_in = ll.rms_norm(x, p["mix_norm"], cfg.norm_eps)
    h, nc = ll.rglru_block(p["rglru"], cfg, h_in, cache=cache,
                           decode=decode, tp_axis=tp_axis)
    x = x + h
    g = ll.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    x = x + ll.glu_ffn(p["ffn"], g, tp_axis)
    return x, nc


def _ssd_full_block(cfg: ModelConfig, p, x, cache, *, decode: bool,
                    tp_axis: Optional[str]):
    h_in = ll.rms_norm(x, p["norm"], cfg.norm_eps)
    h, nc = ll.ssd_block(p["ssd"], cfg, h_in, cache=cache, decode=decode,
                         tp_axis=tp_axis)
    return x + h, nc


def _scan_stack(body, x, blocks, caches, *, remat: bool = False):
    """Scan ``body(x, p, c) -> (x, nc)`` over stacked layers."""
    def scan_body(carry, inp):
        p, c = inp
        y, nc = body(carry, p, c)
        return _constrain(y), nc

    if remat:
        scan_body = jax.checkpoint(scan_body)
    x, new_caches = lax.scan(scan_body, x, (blocks, caches))
    return x, new_caches


def _none_like(tree):
    return None


# --------------------------------------------------------------------------- #
#  embeddings / positions
# --------------------------------------------------------------------------- #

def embed_tokens(params: Params, cfg: ModelConfig, tokens: jnp.ndarray
                 ) -> jnp.ndarray:
    return jnp.take(params["embed"], tokens, axis=0)


def unembed(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    if "unembed" in params:
        return x @ params["unembed"]
    return x @ params["embed"].T


def default_positions(cfg: ModelConfig, B: int, S: int, offset=0):
    base = jnp.arange(S, dtype=jnp.int32)[None, :]       # (1, S)
    if hasattr(offset, "shape") and getattr(offset, "ndim", 0) == 1:
        pos = offset[:, None] + base                      # (B, S)
    else:
        pos = jnp.broadcast_to(base + offset, (B, S))
    if cfg.mrope:
        return jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def sinusoid_positions(S: int, d: int, dtype) -> jnp.ndarray:
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


# --------------------------------------------------------------------------- #
#  forward paths
# --------------------------------------------------------------------------- #

def _backbone(params: Params, cfg: ModelConfig, x, positions, cache, *,
              decode: bool, tp_axis: Optional[str], remat: bool):
    """Run the layer stack; returns (hidden, new_cache)."""
    ln = None if cache is None else cache["len"]
    new_cache = None if cache is None else dict(cache)

    if cfg.family in ("dense", "moe", "vlm", "ssm"):
        if cfg.family == "ssm":
            def body(h, p, c):
                return _ssd_full_block(cfg, p, h, c, decode=decode,
                                       tp_axis=tp_axis)
        else:
            def body(h, p, c):
                return _dense_block(cfg, p, h, positions, c, ln,
                                    decode=decode, tp_axis=tp_axis)
        caches = None if cache is None else cache["layers"]
        if caches is None:
            x, _ = _scan_stack(lambda h, p, c: body(h, p, None), x,
                               params["blocks"],
                               jax.tree.map(lambda a: a[:, :0],
                                            params["blocks"]),
                               remat=remat)
        else:
            x, nc = _scan_stack(body, x, params["blocks"], caches,
                                remat=remat)
            new_cache["layers"] = nc
    elif cfg.family == "hybrid":
        G, T = hybrid_layout(cfg)

        def group_body(h, p, c):
            ncs = {}
            for bi, kind in enumerate(cfg.block_pattern):
                key = f"b{bi}"
                ci = None if c is None else c[key]
                if kind == "rglru":
                    h, nci = _rglru_full_block(cfg, p[key], h, ci,
                                               decode=decode,
                                               tp_axis=tp_axis)
                else:
                    h, nci = _dense_block(cfg, p[key], h, positions, ci, ln,
                                          decode=decode, tp_axis=tp_axis)
                ncs[key] = nci
            return h, ncs

        caches = None if cache is None else cache["groups"]
        if caches is None:
            x, _ = _scan_stack(
                lambda h, p, c: (group_body(h, p, None)[0], 0.0), x,
                params["groups"],
                jax.tree.map(lambda a: a[:, :0], params["groups"]),
                remat=remat)
        else:
            x, nc = _scan_stack(group_body, x, params["groups"], caches,
                                remat=remat)
            new_cache["groups"] = nc
        if T:
            tail_kind = cfg.block_pattern[0]

            def tail_body(h, p, c):
                if tail_kind == "rglru":
                    return _rglru_full_block(cfg, p, h, c, decode=decode,
                                             tp_axis=tp_axis)
                return _dense_block(cfg, p, h, positions, c, ln,
                                    decode=decode, tp_axis=tp_axis)

            tcaches = None if cache is None else cache["tail"]
            if tcaches is None:
                x, _ = _scan_stack(
                    lambda h, p, c: (tail_body(h, p, None)[0], 0.0), x,
                    params["tail"],
                    jax.tree.map(lambda a: a[:, :0], params["tail"]),
                    remat=remat)
            else:
                x, nc = _scan_stack(tail_body, x, params["tail"], tcaches,
                                    remat=remat)
                new_cache["tail"] = nc
    else:
        raise ValueError(cfg.family)

    if new_cache is not None:
        new_cache["len"] = ln + x.shape[1]
    return x, new_cache


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray, *,
            embeds: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None,
            tp_axis: Optional[str] = None,
            remat: bool = False) -> jnp.ndarray:
    """Full-sequence logits (training). ``embeds``: frontend embeddings
    prepended to the token embeddings (VLM patch / audio frame stubs)."""
    if cfg.family == "audio":
        return whisper_forward(params, cfg, tokens, embeds, tp_axis=tp_axis)
    x = embed_tokens(params, cfg, tokens)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    x = _constrain(x)
    B, S, _ = x.shape
    if positions is None:
        positions = default_positions(cfg, B, S)
    x, _ = _backbone(params, cfg, x, positions, None, decode=False,
                     tp_axis=tp_axis, remat=remat)
    x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params, cfg, x)


@partial(jax.jit, static_argnames=("cfg", "dtype"))
def _upcast_layer(blocks: Params, cfg: ModelConfig, i, x, positions, dtype):
    p = jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, i, 0, False)
                     .astype(dtype), blocks)
    return _dense_block(cfg, p, x, positions, None, None, decode=False,
                        tp_axis=None)[0]


@partial(jax.jit, static_argnames=("dtype",))
def _upcast_matmul(x, w, dtype):
    return x @ w.astype(dtype)


def forward_blocked(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                    *, dtype=jnp.float32, vocab_block: int = 16384
                    ) -> jnp.ndarray:
    """``forward`` on ``params`` upcast to ``dtype``, at the highest matmul
    precision, one layer and one vocabulary block at a time.

    The plain reference for a model whose whole upcast copy does not fit
    the device: only one layer's weights and one block of the output
    head exist in ``dtype`` at once. Upcasting is exact, so this equals
    ``forward`` on the upcast parameter tree. Dense, MoE and VLM stacks
    without frontend embeddings.
    """
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"blocked forward unsupported for {cfg.family}")
    with jax.default_matmul_precision("highest"):
        x = embed_tokens(params, cfg, tokens).astype(dtype)
        B, S, _ = x.shape
        positions = default_positions(cfg, B, S)
        for i in range(cfg.n_layers):
            x = _upcast_layer(params["blocks"], cfg, i, x, positions, dtype)
        x = ll.rms_norm(x, params["final_norm"].astype(dtype), cfg.norm_eps)
        w = params["unembed"] if "unembed" in params else params["embed"].T
        return jnp.concatenate(
            [_upcast_matmul(x, w[:, j:j + vocab_block], dtype)
             for j in range(0, w.shape[1], vocab_block)], axis=-1)


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            cache: Dict, *, embeds: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None,
            tp_axis: Optional[str] = None,
            remat: bool = False) -> Tuple[jnp.ndarray, Dict]:
    """Process the prompt, fill the cache, return last-position logits."""
    if cfg.family == "audio":
        return whisper_prefill(params, cfg, tokens, embeds, cache,
                               tp_axis=tp_axis)
    x = embed_tokens(params, cfg, tokens)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    x = _constrain(x)
    B, S, _ = x.shape
    if positions is None:
        positions = default_positions(cfg, B, S)
    x, new_cache = _backbone(params, cfg, x, positions, cache, decode=False,
                             tp_axis=tp_axis, remat=remat)
    x = ll.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    return unembed(params, cfg, x), new_cache


def decode_step(params: Params, cfg: ModelConfig, cache: Dict,
                tokens: jnp.ndarray, *,
                tp_axis: Optional[str] = None
                ) -> Tuple[jnp.ndarray, Dict]:
    """One decode step. tokens: (B, T).

    T = 1 is ordinary autoregressive decode. T > 1 is the speculative
    *verify* path: the T tokens (last accepted token followed by T-1 draft
    tokens) are scored in one pass with causal masking among them; the
    cache advances by T and the caller rolls rejected positions back with
    ``rollback_cache``. Only KV-cache families support T > 1 — recurrent
    state (ssm / hybrid) cannot roll back.
    """
    B, T = tokens.shape
    if T > 1 and cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"multi-token decode unsupported for {cfg.family}")
    if cfg.family == "audio":
        return whisper_decode_step(params, cfg, cache, tokens,
                                   tp_axis=tp_axis)
    x = embed_tokens(params, cfg, tokens)
    pos = cache["len"][:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    if cfg.mrope:
        pos = jnp.broadcast_to(pos[None], (3, B, T))
    x, new_cache = _backbone(params, cfg, x, pos, cache, decode=True,
                             tp_axis=tp_axis, remat=False)
    x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params, cfg, x), new_cache


# --------------------------------------------------------------------------- #
#  layer-wise paths (weight streaming)
# --------------------------------------------------------------------------- #
#
# The scan paths above close over the full stacked parameter pytree — all
# L layers resident. The layer-wise paths pull each layer's weights from a
# ``runtime.paramstore.ParamSource`` right before applying it, which is
# what lets the streaming runtime keep only a window of layers in memory
# (prefetch ahead of the front, release behind it). The math is the exact
# per-layer sequence the scan performs, so resident and streamed decode
# agree to numerical tolerance.
#
# Quantized stores (v2 manifests persisting packed int4/int2 +
# group-scale leaves) keep their matmul weights PACKED here: eligible 2-D
# q4 leaves flow into ``layers.qmm``, which dispatches the fused
# ``kernels.ops.q4_matmul`` (dequant-in-kernel, tile-by-tile in VMEM) —
# only the packed bytes ever cross disk -> staging -> device -> compute.
# Ineligible leaves (q2, stacked expert tensors, einsum-consumed MLA
# projections, misaligned dims) dequantize per layer at use; both paths
# accumulate f32, so streamed-quantized logits equal the
# resident-dequantized reference.

def _dequant_params(p: Params) -> Params:
    """Dequantize any QuantizedTensor leaves pulled from a ParamSource."""
    from ..quant.grouped import dequantize_tree

    return dequantize_tree(p, jnp.float32)


#: leaf names whose consumers route through ``layers.qmm`` — the only
#: sites where a packed weight may survive into the block functions.
_FUSED_Q4_KEYS = frozenset((
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "in_proj", "out_proj"))


def _prepare_layer_params(p: Params) -> Params:
    """Selective dequantization for the layer-wise (streamed) path.

    Q4 leaves that ``layers.qmm`` can feed to the fused kernel stay
    packed; everything else dequantizes as before.
    """
    from ..quant.grouped import QuantizedTensor, dequantize_leaf
    from .layers import q4_fused_eligible

    def is_qt(x):
        return isinstance(x, QuantizedTensor)

    pairs, treedef = jax.tree_util.tree_flatten_with_path(p, is_leaf=is_qt)
    out = []
    for path, leaf in pairs:
        if is_qt(leaf):
            name = getattr(path[-1], "key", None)
            if name in _FUSED_Q4_KEYS and q4_fused_eligible(leaf):
                out.append(leaf)
                continue
            leaf = dequantize_leaf(leaf, jnp.float32)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def _layerwise_backbone(source, cfg: ModelConfig, x, positions, cache, *,
                        decode: bool, tp_axis: Optional[str]):
    """Run the stack one layer at a time, weights pulled from ``source``."""
    if cfg.family not in ("dense", "moe", "vlm", "ssm"):
        raise ValueError(
            f"layer-wise streaming unsupported for family {cfg.family}")
    ln = None if cache is None else cache["len"]
    layers_c = None if cache is None else cache["layers"]
    new_layers = layers_c
    for i in range(cfg.n_layers):
        p = _prepare_layer_params(source.layer(i))
        c_i = None if layers_c is None else jax.tree.map(
            lambda a: a[i], layers_c)
        if cfg.family == "ssm":
            x, nc = _ssd_full_block(cfg, p, x, c_i, decode=decode,
                                    tp_axis=tp_axis)
        else:
            x, nc = _dense_block(cfg, p, x, positions, c_i, ln,
                                 decode=decode, tp_axis=tp_axis)
        x = _constrain(x)
        if nc is not None:
            new_layers = jax.tree.map(
                lambda full, n: full.at[i].set(n), new_layers, nc)
    new_cache = None
    if cache is not None:
        new_cache = dict(cache)
        new_cache["layers"] = new_layers
        new_cache["len"] = ln + x.shape[1]
    return x, new_cache


def forward_layerwise(source, cfg: ModelConfig, tokens: jnp.ndarray, *,
                      embeds: Optional[jnp.ndarray] = None,
                      positions: Optional[jnp.ndarray] = None,
                      tp_axis: Optional[str] = None) -> jnp.ndarray:
    """``forward`` with weights pulled from a ParamSource."""
    head = _dequant_params(source.head())
    x = embed_tokens(head, cfg, tokens)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    x = _constrain(x)
    B, S, _ = x.shape
    if positions is None:
        positions = default_positions(cfg, B, S)
    x, _ = _layerwise_backbone(source, cfg, x, positions, None,
                               decode=False, tp_axis=tp_axis)
    x = ll.rms_norm(x, head["final_norm"], cfg.norm_eps)
    return unembed(head, cfg, x)


def prefill_layerwise(source, cfg: ModelConfig, tokens: jnp.ndarray,
                      cache: Dict, *,
                      embeds: Optional[jnp.ndarray] = None,
                      positions: Optional[jnp.ndarray] = None,
                      tp_axis: Optional[str] = None
                      ) -> Tuple[jnp.ndarray, Dict]:
    """``prefill`` with weights pulled from a ParamSource."""
    head = _dequant_params(source.head())
    x = embed_tokens(head, cfg, tokens)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    x = _constrain(x)
    B, S, _ = x.shape
    if positions is None:
        positions = default_positions(cfg, B, S)
    x, new_cache = _layerwise_backbone(source, cfg, x, positions, cache,
                                       decode=False, tp_axis=tp_axis)
    x = ll.rms_norm(x[:, -1:], head["final_norm"], cfg.norm_eps)
    return unembed(head, cfg, x), new_cache


def decode_step_layerwise(source, cfg: ModelConfig, cache: Dict,
                          tokens: jnp.ndarray, *,
                          tp_axis: Optional[str] = None
                          ) -> Tuple[jnp.ndarray, Dict]:
    """``decode_step`` with weights pulled from a ParamSource.

    Supports the same T > 1 speculative verify semantics as
    ``decode_step`` — a streamed verify pass reads each layer from disk
    once for the whole draft block, which is the amortization the
    acceptance-aware latency model prices.
    """
    B, T = tokens.shape
    if T > 1 and cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"multi-token decode unsupported for {cfg.family}")
    head = _dequant_params(source.head())
    x = embed_tokens(head, cfg, tokens)
    pos = cache["len"][:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    if cfg.mrope:
        pos = jnp.broadcast_to(pos[None], (3, B, T))
    x, new_cache = _layerwise_backbone(source, cfg, x, pos, cache,
                                       decode=True, tp_axis=tp_axis)
    x = ll.rms_norm(x, head["final_norm"], cfg.norm_eps)
    return unembed(head, cfg, x), new_cache


# --------------------------------------------------------------------------- #
#  paged KV-cache paths (block-pool cache, runtime.kvcache)
# --------------------------------------------------------------------------- #
#
# The dense cache above preallocates (L, B, max_len, ...); the paged cache
# holds a global pool of fixed-size token pages plus a per-slot block
# table (see runtime/kvcache.py for allocation, prefix sharing and
# offload). These paths write new cache lines through the table and
# attend over gathered pages — the per-position math is identical to the
# dense decode path, so paged greedy decode is byte-identical to dense.

def _paged_backbone(params: Params, cfg: ModelConfig, x, positions, cache,
                    *, tp_axis: Optional[str], prefill: bool = False,
                    write: bool = True):
    ln = cache["len"]
    table = cache["block_table"]

    def body(h, p, pg):
        h_in = ll.rms_norm(h, p["attn_norm"], cfg.norm_eps)
        if cfg.mla:
            a, npg = ll.mla_block_paged(p["attn"], cfg, h_in, positions,
                                        pg, table, ln, tp_axis=tp_axis,
                                        prefill=prefill, write=write)
        else:
            a, npg = ll.attn_block_paged(p["attn"], cfg, h_in, positions,
                                         pg, table, ln, tp_axis=tp_axis,
                                         prefill=prefill, write=write)
        h = h + a
        g = ll.rms_norm(h, p["ffn_norm"], cfg.norm_eps)
        if cfg.n_experts:
            h = h + ll.moe_ffn(p["moe"], cfg, g, lossless=True,
                               tp_axis=tp_axis)
        else:
            h = h + ll.glu_ffn(p["ffn"], g, tp_axis)
        return h, npg

    x, new_pages = _scan_stack(body, x, params["blocks"], cache["pages"])
    new_cache = dict(cache)
    new_cache["pages"] = new_pages
    new_cache["len"] = ln + x.shape[1]
    return x, new_cache


def decode_step_paged(params: Params, cfg: ModelConfig, cache: Dict,
                      tokens: jnp.ndarray, *,
                      tp_axis: Optional[str] = None
                      ) -> Tuple[jnp.ndarray, Dict]:
    """``decode_step`` against a paged KV cache. tokens: (B, T).

    cache: {"pages": {leaf: (L, P, bs, ...)}, "block_table": (B, nb),
    "len": (B,)} as built by ``runtime.kvcache.PagedKVCache``. T > 1 is
    the speculative verify path; rollback is ``rollback_cache`` on the
    device side plus ``PagedKVCache.trim_to`` on the allocator (pages
    past the accepted length return to the pool — the paged analogue of
    "entries past ``len`` are never attended").
    """
    B, T = tokens.shape
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"paged decode unsupported for {cfg.family}")
    x = embed_tokens(params, cfg, tokens)
    pos = cache["len"][:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    if cfg.mrope:
        pos = jnp.broadcast_to(pos[None], (3, B, T))
    x, new_cache = _paged_backbone(params, cfg, x, pos, cache,
                                   tp_axis=tp_axis)
    x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params, cfg, x), new_cache


def prefill_chunk_paged(params: Params, cfg: ModelConfig, cache: Dict,
                        tokens: jnp.ndarray, *,
                        tp_axis: Optional[str] = None,
                        write: bool = True) -> Tuple[jnp.ndarray, Dict]:
    """One chunk of a chunked (paged) prefill. tokens: (B, S).

    ``cache`` is a per-slot view ({"pages", "block_table", "len"}) whose
    ``len`` counts the prompt positions already materialized in pages
    (shared prefix + earlier chunks); the chunk's KV is written directly
    through the block table and attention runs with the dense-prefill
    math (``chunked_causal_attention``), so running a prompt chunk by
    chunk produces byte-identical activations — and first token — to
    one-shot dense prefill. Returns full (B, S, V) logits (the caller
    argmaxes the last position of the last chunk) and the updated view.

    ``write=False`` re-derives logits without touching pages — used when
    the whole prompt was a prefix-cache hit and the final positions'
    KV already exists in shared pages that must not be rewritten.
    """
    B, T = tokens.shape
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"paged prefill unsupported for {cfg.family}")
    x = embed_tokens(params, cfg, tokens)
    pos = cache["len"][:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    if cfg.mrope:
        pos = jnp.broadcast_to(pos[None], (3, B, T))
    x, new_cache = _paged_backbone(params, cfg, x, pos, cache,
                                   tp_axis=tp_axis, prefill=True,
                                   write=write)
    x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params, cfg, x), new_cache


def rollback_cache(cache: Dict, new_len: jnp.ndarray) -> Dict:
    """Roll rejected speculative positions out of a KV cache.

    Entries past ``len`` are never attended (position-masked) and the next
    decode writes at slot ``len``, so discarding rejected draft tokens is
    just resetting the per-sequence counter. Not valid for recurrent-state
    families (ssm / hybrid), whose state updates are irreversible.
    """
    out = dict(cache)
    out["len"] = jnp.asarray(new_len).astype(cache["len"].dtype)
    return out


# --------------------------------------------------------------------------- #
#  whisper (encoder-decoder)
# --------------------------------------------------------------------------- #

def whisper_encode(params: Params, cfg: ModelConfig, frames: jnp.ndarray,
                   *, tp_axis: Optional[str] = None) -> jnp.ndarray:
    """frames: (B, F, d) precomputed mel-frame embeddings (conv stub)."""
    B, F, d = frames.shape
    x = frames + sinusoid_positions(F, d, frames.dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32)[None],
                                 (B, F))

    def body(h, p, c):
        h_in = ll.rms_norm(h, p["attn_norm"], cfg.norm_eps)
        a, _ = ll.attn_block(p["attn"], cfg, h_in, positions, causal=False,
                             tp_axis=tp_axis)
        h = h + a
        g = ll.rms_norm(h, p["ffn_norm"], cfg.norm_eps)
        return h + ll.glu_ffn(p["ffn"], g, tp_axis), 0.0

    x, _ = _scan_stack(body, x, params["enc_blocks"],
                       jax.tree.map(lambda a: a[:, :0],
                                    params["enc_blocks"]))
    return ll.rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _cross_kv(p, cfg: ModelConfig, enc_out: jnp.ndarray):
    B, F, _ = enc_out.shape
    hk, hd = cfg.kv_heads, cfg.head_dim
    k = (enc_out @ p["wk"]).reshape(B, F, hk, hd)
    v = (enc_out @ p["wv"]).reshape(B, F, hk, hd)
    return k, v


def _whisper_dec_block(cfg, p, x, positions, cache, ln, cross_k, cross_v,
                       *, decode, tp_axis):
    h_in = ll.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    c = None if cache is None else {**cache, "len": ln}
    a, nc = ll.attn_block(p["attn"], cfg, h_in, positions, cache=c,
                          decode=decode, tp_axis=tp_axis)
    x = x + a
    h_in = ll.rms_norm(x, p["cross_norm"], cfg.norm_eps)
    a, _ = ll.attn_block(p["cross"], cfg, h_in, positions,
                         cross_kv=(cross_k, cross_v), causal=False,
                         tp_axis=tp_axis)
    x = x + a
    g = ll.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    x = x + ll.glu_ffn(p["ffn"], g, tp_axis)
    if nc is not None:
        nc.pop("len", None)
    return x, nc


def whisper_forward(params: Params, cfg: ModelConfig, tokens, frames,
                    *, tp_axis: Optional[str] = None) -> jnp.ndarray:
    enc_out = whisper_encode(params, cfg, frames, tp_axis=tp_axis)
    B, S = tokens.shape
    x = embed_tokens(params, cfg, tokens)
    x = x + sinusoid_positions(S, cfg.d_model, x.dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))

    def body(h, p, c):
        ck, cv = _cross_kv(p["cross"], cfg, enc_out)
        return _whisper_dec_block(cfg, p, h, positions, None, None, ck, cv,
                                  decode=False, tp_axis=tp_axis)

    x, _ = _scan_stack(lambda h, p, c: (body(h, p, None)[0], 0.0), x,
                       params["dec_blocks"],
                       jax.tree.map(lambda a: a[:, :0],
                                    params["dec_blocks"]))
    x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params, cfg, x)


def whisper_prefill(params: Params, cfg: ModelConfig, tokens, frames, cache,
                    *, tp_axis: Optional[str] = None):
    enc_out = whisper_encode(params, cfg, frames, tp_axis=tp_axis)
    B, S = tokens.shape
    x = embed_tokens(params, cfg, tokens)
    x = x + sinusoid_positions(S, cfg.d_model, x.dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))
    ln = cache["len"]

    def body(h, p, c):
        ck, cv = _cross_kv(p["cross"], cfg, enc_out)
        h, nc = _whisper_dec_block(cfg, p, h, positions, c, ln, ck, cv,
                                   decode=False, tp_axis=tp_axis)
        nc["cross_k"] = ck.astype(h.dtype)
        nc["cross_v"] = cv.astype(h.dtype)
        return h, nc

    x, nc = _scan_stack(body, x, params["dec_blocks"], cache["layers"])
    new_cache = dict(cache)
    new_cache["cross_k"] = nc.pop("cross_k")
    new_cache["cross_v"] = nc.pop("cross_v")
    new_cache["layers"] = nc
    new_cache["len"] = ln + S
    x = ll.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    return unembed(params, cfg, x), new_cache


def whisper_decode_step(params: Params, cfg: ModelConfig, cache, tokens,
                        *, tp_axis: Optional[str] = None):
    B = tokens.shape[0]
    x = embed_tokens(params, cfg, tokens)
    ln = cache["len"]
    S_tab = cfg.max_decode_len or cache["layers"]["k"].shape[2]
    pos_emb = sinusoid_positions(S_tab, cfg.d_model, x.dtype)
    x = x + jax.vmap(lambda i: pos_emb[jnp.minimum(i, S_tab - 1)])(
        ln)[:, None]
    positions = ln[:, None]

    def body(h, p, c):
        ck = c.pop("cross_k")
        cv = c.pop("cross_v")
        h, nc = _whisper_dec_block(cfg, p, h, positions, c, ln, ck, cv,
                                   decode=True, tp_axis=tp_axis)
        nc["cross_k"] = ck
        nc["cross_v"] = cv
        return h, nc

    caches = dict(cache["layers"])
    caches["cross_k"] = cache["cross_k"]
    caches["cross_v"] = cache["cross_v"]
    x, nc = _scan_stack(body, x, params["dec_blocks"], caches)
    new_cache = dict(cache)
    new_cache["cross_k"] = nc.pop("cross_k")
    new_cache["cross_v"] = nc.pop("cross_v")
    new_cache["layers"] = nc
    new_cache["len"] = ln + 1
    x = ll.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params, cfg, x), new_cache
