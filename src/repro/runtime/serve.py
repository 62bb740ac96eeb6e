"""Piped-ring serving runtime — the paper's technique on a TPU mesh.

Mapping (DESIGN.md §2): one *ring stage* = one coordinate of the "data"
mesh axis (M stages); inside a stage, the "model" axis is a TP group.
The model's (padded) L layers are split into k*M windows of w layers;
stage m owns windows {r*M + m : r < k} — for k > 1 this is exactly the
interleaved/looping pipeline schedule, which is what prima.cpp's
multi-round ring is on homogeneous hardware.

Decode schedule (one token for the whole batch):
  * the global batch splits into M microbatches; microbatch e enters the
    ring at stage 0 at step e;
  * at step t, stage m computes window j = t - ((t - m) mod M) for
    microbatch e = (t - m) mod M (masked out while j is out of range),
    then ppermutes its activation to stage m+1;
  * after k*M + M - 1 steps every microbatch has traversed all L layers;
    final hiddens are captured at the stage owning the last window and
    psum-broadcast for the (vocab-sharded) logits matmul.

Tensor parallelism inside a stage:
  * FFN / MoE: f (or expert) dimension sharded over "model", psum after
    the down-projection;
  * attention: weights replicated, KV cache *sequence*-sharded over
    "model"; each chip computes partial attention over its KV slice and
    shards merge with a distributed online softmax (works for any
    kv_heads, unlike head sharding);
  * SSM: O(1) state replicated inside the stage (the model is small).

Multi-pod: the "pod" axis is a pure data-parallel replica dimension —
each pod runs its own ring; no cross-pod collectives in serving.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig
from ..models import layers as ll
from ..models import model as M
from . import sharding as S

Params = Dict[str, Any]


# --------------------------------------------------------------------------- #
#  ring layout: permutation, padding, shardings
# --------------------------------------------------------------------------- #

def ring_supported(cfg: ModelConfig, batch: int, n_stages: int) -> bool:
    """Ring decode needs a uniform layer stack and >= 1 seq per stage."""
    return (cfg.family in ("dense", "moe", "vlm", "ssm")
            and batch % n_stages == 0)


def padded_layers(L: int, n_stages: int) -> int:
    return -(-L // n_stages) * n_stages


def ring_permutation(L_pad: int, n_stages: int, k: int) -> np.ndarray:
    """perm[i] = global layer index stored at ring-stacked position i.

    Position layout: stage-major, then round, then offset-in-window:
    stage m's contiguous block of k*w rows holds its k windows in order.
    """
    assert L_pad % (n_stages * k) == 0, (L_pad, n_stages, k)
    w = L_pad // (n_stages * k)
    perm = np.zeros(L_pad, dtype=np.int64)
    i = 0
    for m in range(n_stages):
        for r in range(k):
            base = (r * n_stages + m) * w
            for off in range(w):
                perm[i] = base + off
                i += 1
    return perm


def pad_and_permute(stacked: Any, cfg: ModelConfig, n_stages: int, k: int
                    ) -> Any:
    """Zero-pad the layer axis to L_pad (identity residual blocks) and apply
    the ring permutation. Works on params['blocks'] or cache['layers']."""
    L = cfg.n_layers
    L_pad = padded_layers(L, n_stages)
    perm = ring_permutation(L_pad, n_stages, k)

    def fix(a):
        if a.shape[0] != L:
            return a
        if L_pad != L:
            pad = [(0, L_pad - L)] + [(0, 0)] * (a.ndim - 1)
            a = jnp.pad(a, pad)
        return jnp.take(a, perm, axis=0)

    return jax.tree.map(fix, stacked)


#: per-layer matmul weights eligible for int4 ring storage (norms, biases,
#: convs, gates stay bf16 — they are tiny and numerically sensitive)
RING_QUANT_KEYS = frozenset({
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "router",
    "wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "in_proj", "out_proj",
    "w_x", "w_y", "w_out"})


#: leaves whose contraction dim is model-sharded in ring TP — their scale
#: rows (K/group) must stay divisible by tp
_RING_TP_CONTRACTION = frozenset({"w_down", "out_proj"})


def quantize_ring_params(params: Params, cfg: ModelConfig, *,
                         tp: int = 16) -> Tuple[Params, List[str]]:
    """Store the ring layer bank in packed int4 (+bf16 group scales).

    Returns ``(params, skipped)`` where ``skipped`` lists the eligible
    matmul leaves left in bf16 because no group size satisfied the
    sharding divisibility constraints — a silent bf16 fallback would cap
    compression without anyone noticing, so benches must report it.

    The TPU-side compute pairs this with the dequant-in-kernel
    ``kernels/q4_matmul`` (validated vs its oracle); the jnp path
    dequantizes at use. Decode is weight-bandwidth-bound, so halving →
    quartering the streamed bytes moves the dominant roofline term
    directly (EXPERIMENTS §Perf HC2).

    Group size adapts per leaf: 64 normally; smaller for leaves whose
    contraction dim is TP-sharded so packed values and scales shard
    identically (shard_map needs exact divisibility).
    """
    from ..quant.grouped import quantize_q4

    skipped: List[str] = []

    def pick_group(key: str, K: int) -> Optional[int]:
        for g in (64, 32, 16):
            if K % g:
                continue
            if key in _RING_TP_CONTRACTION and (K // g) % tp:
                continue
            if K // g < 1:
                continue
            return g
        return None

    def walk(tree, prefix=""):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                eligible = (k in RING_QUANT_KEYS and hasattr(v, "ndim")
                            and v.ndim >= 3)
                g = pick_group(k, v.shape[-2]) if eligible else None
                if g:
                    out[k] = quantize_q4(v, group=g)
                else:
                    if eligible:
                        skipped.append(f"{prefix}{k} (K={v.shape[-2]})")
                    out[k] = walk(v, f"{prefix}{k}/")
            return out
        return tree

    out = dict(params)
    out["blocks"] = walk(params["blocks"])
    if skipped:
        import logging

        logging.getLogger(__name__).warning(
            "quantize_ring_params: %d leaves left bf16 (no group size "
            "fits K and tp=%d): %s", len(skipped), tp, ", ".join(skipped))
    return out, skipped


def _dequant_tree(p):
    """Dequantize any QuantizedTensor leaves of a (sliced) param subtree."""
    from ..quant.grouped import dequantize_tree

    return dequantize_tree(p, jnp.bfloat16)


#: per-layer leaves the ring window body consumes through ``ll.qmm`` — a
#: 2-D q4 slice of these stays packed and dispatches the fused
#: ``kernels/q4_matmul``, so the microstep streams packed bytes instead of
#: materializing a bf16 copy in HBM first
_RING_QMM_KEYS = frozenset({
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "wq_a", "wq_b", "wkv_a", "in_proj", "out_proj"})


def dequant_ring_reference(blocks, dtype=jnp.float32):
    """Dequantize a *stacked* ring layer bank with the same numerics the
    window body applies at use: leaves consumed through ``ll.qmm`` keep
    full precision (the fused kernel multiplies int4 by the scale in f32
    without a bf16 round-trip), everything else dequantizes through bf16
    exactly like ``_prep_ring_layer``. Reference paths (tests, oracles)
    use this so "quantized ring == dequantized reference" stays an exact
    contract.
    """
    from ..quant.grouped import QuantizedTensor, dequantize_leaf

    def walk(tree):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                if isinstance(v, QuantizedTensor):
                    keep = (k in _RING_QMM_KEYS and v.bits == 4
                            and v.packed.ndim == 3)
                    dq = dequantize_leaf(
                        v, jnp.float32 if keep else jnp.bfloat16)
                    out[k] = dq.astype(dtype)
                else:
                    out[k] = walk(v)
            return out
        return tree

    return walk(blocks)


def _prep_ring_layer(p):
    """Prepare one sliced ring layer's params for the window body.

    q4 leaves consumed via ``ll.qmm`` stay packed (dequantization happens
    tile-by-tile in VMEM inside the fused matmul kernel); everything else
    — einsum-consumed ``wk_b``/``wv_b``, MoE expert banks (3-D after the
    slice), routers, the q2 demo format — dequantizes up front exactly as
    the old whole-subtree path did. Both matmul paths accumulate f32, so
    keeping a leaf packed does not change logits.
    """
    from ..quant.grouped import QuantizedTensor, dequantize_leaf

    def walk(tree):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                if isinstance(v, QuantizedTensor):
                    keep = (k in _RING_QMM_KEYS and v.bits == 4
                            and v.packed.ndim == 2)
                    out[k] = v if keep else dequantize_leaf(v, jnp.bfloat16)
                else:
                    out[k] = walk(v)
            return out
        return tree

    return walk(p)


def pad_vocab(params: Params, cfg: ModelConfig, tp: int) -> Params:
    """Pad embed/unembed vocab to a multiple of tp (shard_map divisibility)."""
    V = cfg.vocab
    V_pad = -(-V // tp) * tp
    if V_pad == V:
        return params
    out = dict(params)
    out["embed"] = jnp.pad(params["embed"], ((0, V_pad - V), (0, 0)))
    if "unembed" in params:
        out["unembed"] = jnp.pad(params["unembed"], ((0, 0), (0, V_pad - V)))
    return out


def _stacked_leaf_spec(key: str, nd: int, *, ep: bool = False):
    """Spec for one stacked per-layer leaf: axis 0 = ring layer order ->
    "data"; FFN/MoE inner dims over "model"; everything else replicated."""
    if key in ("w_gate", "w_up") and nd == 4:          # MoE (L, E, d, f)
        return P("data", "model", None, None) if ep \
            else P("data", None, None, "model")
    if key == "w_down" and nd == 4:
        return P("data", "model", None, None) if ep \
            else P("data", None, "model", None)
    if key in ("w_gate", "w_up") and nd == 3:          # GLU (L, d, f)
        return P("data", None, "model")
    if key == "w_down" and nd == 3:
        return P("data", "model", None)
    return P(*(["data"] + [None] * (nd - 1)))


def ring_param_specs(cfg: ModelConfig, mesh: Mesh, params: Params):
    """PartitionSpecs for ring-mode params.

    Layer axis over "data"; FFN/MoE inner dims over "model"; attention and
    SSM weights replicated over "model"; embeddings vocab-sharded.
    """
    tp = mesh.shape["model"]
    # ring mode currently dispatches MoE with TP inside each expert; EP is
    # the §Perf hillclimb variant (build_ring_serve_step(..., moe_ep=True)).
    ep = False

    def spec(path, leaf):
        key = S._leaf_key(jax.tree_util.keystr(path))
        nd = leaf.ndim
        if key == "embed":
            return P("model", None)
        if key == "unembed":
            return P(None, "model")
        if key == "final_norm":
            return P()
        return _stacked_leaf_spec(key, nd, ep=ep)

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef, [S.sanitize(spec(p, l), tuple(l.shape), mesh)
                  for p, l in flat])


def ring_cache_specs(cfg: ModelConfig, mesh: Mesh, cache: Dict):
    """Layer axis over "data"; KV sequence over "model"; pods shard batch."""
    pod = ("pod",) if "pod" in mesh.axis_names else ()

    def spec(path, leaf):
        key = S._leaf_key(jax.tree_util.keystr(path))
        nd = leaf.ndim
        if key == "len":
            return P(pod) if pod else P()
        if key in ("k", "v"):                 # (L, B, S, hk, hd)
            return P("data", pod, "model", None, None)
        if key in ("k_scale", "v_scale"):     # (L, B, S, hk)
            return P("data", pod, "model", None)
        if key == "latent":                   # (L, B, S, r)
            return P("data", pod, "model", None)
        if key == "state":                    # (L, B, nh, P, N)
            return P("data", pod, None, None, None)
        if key == "conv":                     # (L, B, K-1, C)
            return P("data", pod, None, None)
        return P(*(["data"] + [pod if i == 0 else None
                               for i in range(nd - 1)]))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat])


# --------------------------------------------------------------------------- #
#  masked sequence-sharded KV write
# --------------------------------------------------------------------------- #

def _masked_slot_update(arr: jnp.ndarray, new: jnp.ndarray,
                        slot: jnp.ndarray, s_start: int, s_len: int
                        ) -> jnp.ndarray:
    """Write new (B, 1, ...) at absolute slot into the local seq shard
    arr (B, s_len, ...) iff slot lands in [s_start, s_start + s_len)."""
    local = jnp.clip(slot - s_start, 0, s_len - 1)
    in_range = (slot >= s_start) & (slot < s_start + s_len)

    def upd(a, n, i, ok):
        cur = lax.dynamic_slice_in_dim(a, i, 1, axis=0)
        val = jnp.where(ok, n.astype(a.dtype), cur)
        return lax.dynamic_update_slice_in_dim(a, val, i, axis=0)

    return jax.vmap(upd)(arr, new, local, in_range)


# --------------------------------------------------------------------------- #
#  per-family ring window layers (decode, explicit collectives)
# --------------------------------------------------------------------------- #

def _ring_attn_layer(cfg: ModelConfig, p, x, c, ln, *, s_start, s_len):
    """One dense/moe/vlm decoder layer, ring decode mode.

    x: (mb, T, d) replicated over "model" (T = 1 ordinary decode, T > 1 the
    speculative verify block); c: local cache slice
    {k/v: (mb, s_len, hk, hd), [scales]}; ln: (mb,) tokens so far.
    """
    mb, T = x.shape[0], x.shape[1]
    pos = ln[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    if cfg.mrope:
        pos = jnp.broadcast_to(pos[None], (3, mb, T))
    h = ll.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    if cfg.mla:
        return _ring_mla_layer(cfg, p, x, h, c, ln, pos,
                               s_start=s_start, s_len=s_len)
    q, k, v = ll.attn_qkv(p["attn"], cfg, h, pos)
    window = cfg.attn_window
    Smax_global = s_len * lax.psum(1, "model")
    rolling = window is not None and Smax_global == window
    assert T == 1 or not rolling, "multi-token ring needs Smax > window"
    quantized = "k_scale" in c
    if quantized:
        k_wr, ksc = ll.quantize_kv(k)
        v_wr, vsc = ll.quantize_kv(v)
    else:
        k_wr, v_wr = k, v
    kc, vc = c["k"], c["v"]
    ks = c.get("k_scale")
    vs = c.get("v_scale")
    for t in range(T):                       # static, small (draft block)
        slot = ((ln + t) % window) if rolling \
            else jnp.minimum(ln + t, Smax_global - 1)
        kc = _masked_slot_update(kc, k_wr[:, t:t + 1], slot, s_start, s_len)
        vc = _masked_slot_update(vc, v_wr[:, t:t + 1], slot, s_start, s_len)
        if quantized:
            ks = _masked_slot_update(ks, ksc[:, t:t + 1], slot, s_start,
                                     s_len)
            vs = _masked_slot_update(vs, vsc[:, t:t + 1], slot, s_start,
                                     s_len)
    if quantized:
        new_c = {"k": kc, "v": vc, "k_scale": ks, "v_scale": vs}
        k_at = ll.dequantize_kv(kc, ks, q.dtype)
        v_at = ll.dequantize_kv(vc, vs, q.dtype)
    else:
        new_c = {"k": kc, "v": vc}
        k_at = kc.astype(q.dtype)
        v_at = vc.astype(q.dtype)
    kv_len = jnp.minimum(ln + T, Smax_global) if window is not None \
        else ln + T
    # rolling SWA buffer: every valid slot is in-window once full, and the
    # stats path masks by absolute position, so pass window=None when the
    # buffer size equals the window (slots are position-permuted).
    eff_window = None if rolling else window
    acc, m_, l_ = ll.verify_attention_stats(q, k_at, v_at, kv_len,
                                            window=eff_window,
                                            pos_offset=s_start)
    out = ll.merge_attention_stats(acc, m_, l_, "model")   # (mb, H, T, hd)
    o = ll.qmm(out.transpose(0, 2, 1, 3).reshape(mb, T, -1).astype(x.dtype),
               p["attn"]["wo"])
    x = x + o
    g = ll.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    if cfg.n_experts:
        y = ll.moe_ffn(p["moe"], cfg, g, lossless=True, tp_axis="model")
    else:
        y = ll.glu_ffn(p["ffn"], g, tp_axis="model")
    return x + y, new_c


def _ring_mla_layer(cfg: ModelConfig, p, x, h, c, ln, pos, *, s_start,
                    s_len):
    """MLA ring decode: latent cache sequence-sharded; absorbed scores are
    computed per shard and merged with the distributed online softmax.
    x: (mb, T, d) — T > 1 scores the speculative draft block causally."""
    mb, T = x.shape[0], x.shape[1]
    pa = p["attn"]
    H = cfg.n_heads
    r_kv = cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    scale = 1.0 / math.sqrt(dn + dr)

    q_lat = ll.rms_norm(ll.qmm(h, pa["wq_a"]), pa["q_norm"], cfg.norm_eps)
    q = ll.qmm(q_lat, pa["wq_b"]).reshape(mb, T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = ll.apply_rope(q_rope, pos, cfg.rope_theta)

    kv = ll.qmm(h, pa["wkv_a"])
    latent = ll.rms_norm(kv[..., :r_kv], pa["kv_norm"], cfg.norm_eps)
    k_rope = ll.apply_rope(kv[..., r_kv:][:, :, None, :], pos,
                           cfg.rope_theta)[:, :, 0]
    lat_cat = jnp.concatenate([latent, k_rope], -1)          # (mb, T, r+dr)

    lc = c["latent"]
    for t in range(T):                       # static, small (draft block)
        lc = _masked_slot_update(lc, lat_cat[:, t:t + 1], ln + t,
                                 s_start, s_len)
    new_c = {"latent": lc}
    lat_all = lc[..., :r_kv].astype(x.dtype)                 # (mb, sl, r)
    rope_all = lc[..., r_kv:].astype(x.dtype)

    wk = pa["wk_b"].reshape(r_kv, H, dn)
    q_abs = jnp.einsum("bthd,rhd->bthr", q_nope, wk)
    s_nope = jnp.einsum("bthr,bsr->bhts", q_abs, lat_all,
                        preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bthd,bsd->bhts", q_rope, rope_all,
                        preferred_element_type=jnp.float32)
    s_all = (s_nope + s_rope) * scale                        # (mb, H, T, sl)
    spos = jnp.arange(s_len) + s_start                       # (sl,)
    qpos = ln[:, None] + jnp.arange(T)[None, :]              # (mb, T)
    mask = spos[None, None, :] <= qpos[:, :, None]           # (mb, T, sl)
    s_all = jnp.where(mask[:, None], s_all, -jnp.inf)
    m_ = jnp.max(s_all, -1)                                  # (mb, H, T)
    m_safe = jnp.where(jnp.isfinite(m_), m_, 0.0)
    pr = jnp.where(mask[:, None], jnp.exp(s_all - m_safe[..., None]), 0.0)
    l_ = pr.sum(-1)
    acc = jnp.einsum("bhts,bsr->bhtr", pr, lat_all.astype(jnp.float32))
    o_lat = ll.merge_attention_stats(acc, m_, l_, "model")   # (mb, H, T, r)
    wv = pa["wv_b"].reshape(r_kv, H, dv)
    out = jnp.einsum("bhtr,rhv->bthv", o_lat.astype(x.dtype), wv)
    o = ll.qmm(out.reshape(mb, T, H * dv), pa["wo"])
    x = x + o
    g = ll.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    y = ll.glu_ffn(p["ffn"], g, tp_axis="model")
    return x + y, new_c


def _ring_ssd_layer(cfg: ModelConfig, p, x, c, ln):
    """SSM ring decode: state update, replicated inside the stage."""
    h = ll.rms_norm(x, p["norm"], cfg.norm_eps)
    y, new_c = ll.ssd_block(p["ssd"], cfg, h, cache=c, decode=True)
    return x + y, new_c


def run_ring_window(cfg: ModelConfig, p_win, x, c_win, ln, *,
                    s_start, s_len):
    """Apply one window of w layers (leading axis of p_win/c_win)."""
    w = jax.tree.leaves(p_win)[0].shape[0]
    new_caches = []
    for i in range(w):
        p_i = _prep_ring_layer(jax.tree.map(lambda a: a[i], p_win))
        c_i = jax.tree.map(lambda a: a[i], c_win)
        if cfg.family == "ssm":
            x, nc = _ring_ssd_layer(cfg, p_i, x, c_i, ln)
        else:
            x, nc = _ring_attn_layer(cfg, p_i, x, c_i, ln,
                                     s_start=s_start, s_len=s_len)
        new_caches.append(nc)
    c_new = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *new_caches)
    return x, c_new


# --------------------------------------------------------------------------- #
#  vocab-sharded embed / unembed
# --------------------------------------------------------------------------- #

def _ring_embed(embed_loc: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """embed_loc: (V/tp, d) local vocab shard; tokens: (B, 1)."""
    v_loc = embed_loc.shape[0]
    off = lax.axis_index("model") * v_loc
    idx = jnp.clip(tokens - off, 0, v_loc - 1)
    emb = jnp.take(embed_loc, idx, axis=0)                   # (B, 1, d)
    ok = (tokens >= off) & (tokens < off + v_loc)
    emb = jnp.where(ok[..., None], emb, 0.0)
    return lax.psum(emb, "model")


def _ring_unembed(params_loc, cfg: ModelConfig, x: jnp.ndarray
                  ) -> jnp.ndarray:
    """x: (B, 1, d) -> local logits (B, 1, V/tp)."""
    if "unembed" in params_loc:
        return x @ params_loc["unembed"]
    return x @ params_loc["embed"].T


# --------------------------------------------------------------------------- #
#  the piped-ring serve step
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class RingPlan:
    """Static ring decode plan (the Halda decision for this mesh)."""
    n_stages: int
    k: int                      # rounds per token
    w: int                      # layers per window
    L_pad: int

    @classmethod
    def make(cls, cfg: ModelConfig, n_stages: int, k: int = 1) -> "RingPlan":
        L_pad = padded_layers(cfg.n_layers, n_stages)
        per_stage = L_pad // n_stages
        assert per_stage % k == 0, (per_stage, k)
        return cls(n_stages=n_stages, k=k, w=per_stage // k, L_pad=L_pad)


def build_ring_serve_step(cfg: ModelConfig, mesh: Mesh, plan: RingPlan,
                          *, n_tokens: int = 1) -> Callable:
    """Returns jit'd serve_step(params_ring, cache_ring, tokens, ln) ->
    (logits, new_cache).

    ``params_ring``/``cache_ring`` must already be in ring layer order
    (``pad_and_permute``) with vocab padded (``pad_vocab``).

    ``n_tokens`` (T): tokens scored per ring pass. T = 1 is the paper's
    one-token-per-ring decode; T > 1 is the speculative *verify* pass —
    tokens (B, T) are written into the cache and scored with causal
    masking among them, ``len`` advances by T, and the engine rolls back
    rejected positions by resetting per-slot ``len`` (the next pass
    overwrites the stale slots).
    """
    if n_tokens > 1 and cfg.family == "ssm":
        raise ValueError("speculative verify needs a rollbackable KV cache; "
                         "ssm state is irreversible")
    M_stages, k, w = plan.n_stages, plan.k, plan.w
    has_pod = "pod" in mesh.axis_names
    pod = ("pod",) if has_pod else ()
    n_steps = k * M_stages + M_stages - 1
    kM = k * M_stages

    def local_fn(tokens, ln, params_loc, cache_loc):
        # local shapes: tokens (B, T), ln (B,) [per-pod batch]
        # params_loc["blocks"]: (k*w, ...); cache_loc["layers"]: (k*w, B, ...)
        m = lax.axis_index("data")
        B = tokens.shape[0]
        mb = B // M_stages
        d = params_loc["embed"].shape[1]
        seq_sharded = cfg.family != "ssm"
        if seq_sharded and cfg.family in ("dense", "moe", "vlm") \
                and not cfg.mla:
            s_len = cache_loc["layers"]["k"].shape[2]
        elif cfg.mla:
            s_len = cache_loc["layers"]["latent"].shape[2]
        else:
            s_len = 0
        s_start = lax.axis_index("model") * s_len

        emb_all = _ring_embed(params_loc["embed"], tokens)    # (B, T, d)
        dtype = emb_all.dtype

        def step(t, carry):
            x, layers_c, out_buf = carry
            e = jnp.mod(t - m, M_stages)                      # microbatch id
            j = t - e                                         # window index
            valid = (j >= 0) & (j < kM)
            r = jnp.clip(j // M_stages, 0, k - 1)

            p_r = jax.tree.map(
                lambda a: lax.dynamic_slice_in_dim(a, r * w, w, axis=0),
                params_loc["blocks"])
            c_r = jax.tree.map(
                lambda a: lax.dynamic_slice_in_dim(
                    lax.dynamic_slice_in_dim(a, r * w, w, axis=0),
                    e * mb, mb, axis=1),
                layers_c)
            ln_mb = lax.dynamic_slice(ln, (e * mb,), (mb,))
            emb_mb = lax.dynamic_slice_in_dim(emb_all, e * mb, mb, axis=0)

            x_in = jnp.where(jnp.equal(j, 0), emb_mb, x)
            x_out, c_new = run_ring_window(cfg, p_r, x_in, c_r, ln_mb,
                                           s_start=s_start, s_len=s_len)

            # masked cache write-back
            def wb(full, new, old):
                sel = jnp.where(valid, new, old)
                inner = lax.dynamic_update_slice_in_dim(
                    lax.dynamic_slice_in_dim(full, r * w, w, axis=0),
                    sel, e * mb, axis=1)
                return lax.dynamic_update_slice_in_dim(full, inner, r * w,
                                                       axis=0)

            layers_c = jax.tree.map(wb, layers_c, c_new, c_r)

            # capture finished microbatch (last window)
            fin = valid & (j == kM - 1)
            hid = ll.rms_norm(x_out, params_loc["final_norm"], cfg.norm_eps)
            cur = lax.dynamic_slice_in_dim(out_buf, e * mb, mb, axis=0)
            out_buf = lax.dynamic_update_slice_in_dim(
                out_buf, jnp.where(fin, hid, cur), e * mb, axis=0)

            # ring hop
            perm = [(i, (i + 1) % M_stages) for i in range(M_stages)]
            x_next = lax.ppermute(x_out, "data", perm)
            return x_next, layers_c, out_buf

        x0 = jnp.zeros((mb, n_tokens, d), dtype)
        out0 = jnp.zeros((B, n_tokens, d), dtype)
        x_fin, layers_c, out_buf = lax.fori_loop(
            0, n_steps, step, (x0, cache_loc["layers"], out0))

        # final hiddens live on the stage that owns the last window;
        # psum over the ring replicates them for the vocab-sharded matmul.
        hidden = lax.psum(out_buf, "data")
        logits_loc = _ring_unembed(params_loc, cfg, hidden)   # (B,T,V/tp)
        new_cache = dict(cache_loc)
        new_cache["layers"] = layers_c
        new_cache["len"] = ln + n_tokens
        return logits_loc, new_cache

    # ---- shard_map wiring -------------------------------------------------
    params_like = None  # resolved at call time via eval_shape by caller

    def make(params_ring, cache_ring):
        p_specs = ring_param_specs(cfg, mesh, params_ring)
        c_specs = ring_cache_specs(cfg, mesh, cache_ring)
        tok_spec = P(pod, None) if pod else P(None, None)
        ln_spec = P(pod) if pod else P()
        out_spec = (P(pod, None, "model") if pod else P(None, None, "model"),
                    c_specs)
        fn = shard_map(local_fn, mesh=mesh,
                       in_specs=(tok_spec, ln_spec, p_specs, c_specs),
                       out_specs=out_spec, check_vma=False)
        return jax.jit(fn, donate_argnums=(3,))

    return make


# --------------------------------------------------------------------------- #
#  streamed piped ring: host-driven microsteps over disk-backed banks
# --------------------------------------------------------------------------- #
#
# ``build_ring_serve_step`` runs the whole k*M + M - 1 microstep schedule
# inside one jit over the full resident layer bank. The streamed variant
# exposes ONE microstep as the jitted unit: the host loop feeds each step
# the (w, ...) window bank it needs (assembled from the layer-sharded
# store by ``streaming.RingBankPrefetcher``), so per-device weight
# residency is bounded by the window size — the paper's pipelined layer
# streaming on the SPMD ring. The KV cache stays device-resident.

def ring_bank_rounds(plan: RingPlan, t: int) -> np.ndarray:
    """(M,) round index r_m(t) stage m computes at microstep t (clipped —
    out-of-schedule stages are masked inside the step anyway)."""
    M_stages, k = plan.n_stages, plan.k
    out = np.zeros(M_stages, dtype=np.int64)
    for m in range(M_stages):
        e = (t - m) % M_stages
        j = t - e
        out[m] = min(max(j // M_stages, 0), k - 1)
    return out


def ring_bank_layers(plan: RingPlan, t: int) -> np.ndarray:
    """(M*w,) global layer index for each row of the step-t window bank.

    Bank row m*w + off is ring-stacked position m*k*w + r_m(t)*w + off,
    i.e. global layer (r_m(t)*M + m)*w + off (rows >= L are zero padding).
    """
    M_stages, k, w = plan.n_stages, plan.k, plan.w
    rs = ring_bank_rounds(plan, t)
    rows = np.zeros(M_stages * w, dtype=np.int64)
    for m in range(M_stages):
        for off in range(w):
            rows[m * w + off] = (rs[m] * M_stages + m) * w + off
    return rows


def ring_bank_specs(cfg: ModelConfig, mesh: Mesh, bank_like):
    """PartitionSpecs for a (M*w, ...) window-bank pytree."""
    def spec(path, leaf):
        key = S._leaf_key(jax.tree_util.keystr(path))
        return S.sanitize(_stacked_leaf_spec(key, leaf.ndim),
                          tuple(leaf.shape), mesh)

    flat, treedef = jax.tree_util.tree_flatten_with_path(bank_like)
    return jax.tree_util.tree_unflatten(treedef,
                                        [spec(p, l) for p, l in flat])


def build_ring_stream_step(cfg: ModelConfig, mesh: Mesh, plan: RingPlan,
                           head_params: Params, cache_like, layer_like, *,
                           n_tokens: int = 1):
    """Build the jitted pieces of the streamed ring pass.

    Returns ``((embed_fn, micro_fn, final_fn), bank_specs)``:

      embed_fn(tokens, head)                  -> emb_all (B, T, d)
      micro_fn(t, x, emb_all, ln, layers_c, out_buf, bank, final_norm)
                                              -> (x, layers_c, out_buf)
      final_fn(out_buf, head)                 -> logits (B, T, V_pad)

    ``bank`` holds each stage's current (w, ...) window
    (``ring_bank_layers`` rows, assembled host-side per microstep);
    ``head_params``/``cache_like`` must be ring-prepared (``pad_vocab``,
    cache via ``pad_and_permute``). Single-pod meshes only — the streamed
    driver is host-paced and pods would need one driver per replica.
    """
    if "pod" in mesh.axis_names:
        raise ValueError("streamed ring does not support the pod axis")
    if n_tokens > 1 and cfg.family == "ssm":
        raise ValueError("speculative verify needs a rollbackable KV cache")
    M_stages, k, w = plan.n_stages, plan.k, plan.w
    kM = k * M_stages

    def embed_local(tokens, head_loc):
        return _ring_embed(head_loc["embed"], tokens)

    def micro_local(t, x, emb_all, ln, layers_c, out_buf, bank_loc,
                    final_norm):
        m = lax.axis_index("data")
        B = emb_all.shape[0]
        mb = B // M_stages
        if cfg.family in ("dense", "moe", "vlm") and not cfg.mla:
            s_len = layers_c["k"].shape[2]
        elif cfg.mla:
            s_len = layers_c["latent"].shape[2]
        else:
            s_len = 0
        s_start = lax.axis_index("model") * s_len

        e = jnp.mod(t - m, M_stages)
        j = t - e
        valid = (j >= 0) & (j < kM)
        r = jnp.clip(j // M_stages, 0, k - 1)

        c_r = jax.tree.map(
            lambda a: lax.dynamic_slice_in_dim(
                lax.dynamic_slice_in_dim(a, r * w, w, axis=0),
                e * mb, mb, axis=1),
            layers_c)
        ln_mb = lax.dynamic_slice(ln, (e * mb,), (mb,))
        emb_mb = lax.dynamic_slice_in_dim(emb_all, e * mb, mb, axis=0)

        x_in = jnp.where(jnp.equal(j, 0), emb_mb, x)
        x_out, c_new = run_ring_window(cfg, bank_loc, x_in, c_r, ln_mb,
                                       s_start=s_start, s_len=s_len)

        def wb(full, new, old):
            sel = jnp.where(valid, new, old)
            inner = lax.dynamic_update_slice_in_dim(
                lax.dynamic_slice_in_dim(full, r * w, w, axis=0),
                sel, e * mb, axis=1)
            return lax.dynamic_update_slice_in_dim(full, inner, r * w,
                                                   axis=0)

        layers_c = jax.tree.map(wb, layers_c, c_new, c_r)

        fin = valid & (j == kM - 1)
        hid = ll.rms_norm(x_out, final_norm, cfg.norm_eps)
        cur = lax.dynamic_slice_in_dim(out_buf, e * mb, mb, axis=0)
        out_buf = lax.dynamic_update_slice_in_dim(
            out_buf, jnp.where(fin, hid, cur), e * mb, axis=0)

        perm = [(i, (i + 1) % M_stages) for i in range(M_stages)]
        x_next = lax.ppermute(x_out, "data", perm)
        return x_next, layers_c, out_buf

    def final_local(out_buf, head_loc):
        hidden = lax.psum(out_buf, "data")
        return _ring_unembed(head_loc, cfg, hidden)

    hp_specs = ring_param_specs(cfg, mesh, head_params)
    c_specs = ring_cache_specs(cfg, mesh, cache_like)["layers"]
    bank_like = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((M_stages * w,) + tuple(a.shape),
                                       a.dtype), layer_like)
    bank_specs = ring_bank_specs(cfg, mesh, bank_like)
    rep = P(None, None, None)     # (B|mb, T, d) activations

    embed_fn = jax.jit(shard_map(
        embed_local, mesh=mesh, in_specs=(P(None, None), hp_specs),
        out_specs=rep, check_vma=False))
    micro_fn = jax.jit(shard_map(
        micro_local, mesh=mesh,
        in_specs=(P(), P("data", None, None), rep, P(None), c_specs,
                  P("data", None, None), bank_specs, P()),
        out_specs=(P("data", None, None), c_specs, P("data", None, None)),
        check_vma=False), donate_argnums=(1, 4, 5))
    final_fn = jax.jit(shard_map(
        final_local, mesh=mesh,
        in_specs=(P("data", None, None), hp_specs),
        out_specs=P(None, None, "model"), check_vma=False))
    return (embed_fn, micro_fn, final_fn), bank_specs


# --------------------------------------------------------------------------- #
#  GSPMD decode path (hybrid / audio / small-batch fallback) + prefill
# --------------------------------------------------------------------------- #

def gspmd_decode_step(cfg: ModelConfig, mesh: Mesh, params_like, cache_like):
    """jit(decode_step) with GSPMD shardings (non-ring baseline and the
    path for architectures whose stack the SPMD ring cannot express)."""
    pspec = S.param_shardings(cfg, mesh, params_like)
    cspec = S.cache_shardings(cfg, mesh, cache_like)
    B = cache_like["len"].shape[0]
    b_spec = S.sanitize(P(S.batch_axes(mesh)), (B, 1), mesh)
    tok = NamedSharding(mesh, b_spec)
    out = NamedSharding(mesh, P(b_spec[0], None, None))

    def fn(params, cache, tokens):
        return M.decode_step(params, cfg, cache, tokens)

    fn = _with_act_constraint(fn, mesh, B)
    return jax.jit(fn, in_shardings=(pspec, cspec, tok),
                   out_shardings=(out, cspec),
                   donate_argnums=(1,))


def _with_act_constraint(fn, mesh: Mesh, batch: int):
    """Pin (B, S, d) activations (and MoE capacity buffers) to
    batch-over-data during tracing."""
    spec = S.sanitize(P(S.batch_axes(mesh), None, None), (batch, 1, 1),
                      mesh)
    act = NamedSharding(mesh, spec)
    moe = NamedSharding(mesh, P(None, S.batch_axes(mesh), None))

    # NOTE (§Perf, refuted): also constraining the MoE (E,C,d) buffers
    # forces GSPMD to materialize both the scatter layout and the target
    # layout (37 -> 108 GiB/chip). The buffer is bounded structurally
    # instead (chunked dispatch in layers.moe_ffn).
    def wrapped(*args):
        M.set_activation_constraint(
            lambda x: lax.with_sharding_constraint(x, act))
        try:
            return fn(*args)
        finally:
            M.set_activation_constraint(None)

    return wrapped


def gspmd_prefill(cfg: ModelConfig, mesh: Mesh, params_like, cache_like, *,
                  has_embeds: bool = False):
    pspec = S.param_shardings(cfg, mesh, params_like)
    cspec = S.cache_shardings(cfg, mesh, cache_like)
    B = cache_like["len"].shape[0]
    b_spec = S.sanitize(P(S.batch_axes(mesh)), (B, 1), mesh)
    tok = NamedSharding(mesh, b_spec)
    out = NamedSharding(mesh, P(b_spec[0], None, None))

    if has_embeds:
        def fn(params, cache, tokens, embeds):
            return M.prefill(params, cfg, tokens, cache, embeds=embeds,
                             remat=True)
        in_sh = (pspec, cspec, tok, S.embeds_sharding(mesh))
    else:
        def fn(params, cache, tokens):
            return M.prefill(params, cfg, tokens, cache, remat=True)
        in_sh = (pspec, cspec, tok)

    fn = _with_act_constraint(fn, mesh, B)
    return jax.jit(fn, in_shardings=in_sh,
                   out_shardings=(out, cspec),
                   donate_argnums=(1,))
