"""Quantized ring weight bank: int4 storage must reproduce the
dequantized-reference logits exactly (the only approximation is the
quantization itself, bounded by test_quant)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import decode_step, init_cache, init_params
from repro.runtime import serve

needs_8 = pytest.mark.skipif(jax.device_count() < 8,
                             reason="needs 8 CPU devices")


@needs_8
@pytest.mark.parametrize("arch", ["qwen2.5-14b", "mixtral-8x7b"])
def test_ring_q4_matches_dequantized_reference(arch):
    cfg = dataclasses.replace(get_config(arch).reduced(), n_layers=8)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    B, Smax = 8, 32
    mesh = make_mesh((4, 2), ("data", "model"))
    toks = jax.random.randint(key, (B, 4), 0, cfg.vocab)

    # reference: plain decode with dequantized weights (same numerics
    # policy as the ring window body — qmm-consumed leaves f32, rest bf16)
    pq, skipped = serve.quantize_ring_params(dict(params), cfg, tp=2)
    assert skipped == []
    pd = dict(pq)
    pd["blocks"] = serve.dequant_ring_reference(pq["blocks"])
    cache_ref = init_cache(cfg, B, Smax, dtype=jnp.float32)
    refs = []
    for t in range(3):
        lg, cache_ref = decode_step(pd, cfg, cache_ref, toks[:, t:t + 1])
        refs.append(lg)

    plan = serve.RingPlan.make(cfg, 4, k=2)
    pr = serve.pad_vocab(dict(params), cfg, 2)
    pr["blocks"] = serve.pad_and_permute(params["blocks"], cfg, 4, 2)
    pr, _ = serve.quantize_ring_params(pr, cfg, tp=2)
    cache = init_cache(cfg, B, Smax, dtype=jnp.float32)
    cache["layers"] = serve.pad_and_permute(cache["layers"], cfg, 4, 2)
    step = serve.build_ring_serve_step(cfg, mesh, plan)(pr, cache)
    ln = jnp.zeros((B,), jnp.int32)
    for t in range(3):
        logits, cache = step(toks[:, t:t + 1], ln, pr, cache)
        ln = ln + 1
        rel = float(jnp.max(jnp.abs(logits[:, :, :cfg.vocab] - refs[t]))
                    ) / float(jnp.max(jnp.abs(refs[t])))
        assert rel < 2e-4, (arch, t, rel)


def test_quantize_ring_params_selective():
    cfg = get_config("qwen2.5-14b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    pq, skipped = serve.quantize_ring_params(params, cfg, tp=2)
    assert skipped == []
    from repro.quant.grouped import QuantizedTensor
    flat = jax.tree_util.tree_flatten_with_path(
        pq["blocks"], is_leaf=lambda x: isinstance(x, QuantizedTensor))[0]
    kinds = {}
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        kinds[name.split("'")[-2]] = isinstance(leaf, QuantizedTensor)
    assert kinds["wq"] and kinds["w_down"]
    assert not kinds["attn_norm"] and not kinds["bq"]


def test_prep_ring_layer_keeps_q4_packed_for_qmm():
    """The ring microstep must hand q4 matmul weights to ``ll.qmm`` still
    packed (fused dequant-matmul streams the int4 bytes; a bf16
    materialization would forfeit the 0.27x ring traffic) while
    non-matmul leaves (norms, biases, routers) dequantize up front."""
    from repro.quant.grouped import QuantizedTensor

    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              n_layers=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    pq, skipped = serve.quantize_ring_params(dict(params), cfg, tp=2)
    assert skipped == []

    # slice layer 0 out of the stacked banks (member-wise for packed)
    def slice0(leaf):
        if isinstance(leaf, QuantizedTensor):
            return QuantizedTensor(packed=leaf.packed[0],
                                   scale=leaf.scale[0], bits=leaf.bits,
                                   group=leaf.group, shape=leaf.shape[1:])
        return leaf[0]
    layer0 = jax.tree.map(
        slice0, pq["blocks"],
        is_leaf=lambda x: isinstance(x, QuantizedTensor))
    prepped = serve._prep_ring_layer(layer0)

    def walk(tree, out, prefix=""):
        for k, v in tree.items():
            if isinstance(v, dict):
                walk(v, out, k)
            else:
                out[k] = v
        return out
    leaves = walk(prepped, {})
    for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert isinstance(leaves[k], QuantizedTensor), k
        assert leaves[k].packed.dtype == jnp.int8   # packed int4 pairs
    # norms/biases were never quantized and pass through as plain arrays
    assert not isinstance(leaves["attn_norm"], QuantizedTensor)
    assert not isinstance(leaves["bq"], QuantizedTensor)


def test_quantize_ring_params_reports_skipped():
    """A leaf no group size fits must be surfaced, not silently left bf16
    (a hidden compression cap would skew the streamed-bytes accounting)."""
    import numpy as np
    from repro.quant.grouped import QuantizedTensor

    cfg = get_config("qwen2.5-14b").reduced()
    blocks = {"wq": jnp.asarray(np.zeros((4, 64, 64), np.float32)),
              # K=50: not divisible by 64/32/16 -> unquantizable
              "wo": jnp.asarray(np.zeros((4, 50, 64), np.float32))}
    pq, skipped = serve.quantize_ring_params({"blocks": blocks}, cfg, tp=2)
    assert isinstance(pq["blocks"]["wq"], QuantizedTensor)
    assert not isinstance(pq["blocks"]["wo"], QuantizedTensor)
    assert skipped == ["wo (K=50)"]
