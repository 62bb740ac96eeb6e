"""Quantized (v2) layer store: packed int4 persists through the manifest,
round-trips to zero-copy QuantizedTensor views, streams through the
prefetch window with packed-byte accounting, and reproduces the
resident-dequantized logits exactly. Plus the store-hardening sweep:
v1 backward compatibility, corrupt/truncated manifests, and the
``willneed`` bounds/error-propagation fix."""
import dataclasses
import json
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.latency import quantized_layer_bytes
from repro.launch.mesh import make_mesh
from repro.models import (decode_step, decode_step_layerwise, init_cache,
                          init_params, prefill, prefill_layerwise)
from repro.quant import QuantizedTensor, dequantize_tree, quantize_tree
from repro.runtime.paramstore import (MANIFEST, ParamStore, save_param_store)
from repro.runtime.streaming import StreamingParamSource

KEY = jax.random.PRNGKey(0)


def _cfg(arch="qwen2.5-14b", n_layers=4, **over):
    return dataclasses.replace(get_config(arch).reduced(),
                               n_layers=n_layers, **over)


@pytest.fixture()
def store_dir():
    d = tempfile.mkdtemp(prefix="test_qstore_")
    yield d
    shutil.rmtree(d, ignore_errors=True)


def _trees_exact(a, b):
    flags = jax.tree.map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))),
        a, b)
    return all(jax.tree.leaves(flags))


def _quantized(params):
    qp = dict(params)
    qp["blocks"] = quantize_tree(params["blocks"], bits=4, stacked=True)
    return qp


# --------------------------------------------------------------------------- #
#  v2 round-trip
# --------------------------------------------------------------------------- #

def test_quantized_store_roundtrip_exact(store_dir):
    """save(quantize_tree(params)) -> layer(i) -> dequant must equal the
    resident quantize+dequant exactly (same packed codes, same scales)."""
    cfg = _cfg()
    qp = _quantized(init_params(cfg, KEY))
    save_param_store(qp, cfg, store_dir)
    with ParamStore(store_dir) as store:
        assert store.version == 2
        assert store.quant_format == "q4"
        assert store.n_layers == cfg.n_layers
        for i in range(cfg.n_layers):
            got = store.layer(i)
            want = jax.tree.map(lambda a: a[i], qp["blocks"])
            # packed codes + scales round-trip bit-exactly...
            leaf = got["attn"]["wq"]
            ref = want["attn"]["wq"]
            assert isinstance(leaf, QuantizedTensor)
            assert leaf.bits == ref.bits and leaf.group == ref.group
            assert np.array_equal(np.asarray(leaf.packed),
                                  np.asarray(ref.packed))
            assert np.array_equal(np.asarray(leaf.scale),
                                  np.asarray(ref.scale))
            # ...so dequantization is exactly the resident computation
            assert _trees_exact(dequantize_tree(got), dequantize_tree(want))


def test_quantized_store_packed_footprint(store_dir):
    """The store's layer files hold the packed bytes: well under a bf16
    store of the same blocks, and near the analytic reduced-b estimate."""
    cfg = _cfg()
    params = init_params(cfg, KEY)
    save_param_store(_quantized(params), cfg, store_dir)
    bdir = tempfile.mkdtemp(prefix="test_qstore_bf16_")
    try:
        bf16 = dict(params)
        bf16["blocks"] = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                                      params["blocks"])
        save_param_store(bf16, cfg, bdir)
        with ParamStore(store_dir) as qs, ParamStore(bdir) as bs:
            ratio = qs.layer_nbytes / bs.layer_nbytes
            assert ratio <= 0.35, ratio
            # analytic reduced b (norms/biases stream f32 here, so the
            # store sits a little above the pure-weight estimate)
            est = quantized_layer_bytes(bs.layer_nbytes)
            assert est <= qs.layer_nbytes <= 1.5 * est
    finally:
        shutil.rmtree(bdir, ignore_errors=True)


def test_quantized_store_head_leaves(store_dir):
    """QuantizedTensor head leaves (e.g. a quantized unembed) persist and
    reassemble like block leaves."""
    from repro.quant import quantize_q4

    cfg = _cfg(n_layers=2)
    params = init_params(cfg, KEY)
    qp = _quantized(params)
    if "unembed" not in qp:
        qp["unembed"] = jax.random.normal(KEY, (cfg.d_model, cfg.vocab))
    qp["unembed"] = quantize_q4(qp["unembed"])
    save_param_store(qp, cfg, store_dir)
    with ParamStore(store_dir) as store:
        head = store.head()
        assert isinstance(head["unembed"], QuantizedTensor)
        assert _trees_exact(dequantize_tree(head["unembed"]),
                            dequantize_tree(qp["unembed"]))


def test_quantized_store_64_layers_skips_stacked_biases(store_dir):
    """n_layers divisible by the group must not turn (L, D) bias leaves
    into cross-layer 'weights': stacked=True quantization only touches
    ndim>=3 matmul leaves, so the per-layer store sharding survives at
    the paper's 30-70B layer counts (e.g. 64-layer qwen1.5-32b)."""
    cfg = _cfg("qwen1.5-32b", n_layers=64)
    params = init_params(cfg, KEY)
    qp = dict(params)
    qp["blocks"] = quantize_tree(params["blocks"], bits=4, stacked=True)
    assert isinstance(qp["blocks"]["attn"]["wq"], QuantizedTensor)
    assert not isinstance(qp["blocks"]["attn"]["bq"], QuantizedTensor)
    save_param_store(qp, cfg, store_dir)          # used to raise: axis != L
    with ParamStore(store_dir) as store:
        assert store.n_layers == 64
        got = store.layer(63)
        want = jax.tree.map(lambda a: a[63], qp["blocks"])
        assert _trees_exact(dequantize_tree(got), dequantize_tree(want))


def test_quantized_store_ssm(store_dir):
    cfg = _cfg("mamba2-780m", n_layers=2)
    qp = _quantized(init_params(cfg, KEY))
    save_param_store(qp, cfg, store_dir)
    with ParamStore(store_dir) as store:
        got = dequantize_tree(store.layer(1))
        want = dequantize_tree(jax.tree.map(lambda a: a[1], qp["blocks"]))
        assert _trees_exact(got, want)


# --------------------------------------------------------------------------- #
#  streamed decode: packed bytes through the window, exact parity
# --------------------------------------------------------------------------- #

def test_streamed_q4_matches_resident_dequantized(store_dir):
    """Streaming the packed store must reproduce the resident-dequantized
    tokens exactly, while staging ~4x fewer bytes per layer."""
    cfg = _cfg(n_layers=4)
    params = init_params(cfg, KEY)
    qp = _quantized(params)
    dp = dict(params)
    dp["blocks"] = dequantize_tree(qp["blocks"], jnp.float32)
    save_param_store(qp, cfg, store_dir)
    raw_layer = sum(a.nbytes for a in
                    jax.tree.leaves(params["blocks"])) // cfg.n_layers

    B, S, steps = 2, 8, 3
    toks = jax.random.randint(KEY, (B, S + steps), 0, cfg.vocab)
    cache_r = init_cache(cfg, B, 32, dtype=jnp.float32)
    lg_r, cache_r = prefill(dp, cfg, toks[:, :S], cache_r)

    src = StreamingParamSource(ParamStore(store_dir), window=2)
    try:
        cache_s = init_cache(cfg, B, 32, dtype=jnp.float32)
        lg_s, cache_s = prefill_layerwise(src, cfg, toks[:, :S], cache_s)
        assert _trees_exact(jnp.argmax(lg_r[:, -1], -1),
                            jnp.argmax(lg_s[:, -1], -1))
        for t in range(S, S + steps):
            lg_r, cache_r = decode_step(dp, cfg, cache_r, toks[:, t:t + 1])
            lg_s, cache_s = decode_step_layerwise(src, cfg, cache_s,
                                                  toks[:, t:t + 1])
            assert _trees_exact(jnp.argmax(lg_r[:, 0], -1),
                                jnp.argmax(lg_s[:, 0], -1))
        st = src.stats()
        # byte accounting sees the packed leaves, not the dequant width
        assert st.bytes_per_layer == src.store.layer_nbytes
        assert st.bytes_per_layer < 0.35 * raw_layer / 2  # vs bf16 = raw/2
        assert st.peak_resident_bytes <= 2 * src.store.layer_nbytes
    finally:
        src.close()


# --------------------------------------------------------------------------- #
#  quantized store through the streamed SPMD ring
# --------------------------------------------------------------------------- #

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 CPU devices (conftest sets flag)")


@needs_8_devices
def test_ring_stream_quantized_store(store_dir):
    from repro.runtime import serve
    from repro.runtime.streaming import StreamingRingDriver

    cfg = _cfg(n_layers=8)
    params = init_params(cfg, KEY)
    pq, skipped = serve.quantize_ring_params(dict(params), cfg, tp=2)
    assert skipped == []
    pd = dict(pq)
    pd["blocks"] = serve.dequant_ring_reference(pq["blocks"])

    B, Smax, steps = 8, 32, 3
    toks = jax.random.randint(KEY, (B, steps), 0, cfg.vocab)
    cache_r = init_cache(cfg, B, Smax, dtype=jnp.float32)
    refs = []
    for t in range(steps):
        lg, cache_r = decode_step(pd, cfg, cache_r, toks[:, t:t + 1])
        refs.append(lg)

    mesh = make_mesh((4, 2), ("data", "model"))
    plan = serve.RingPlan.make(cfg, 4, k=2)
    head = {k: v for k, v in serve.pad_vocab(dict(params), cfg, 2).items()
            if k != "blocks"}
    cache_s = init_cache(cfg, B, Smax, dtype=jnp.float32)
    cache_s["layers"] = serve.pad_and_permute(cache_s["layers"], cfg, 4, 2)

    save_param_store(pq, cfg, store_dir)
    drv = StreamingRingDriver(cfg, mesh, plan, ParamStore(store_dir),
                              head_params=head, cache_like=cache_s)
    ln = jnp.zeros((B,), jnp.int32)
    scale = float(jnp.max(jnp.abs(refs[-1])))
    for t in range(steps):
        logits, cache_s = drv.step(toks[:, t:t + 1], ln, cache_s)
        ln = ln + 1
        rel = float(jnp.max(jnp.abs(
            logits[:, :, :cfg.vocab] - refs[t]))) / scale
        assert rel < 2e-4, (t, rel)
    assert drv.stats().total_bytes_read > 0
    drv.close()


# --------------------------------------------------------------------------- #
#  manifest compatibility + error paths
# --------------------------------------------------------------------------- #

def test_v1_manifest_backward_compat(store_dir):
    """Unquantized saves stay version 1 and load byte-identically — a v2
    reader must accept stores written before quantized leaves existed."""
    cfg = _cfg(n_layers=2)
    params = init_params(cfg, KEY)
    save_param_store(params, cfg, store_dir)
    mpath = os.path.join(store_dir, MANIFEST)
    with open(mpath) as f:
        m = json.load(f)
    assert m["version"] == 1
    assert all("part" not in d and "quant" not in d for d in m["leaves"])
    # a genuinely old manifest has no version key at all -> implied v1
    del m["version"]
    with open(mpath, "w") as f:
        json.dump(m, f)
    with ParamStore(store_dir) as store:
        assert store.version == 1
        assert store.quant_format is None
        want = jax.tree.map(lambda a: a[0], params["blocks"])
        assert _trees_exact(store.layer(0), want)


def test_corrupt_manifest_raises(store_dir):
    cfg = _cfg(n_layers=2)
    save_param_store(init_params(cfg, KEY), cfg, store_dir)
    mpath = os.path.join(store_dir, MANIFEST)

    with open(mpath) as f:
        good = f.read()

    # truncated mid-JSON
    with open(mpath, "w") as f:
        f.write(good[:len(good) // 2])
    with pytest.raises(ValueError, match="corrupt param-store manifest"):
        ParamStore(store_dir)

    # future / unknown version
    m = json.loads(good)
    m["version"] = 99
    with open(mpath, "w") as f:
        json.dump(m, f)
    with pytest.raises(ValueError, match="unsupported param-store"):
        ParamStore(store_dir)

    # valid JSON but missing required keys
    m = json.loads(good)
    del m["leaves"]
    with open(mpath, "w") as f:
        json.dump(m, f)
    with pytest.raises(ValueError, match="missing"):
        ParamStore(store_dir)


def test_quantized_manifest_missing_subleaf_raises(store_dir):
    """A v2 manifest whose scale sub-leaf vanished is corruption, not a
    silently-bf16 layer."""
    cfg = _cfg(n_layers=2)
    save_param_store(_quantized(init_params(cfg, KEY)), cfg, store_dir)
    mpath = os.path.join(store_dir, MANIFEST)
    with open(mpath) as f:
        m = json.load(f)
    m["leaves"] = [d for d in m["leaves"] if d.get("part") != "scale"]
    with open(mpath, "w") as f:
        json.dump(m, f)
    store = ParamStore(store_dir)
    try:
        with pytest.raises(ValueError, match="missing its scale"):
            store.layer(0)
    finally:
        store.close()


def test_quantized_manifest_null_quant_record_raises(store_dir):
    """quant: null on a packed/scale sub-leaf is corruption too — it must
    raise the same descriptive ValueError, not leak a KeyError."""
    cfg = _cfg(n_layers=2)
    save_param_store(_quantized(init_params(cfg, KEY)), cfg, store_dir)
    mpath = os.path.join(store_dir, MANIFEST)
    with open(mpath) as f:
        m = json.load(f)
    for d in m["leaves"]:
        if d.get("part"):
            d["quant"] = None
    with open(mpath, "w") as f:
        json.dump(m, f)
    store = ParamStore(store_dir)
    try:
        with pytest.raises(ValueError, match="quant record is missing"):
            store.layer(0)
    finally:
        store.close()


# --------------------------------------------------------------------------- #
#  willneed: bounds + error propagation (the prefetch-hint bugfix)
# --------------------------------------------------------------------------- #

def test_willneed_out_of_range_raises(store_dir):
    cfg = _cfg(n_layers=2)
    save_param_store(init_params(cfg, KEY), cfg, store_dir)
    with ParamStore(store_dir) as store:
        store.willneed(0)                    # in range: fine
        store.willneed(cfg.n_layers - 1)
        with pytest.raises(IndexError):
            store.willneed(cfg.n_layers)     # past the stack
        with pytest.raises(IndexError):
            store.willneed(-1)


def test_willneed_missing_layer_file_propagates(store_dir):
    """A vanished layer_*.bin is store corruption — willneed must surface
    the OSError instead of swallowing it as a failed madvise hint."""
    cfg = _cfg(n_layers=2)
    save_param_store(init_params(cfg, KEY), cfg, store_dir)
    os.remove(os.path.join(store_dir, "layer_00001.bin"))
    with ParamStore(store_dir) as store:
        store.willneed(0)                    # intact layer still fine
        with pytest.raises(OSError):
            store.willneed(1)


def test_streamed_q4_mla_matches_resident_dequantized(store_dir):
    """Regression: MLA consumes its o-proj outside ``layers.qmm``'s
    original call sites — a quantized store streamed through the
    layer-wise MLA path must still decode (packed ``wo`` routed through
    the fused dispatch) and match the resident-dequantized tokens."""
    cfg = _cfg("minicpm3-4b", n_layers=2)
    params = init_params(cfg, KEY)
    qp = _quantized(params)
    dp = dict(params)
    dp["blocks"] = dequantize_tree(qp["blocks"], jnp.float32)
    save_param_store(qp, cfg, store_dir)

    B, S, steps = 2, 6, 3
    toks = jax.random.randint(KEY, (B, S + steps), 0, cfg.vocab)
    cache_r = init_cache(cfg, B, 32, dtype=jnp.float32)
    lg_r, cache_r = prefill(dp, cfg, toks[:, :S], cache_r)

    src = StreamingParamSource(ParamStore(store_dir), window=2)
    try:
        cache_s = init_cache(cfg, B, 32, dtype=jnp.float32)
        lg_s, cache_s = prefill_layerwise(src, cfg, toks[:, :S], cache_s)
        assert _trees_exact(jnp.argmax(lg_r[:, -1], -1),
                            jnp.argmax(lg_s[:, -1], -1))
        for t in range(S, S + steps):
            lg_r, cache_r = decode_step(dp, cfg, cache_r, toks[:, t:t + 1])
            lg_s, cache_s = decode_step_layerwise(src, cfg, cache_s,
                                                  toks[:, t:t + 1])
            assert _trees_exact(jnp.argmax(lg_r[:, 0], -1),
                                jnp.argmax(lg_s[:, 0], -1))
    finally:
        src.close()
