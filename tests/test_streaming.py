"""Weight-streaming subsystem: layer-sharded param store, async
prefetcher (window bound + release-behind-front), layer-wise forward
parity, continuous-batching integration, and the streamed SPMD ring."""
import dataclasses
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import (decode_step, decode_step_layerwise, forward,
                          forward_layerwise, init_cache, init_params,
                          prefill, prefill_layerwise)
from repro.runtime.paramstore import (ParamStore, ResidentSource,
                                      load_resident, save_param_store)
from repro.runtime.streaming import (LayerPrefetcher, PrefetchEvent,
                                     StreamingParamSource,
                                     make_streaming_engine)

KEY = jax.random.PRNGKey(0)


def _cfg(arch="qwen2.5-14b", n_layers=4, **over):
    return dataclasses.replace(get_config(arch).reduced(),
                               n_layers=n_layers, **over)


@pytest.fixture()
def store_dir():
    d = tempfile.mkdtemp(prefix="test_paramstore_")
    yield d
    shutil.rmtree(d, ignore_errors=True)


def _trees_equal(a, b):
    flags = jax.tree.map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))),
        a, b)
    return all(jax.tree.leaves(flags))


# --------------------------------------------------------------------------- #
#  store round-trip
# --------------------------------------------------------------------------- #

def test_store_roundtrip_exact(store_dir):
    cfg = _cfg()
    params = init_params(cfg, KEY)
    save_param_store(params, cfg, store_dir)
    with ParamStore(store_dir) as store:
        assert store.n_layers == cfg.n_layers
        assert store.layer_nbytes > 0
        assert _trees_equal(params, load_resident(store))


def test_store_roundtrip_bf16(store_dir):
    cfg = _cfg(n_layers=2)
    params = init_params(cfg, KEY, dtype=jnp.bfloat16)
    save_param_store(params, cfg, store_dir)
    with ParamStore(store_dir) as store:
        back = load_resident(store)
        assert _trees_equal(params, back)
        leaf = jax.tree.leaves(back["blocks"])[0]
        assert leaf.dtype.name == "bfloat16"


def test_store_roundtrip_ssm(store_dir):
    cfg = _cfg("mamba2-780m", n_layers=2)
    params = init_params(cfg, KEY)
    save_param_store(params, cfg, store_dir)
    with ParamStore(store_dir) as store:
        assert _trees_equal(params, load_resident(store))


def test_store_rejects_unsharded_family(store_dir):
    cfg = get_config("recurrentgemma-9b").reduced()   # hybrid: groups/tail
    params = init_params(cfg, KEY)
    with pytest.raises(ValueError):
        save_param_store(params, cfg, store_dir)


def test_store_release_is_safe(store_dir):
    cfg = _cfg(n_layers=2)
    save_param_store(init_params(cfg, KEY), cfg, store_dir)
    with ParamStore(store_dir) as store:
        store.release(0)              # unmapped layer: no-op
        p0 = store.layer(0)
        ref = jax.tree.map(lambda a: np.array(a, copy=True), p0)
        store.release(0)              # mapped: pages dropped, refault on read
        assert _trees_equal(ref, store.layer(0))


# --------------------------------------------------------------------------- #
#  prefetcher: window bound + release behind the front
# --------------------------------------------------------------------------- #

def test_prefetcher_residency_bounded_by_window(store_dir):
    cfg = _cfg(n_layers=6)
    save_param_store(init_params(cfg, KEY), cfg, store_dir)
    store = ParamStore(store_dir)
    pf = LayerPrefetcher(store, window=2, device_put=False)
    try:
        for _pass in range(2):                  # cyclic decode pattern
            for i in range(cfg.n_layers):
                p = pf.get(i)
                assert jax.tree.leaves(p)[0] is not None
        st = pf.stats()
        assert st.peak_resident_bytes <= 2 * store.layer_nbytes
        assert st.layers_served == 2 * cfg.n_layers
        # window < L forces re-reads every pass (plus up to one cyclic
        # speculative read past the final front position)
        assert 2 * cfg.n_layers <= len(st.events) <= 2 * cfg.n_layers + 2
        assert st.releases > 0                  # pages dropped behind front
    finally:
        pf.close()
        store.close()


def test_prefetcher_random_access_correct(store_dir):
    cfg = _cfg(n_layers=4)
    params = init_params(cfg, KEY)
    save_param_store(params, cfg, store_dir)
    store = ParamStore(store_dir)
    pf = LayerPrefetcher(store, window=2, device_put=False)
    try:
        for i in (3, 0, 2, 1, 3):
            got = pf.get(i)
            want = jax.tree.map(lambda a: a[i], params["blocks"])
            assert _trees_equal(got, want)
    finally:
        pf.close()
        store.close()


def test_prefetcher_staging_failure_raises_not_hangs(store_dir):
    """A worker-thread failure must surface in get() as an error, never a
    deadlock (the store directory vanishing mid-serve, an IO error...)."""
    cfg = _cfg(n_layers=4)
    save_param_store(init_params(cfg, KEY), cfg, store_dir)
    store = ParamStore(store_dir)
    store.layer_nbytes = 1 << 40          # poison: reads past EOF
    pf = LayerPrefetcher(store, window=2, device_put=False)
    try:
        with pytest.raises(RuntimeError, match="prefetch of layer"):
            pf.get(0)
    finally:
        pf.close()
        store.close()


# --------------------------------------------------------------------------- #
#  layer-wise forward parity (the acceptance criterion)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("arch", ["qwen2.5-14b", "mamba2-780m"])
def test_layerwise_matches_scan_resident(arch):
    cfg = _cfg(arch, n_layers=3)
    params = init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 10), 0, cfg.vocab)
    full = forward(params, cfg, toks)
    lw = forward_layerwise(ResidentSource(params), cfg, toks)
    scale = float(jnp.max(jnp.abs(full)))
    assert float(jnp.max(jnp.abs(full - lw))) / scale < 1e-5


@pytest.mark.parametrize("arch", ["qwen2.5-14b", "minicpm3-4b",
                                  "mamba2-780m"])
def test_streamed_decode_matches_resident(arch, store_dir):
    """Window < L: streamed prefill + decode must equal the resident path
    within test tolerance, with residency bounded by the window."""
    cfg = _cfg(arch, n_layers=4)
    params = init_params(cfg, KEY)
    save_param_store(params, cfg, store_dir)
    B, S, steps = 2, 8, 3
    toks = jax.random.randint(KEY, (B, S + steps), 0, cfg.vocab)

    cache_r = init_cache(cfg, B, 32, dtype=jnp.float32)
    lg_r, cache_r = prefill(params, cfg, toks[:, :S], cache_r)

    src = StreamingParamSource(ParamStore(store_dir), window=2)
    try:
        cache_s = init_cache(cfg, B, 32, dtype=jnp.float32)
        lg_s, cache_s = prefill_layerwise(src, cfg, toks[:, :S], cache_s)
        scale = float(jnp.max(jnp.abs(lg_r)))
        assert float(jnp.max(jnp.abs(lg_r - lg_s))) / scale < 1e-5
        for t in range(S, S + steps):
            lg_r, cache_r = decode_step(params, cfg, cache_r,
                                        toks[:, t:t + 1])
            lg_s, cache_s = decode_step_layerwise(src, cfg, cache_s,
                                                  toks[:, t:t + 1])
            rel = float(jnp.max(jnp.abs(lg_r - lg_s))) / scale
            assert rel < 1e-5, (arch, t, rel)
        st = src.stats()
        assert st.peak_resident_bytes <= 2 * src.store.layer_nbytes
    finally:
        src.close()


def test_streamed_multi_token_verify(store_dir):
    """T>1 speculative verify through the streamed path == resident."""
    cfg = _cfg(n_layers=3)
    params = init_params(cfg, KEY)
    save_param_store(params, cfg, store_dir)
    B, T = 2, 3
    toks = jax.random.randint(KEY, (B, T), 0, cfg.vocab)
    cache_r = init_cache(cfg, B, 32, dtype=jnp.float32)
    lg_r, _ = decode_step(params, cfg, cache_r, toks)
    with StreamingParamSource(ParamStore(store_dir), window=2) as src:
        cache_s = init_cache(cfg, B, 32, dtype=jnp.float32)
        lg_s, _ = decode_step_layerwise(src, cfg, cache_s, toks)
    scale = float(jnp.max(jnp.abs(lg_r)))
    assert float(jnp.max(jnp.abs(lg_r - lg_s))) / scale < 1e-5


# --------------------------------------------------------------------------- #
#  continuous batching over a streamed source
# --------------------------------------------------------------------------- #

def test_engine_streamed_matches_resident(store_dir):
    from repro.data import RequestGenerator

    cfg = _cfg(n_layers=2)
    params = init_params(cfg, KEY)
    save_param_store(params, cfg, store_dir)
    B, ctx = 2, 64
    reqs = RequestGenerator(cfg.vocab, prompt_len=(4, 9), max_new=5,
                            seed=3).generate(4)

    eng_r = make_streaming_engine(ResidentSource(params), cfg, B, ctx)
    cache = init_cache(cfg, B, ctx, dtype=jnp.float32)
    fin_r, _ = eng_r.run(cache, list(reqs))

    src = StreamingParamSource(ParamStore(store_dir), window=1)
    try:
        eng_s = make_streaming_engine(src, cfg, B, ctx)
        cache = init_cache(cfg, B, ctx, dtype=jnp.float32)
        fin_s, _ = eng_s.run(cache, list(reqs))
        assert {f.uid: f.tokens for f in fin_s} == \
               {f.uid: f.tokens for f in fin_r}
        st = eng_s.streaming_stats()
        assert st is not None
        assert st.peak_resident_bytes <= src.store.layer_nbytes
        assert eng_r.streaming_stats() is None   # ResidentSource: no stats
    finally:
        src.close()


# --------------------------------------------------------------------------- #
#  streamed SPMD ring
# --------------------------------------------------------------------------- #

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 CPU devices (conftest sets flag)")


def _ring_stream_parity(arch, *, n_layers=8, k=2, B=8, Smax=32, steps=3,
                        tol=2e-4, n_tokens=1):
    from repro.runtime import serve
    from repro.runtime.streaming import StreamingRingDriver

    cfg = _cfg(arch, n_layers=n_layers)
    params = init_params(cfg, KEY)
    T = n_tokens
    toks = jax.random.randint(KEY, (B, steps * T), 0, cfg.vocab)

    cache_r = init_cache(cfg, B, Smax, dtype=jnp.float32)
    refs = []
    for t in range(steps):
        lg, cache_r = decode_step(params, cfg, cache_r,
                                  toks[:, t * T:(t + 1) * T])
        refs.append(lg)

    mesh = make_mesh((4, 2), ("data", "model"))
    plan = serve.RingPlan.make(cfg, 4, k=k)
    pr = serve.pad_vocab(dict(params), cfg, 2)
    head = {kk: v for kk, v in pr.items() if kk != "blocks"}
    cache_s = init_cache(cfg, B, Smax, dtype=jnp.float32)
    cache_s["layers"] = serve.pad_and_permute(cache_s["layers"], cfg, 4, k)

    d = tempfile.mkdtemp(prefix="test_ringstore_")
    try:
        save_param_store(params, cfg, d)
        drv = StreamingRingDriver(cfg, mesh, plan, ParamStore(d),
                                  head_params=head, cache_like=cache_s,
                                  n_tokens=T)
        ln = jnp.zeros((B,), jnp.int32)
        scale = float(jnp.max(jnp.abs(refs[-1])))
        for t in range(steps):
            logits, cache_s = drv.step(toks[:, t * T:(t + 1) * T], ln,
                                       cache_s)
            ln = ln + T
            rel = float(jnp.max(jnp.abs(
                logits[:, :, :cfg.vocab] - refs[t]))) / scale
            assert rel < tol, (arch, k, t, rel)
        assert drv.stats().total_bytes_read > 0
        drv.close()
    finally:
        shutil.rmtree(d, ignore_errors=True)


@needs_8_devices
@pytest.mark.parametrize("k", [1, 2])
def test_ring_stream_dense(k):
    _ring_stream_parity("qwen2.5-14b", k=k)


@needs_8_devices
def test_ring_stream_verify_multi_token():
    _ring_stream_parity("qwen2.5-14b", k=2, n_tokens=2)


@needs_8_devices
def test_ring_stream_layer_padding():
    _ring_stream_parity("minitron-8b", n_layers=6, k=1)


# --------------------------------------------------------------------------- #
#  latency-model cross-check plumbing
# --------------------------------------------------------------------------- #

def test_streaming_crosscheck():
    from repro.core.latency import streaming_crosscheck, streaming_disk_term
    from repro.core.profiles import DeviceProfile

    dev = DeviceProfile(name="x", disk_seq_bps=1e9, disk_rand_bps=1e9)
    layer_bytes = 1e8                            # 0.1 s/layer predicted
    assert streaming_disk_term(dev, layer_bytes) == pytest.approx(0.1)
    events = [PrefetchEvent(layer=i, t_start=0.0, t_end=0.11,
                            nbytes=int(layer_bytes)) for i in range(5)]
    chk = streaming_crosscheck(dev, layer_bytes, events)
    assert chk.ratio == pytest.approx(1.1)
    assert chk.consistent
    assert chk.measured_bps == pytest.approx(1e8 / 0.11)
    # an order-of-magnitude drift flags as inconsistent
    slow = [PrefetchEvent(layer=0, t_start=0.0, t_end=2.0,
                          nbytes=int(layer_bytes))]
    assert not streaming_crosscheck(dev, layer_bytes, slow).consistent
