"""Paged KV cache allocator: BlockPool invariants, prefix cache, offload."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # container lacks hypothesis
    from conftest import hypothesis_fallback as _hf
    given, settings, st = _hf.given, _hf.settings, _hf.st

from repro.runtime.kvcache import (SINK_PAGE, BlockOffloader, BlockPool,
                                   PoolExhausted, chain_key)


def test_pool_alloc_release_roundtrip():
    pool = BlockPool(8, 16)
    pids = [pool.alloc() for _ in range(7)]
    assert len(set(pids)) == 7 and SINK_PAGE not in pids
    assert pool.n_free == 0
    with pytest.raises(PoolExhausted):
        pool.alloc()
    for p in pids:
        pool.release(p)
    assert pool.n_free == 7 and pool.n_active == 0
    pool.check()


def test_pool_double_free_raises():
    pool = BlockPool(4, 8)
    p = pool.alloc()
    pool.release(p)
    with pytest.raises(ValueError, match="double free"):
        pool.release(p)
    pool.check()


def test_pool_refcount_share_and_release():
    pool = BlockPool(4, 8)
    p = pool.alloc()
    pool.register(123, p)
    pool.retain(p)                       # second owner (prefix share)
    assert pool.refcount(p) == 2
    pool.release(p)
    assert pool.refcount(p) == 1         # still active for the sharer
    pool.release(p)
    # hashed page at refcount 0 parks in the prefix cache, not free list
    assert pool.n_cached == 1 and pool.refcount(p) == 0
    assert pool.lookup(123) == p
    pool.retain(p)                       # cache hit revives it
    assert pool.refcount(p) == 1 and pool.n_cached == 0
    pool.check()


def test_pool_lru_eviction_order():
    pool = BlockPool(4, 8)               # 3 usable pages
    pages = []
    for h in (1, 2, 3):
        p = pool.alloc()
        pool.register(h, p)
        pages.append(p)
    for p in pages:
        pool.release(p)                  # all cached, LRU order 1,2,3
    evicted = []
    pool.alloc(evict_cb=lambda pid, h: evicted.append(h))
    pool.alloc(evict_cb=lambda pid, h: evicted.append(h))
    assert evicted == [1, 2]             # least-recently-cached first
    assert pool.lookup(3) is not None    # newest survivor
    pool.check()


def test_pool_unregister_blocks_future_lookup():
    pool = BlockPool(4, 8)
    p = pool.alloc()
    pool.register(77, p)
    pool.unregister(p)                   # page about to be written
    assert pool.lookup(77) is None
    pool.release(p)
    assert pool.n_free == 3              # unhashed -> free list, not cache
    pool.check()


def test_chain_key_partial_vs_full_distinct():
    toks = list(range(16))
    assert chain_key((), toks, 16) != chain_key((), toks, 8)
    assert chain_key((), toks, 16) != chain_key(((), 8, (1,)), toks, 16)
    # the key is the exact token chain — equality, not a digest, so a
    # prefix-cache hit can never be a hash collision
    assert chain_key((), toks, 16) == chain_key((), list(range(16)), 16)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000))
def test_pool_random_churn_keeps_invariants(seed):
    """Random admit/share/finish churn: refcounts balance, no page is
    ever in two states, and releasing every owner empties the pool."""
    rng = np.random.default_rng(seed)
    pool = BlockPool(int(rng.integers(3, 12)), 8)
    owners = []                          # list of (pid, hashed)
    next_h = 1
    for _ in range(200):
        op = rng.random()
        if op < 0.45:
            try:
                pid = pool.alloc(evict_cb=lambda *_: None)
            except PoolExhausted:
                continue
            if rng.random() < 0.5:
                pool.register(next_h, pid)
                next_h += 1
            owners.append(pid)
        elif op < 0.7 and owners:
            pid = owners[int(rng.integers(len(owners)))]
            pool.retain(pid)
            owners.append(pid)
        elif owners:
            pid = owners.pop(int(rng.integers(len(owners))))
            pool.release(pid)
        pool.check()
    for pid in owners:
        pool.release(pid)
    pool.check()
    assert pool.n_active == 0


def test_offloader_roundtrip_and_events():
    off = BlockOffloader()
    try:
        tree = {"k": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
                "v": np.ones((2, 3, 4), np.float32)}
        off.offload(99, tree)
        assert off.holds(99)
        assert off.offloaded_bytes == 2 * 24 * 4
        off.schedule(99)
        staged = off.get(99)
        np.testing.assert_array_equal(np.asarray(staged["k"]), tree["k"])
        np.testing.assert_array_equal(np.asarray(staged["v"]), tree["v"])
        assert not off.holds(99)                 # back on device
        assert len(off.events) == 1
        assert off.events[0].nbytes == 2 * 24 * 4
        assert off.fetched_bytes == off.events[0].nbytes
    finally:
        off.close()


def test_offloader_get_unscheduled_after_close_raises():
    off = BlockOffloader()
    off.close()
    with pytest.raises(RuntimeError):
        off.get(42)


def test_paged_cache_admit_finish_refcount_balance():
    """Manager-level churn: every admit's pages are returned on finish;
    hashed prompt pages park in the prefix cache, the rest free."""
    import dataclasses

    from repro.configs import get_config
    from repro.runtime.kvcache import PagedKVCache

    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              n_layers=2)
    kv = PagedKVCache(cfg, batch=2, ctx=64, n_pages=24, page_tokens=8)
    cache = kv.init_cache()
    rng = np.random.default_rng(0)
    L = cfg.n_layers
    hk, hd = cfg.kv_heads, cfg.head_dim
    fake = {"k": np.zeros((L, 1, 64, hk, hd), np.float32),
            "v": np.zeros((L, 1, 64, hk, hd), np.float32)}
    try:
        for round_ in range(6):
            prompts = [rng.integers(0, 100, int(rng.integers(3, 20)))
                       for _ in range(2)]
            for slot, p in enumerate(prompts):
                kv.plan_admit(cache, slot, [int(t) for t in p], 8)
                cache = kv.install(cache, slot, fake, len(p))
            cache = kv.begin_step(cache, [0, 1], 1)
            kv.advance(0), kv.advance(1)
            kv.pool.check()
            kv.release_slot(0), kv.release_slot(1)
            kv.pool.check()
            assert kv.pool.n_active == 0
    finally:
        kv.close()


def test_paged_cache_plan_admit_rejected_leaves_pool_clean():
    """An admit the pool cannot carry is rejected whole at reservation
    time — no page leaks, no garbage page left hash-addressable, and a
    fitting request still admits cleanly afterwards."""
    import dataclasses

    from repro.configs import get_config
    from repro.runtime.kvcache import PagedKVCache

    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              n_layers=2)
    kv = PagedKVCache(cfg, batch=1, ctx=64, n_pages=5, page_tokens=8,
                      offload=False)
    cache = kv.init_cache()
    try:
        with pytest.raises(PoolExhausted):
            kv.plan_admit(cache, 0, list(range(30)), 4)   # worst 6 > 4
        kv.pool.check()
        assert kv.pool.n_active == 0 and kv.pool.n_free == 4
        # the rejected prompt's pages must not be prefix-addressable
        h = chain_key((), list(range(8)), 8)
        assert kv.pool.lookup(h) is None
        # a fitting request still admits cleanly afterwards
        kv.plan_admit(cache, 0, list(range(10)), 4)
        kv.pool.check()
        assert kv.pool.n_active == 2
    finally:
        kv.close()


def test_paged_cache_abort_admit_releases_planned_pages():
    """A prefill that fails between plan_admit and install must not leak
    the slot's pages, reservation, or prefix-cache entries."""
    import dataclasses

    from repro.configs import get_config
    from repro.runtime.kvcache import PagedKVCache

    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              n_layers=2)
    kv = PagedKVCache(cfg, batch=1, ctx=64, n_pages=8, page_tokens=8,
                      offload=False)
    cache = kv.init_cache()
    try:
        prompt = list(range(14))
        kv.plan_admit(cache, 0, prompt, 8)
        assert kv.pool.n_active == 2
        kv.abort_admit(0)
        kv.pool.check()
        assert kv.pool.n_active == 0 and kv.pool.n_free == 7
        assert kv.pool.lookup(chain_key((), prompt[:8], 8)) is None
        # the slot is immediately reusable
        kv.plan_admit(cache, 0, prompt, 8)
        kv.pool.check()
        kv.abort_admit(0)
        kv.abort_admit(0)                      # idempotent no-op
        kv.pool.check()
    finally:
        kv.close()


def test_paged_engine_admit_failure_does_not_leak_pages():
    """Engine-level: an exception out of prefill_one rolls the planned
    pages back and leaves the engine serviceable."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import init_params
    from repro.runtime.kvcache import make_paged_engine

    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              n_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng, kv = make_paged_engine(params, cfg, 2, 64, n_pages=16,
                                page_tokens=8, offload=False)
    try:
        boom = {"n": 0}
        real_prefill = eng.prefill_one

        def flaky_prefill(prompt):
            if boom["n"] == 0:
                boom["n"] += 1
                raise RuntimeError("transient prefill failure")
            return real_prefill(prompt)
        eng.prefill_one = flaky_prefill

        class Req:
            uid = 0
            prompt = np.arange(12)
            max_new_tokens = 4
        with pytest.raises(RuntimeError, match="transient"):
            eng.run(kv.init_cache(), [Req()])
        kv.pool.check()
        assert kv.pool.n_active == 0           # nothing leaked
        fin, _ = eng.run(kv.init_cache(), [Req()])   # retry succeeds
        assert len(fin) == 1 and len(fin[0].tokens) == 4
    finally:
        kv.close()


def test_paged_cache_admission_reservation_prevents_growth_death():
    """Worst-case reservation at admit: once admitted, growth across
    every decode step (up to prompt + max_new) always finds a page —
    ``begin_step`` can never die mid-decode."""
    import dataclasses

    from repro.configs import get_config
    from repro.runtime.kvcache import PagedKVCache

    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              n_layers=2)
    L, hk, hd = cfg.n_layers, cfg.kv_heads, cfg.head_dim
    fake = {"k": np.zeros((L, 1, 64, hk, hd), np.float32),
            "v": np.zeros((L, 1, 64, hk, hd), np.float32)}
    kv = PagedKVCache(cfg, batch=2, ctx=64, n_pages=8, page_tokens=8,
                      offload=False)
    cache = kv.init_cache()
    try:
        kv.plan_admit(cache, 0, list(range(14)), 10)      # worst 4
        cache = kv.install(cache, 0, fake, 14)
        # second admit of the same shape must be refused (4 + 4 > 7)...
        with pytest.raises(PoolExhausted, match="oversubscribe"):
            kv.plan_admit(cache, 1, list(range(50, 64)), 10)
        # ...so slot 0 can always grow to its full budget
        for step in range(10):
            cache = kv.begin_step(cache, [0], 1)
            kv.advance(0)
        kv.pool.check()
        kv.release_slot(0)
        # and the refused request fits once the slot frees
        kv.plan_admit(cache, 1, list(range(50, 64)), 10)
        kv.pool.check()
    finally:
        kv.close()


def test_paged_cache_trim_frees_growth_pages():
    import dataclasses

    from repro.configs import get_config
    from repro.runtime.kvcache import PagedKVCache

    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              n_layers=2)
    cfgL = cfg.n_layers
    kv = PagedKVCache(cfg, batch=1, ctx=64, n_pages=16, page_tokens=8)
    cache = kv.init_cache()
    hk, hd = cfg.kv_heads, cfg.head_dim
    fake = {"k": np.zeros((cfgL, 1, 64, hk, hd), np.float32),
            "v": np.zeros((cfgL, 1, 64, hk, hd), np.float32)}
    try:
        kv.plan_admit(cache, 0, list(range(6)), 20)
        cache = kv.install(cache, 0, fake, 6)
        n0 = kv.pool.n_active
        cache = kv.begin_step(cache, [0], 12)      # crosses 2 boundaries
        assert kv.pool.n_active == n0 + 2
        kv.trim_to(0, 7)                           # accept 1 of 12
        assert kv.pool.n_active == n0
        assert kv.length(0) == 7
        kv.pool.check()
    finally:
        kv.close()


def test_paged_cache_rejects_oversized_request():
    import dataclasses

    from repro.configs import get_config
    from repro.runtime.kvcache import PagedKVCache

    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              n_layers=2)
    kv = PagedKVCache(cfg, batch=1, ctx=32, n_pages=16, page_tokens=8)
    try:
        with pytest.raises(ValueError, match="paged slot addresses"):
            kv.plan_admit(kv.init_cache(), 0, list(range(20)), 20)
    finally:
        kv.close()


def test_paged_cache_rejects_recurrent_and_int8_mla():
    import dataclasses

    from repro.configs import get_config
    from repro.runtime.kvcache import PagedKVCache

    ssm = get_config("mamba2-780m").reduced()
    with pytest.raises(ValueError, match="unsupported for family"):
        PagedKVCache(ssm, batch=1, ctx=32, n_pages=8)
    # dense int8 KV is supported: quantized k/v leaves plus per-(pos,
    # kv-head) scale leaves, read by the fused paged kernels
    q = get_config("qwen2.5-14b").reduced()
    q8 = dataclasses.replace(q, kv_dtype="int8")
    kv = PagedKVCache(q8, batch=1, ctx=32, n_pages=8)
    cache = kv.init_cache()
    assert set(cache["pages"]) == {"k", "v", "k_scale", "v_scale"}
    assert cache["pages"]["k"].dtype == jnp.int8
    assert cache["pages"]["k_scale"].dtype != jnp.int8
    # the MLA latent is already compressed — int8 on top stays rejected
    mla = get_config("minicpm3-4b").reduced()
    mla8 = dataclasses.replace(mla, kv_dtype="int8")
    with pytest.raises(NotImplementedError):
        PagedKVCache(mla8, batch=1, ctx=32, n_pages=8)


def test_begin_step_span_counts_live_and_table_pages():
    """A recorded ``kv.begin_step`` span carries the pages the active
    slots hold after growth and the table's size: here slot 0 holds 2
    pages (14 tokens + 1 of 8-token pages), slot 1 holds 1 (7 + 1), and
    the table is 3 slots of 8 pages; an idle slot adds nothing."""
    import dataclasses

    from repro.configs import get_config
    from repro.runtime.kvcache import PagedKVCache
    from repro.runtime.telemetry import Tracer

    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              n_layers=2)
    L, hk, hd = cfg.n_layers, cfg.kv_heads, cfg.head_dim
    fake = {"k": np.zeros((L, 1, 64, hk, hd), np.float32),
            "v": np.zeros((L, 1, 64, hk, hd), np.float32)}
    tr = Tracer()
    kv = PagedKVCache(cfg, batch=3, ctx=64, n_pages=16, page_tokens=8,
                      offload=False, tracer=tr)
    cache = kv.init_cache()
    try:
        for slot, n in ((0, 14), (1, 7)):
            kv.plan_admit(cache, slot, list(range(100 * slot,
                                                  100 * slot + n)), 4)
            cache = kv.install(cache, slot, fake, n)
        cache = kv.begin_step(cache, [0, 1], 1)
        cache = kv.begin_step(cache, [1], 1)
    finally:
        kv.close()
    spans = [dict(e.args) for e in tr.events() if e.name == "kv.begin_step"]
    assert [(s["live_pages"], s["table_pages"]) for s in spans] \
        == [(3, 24), (1, 24)]
