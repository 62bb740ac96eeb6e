"""Serving metrics: histogram correctness, registry exposure, request
lifecycle through the engine, classified sheds, and trace-eviction
surfacing."""
import dataclasses
import json
import logging
import math
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import init_cache, init_params
from repro.runtime.engine import make_dense_engine
from repro.runtime.kvcache import make_paged_engine
from repro.runtime.metrics import (LogHistogram, MetricsRegistry,
                                   RequestTracker,
                                   validate_metrics_snapshot)
from repro.runtime.telemetry import Tracer, validate_chrome_trace

KEY = jax.random.PRNGKey(0)


def _cfg(arch="qwen2.5-14b", n_layers=2, **over):
    return dataclasses.replace(get_config(arch).reduced(),
                               n_layers=n_layers, **over)


class _Req:
    def __init__(self, uid, prompt, max_new, session=None):
        self.uid = uid
        self.prompt = prompt
        self.max_new_tokens = max_new
        self.session = session


# --------------------------------------------------------------------------- #
#  LogHistogram: quantile accuracy, merging, concurrency
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("dist", ["uniform", "lognormal", "pointmass"])
def test_histogram_quantiles_within_bucket_error(dist):
    """p50/p90/p99 of the log-bucketed histogram agree with exact numpy
    quantiles (same inverted-CDF definition) within one bucket of
    relative error — the histogram's accuracy contract."""
    rng = np.random.default_rng(hash(dist) % 2**32)
    n = 5000
    if dist == "uniform":
        xs = rng.uniform(0.001, 10.0, n)
    elif dist == "lognormal":
        xs = rng.lognormal(0.0, 2.0, n)
    else:
        xs = np.full(n, 3.7)
    h = LogHistogram()
    for x in xs:
        h.observe(x)
    assert h.count == n
    assert h.min == pytest.approx(xs.min())
    assert h.max == pytest.approx(xs.max())
    assert h.total == pytest.approx(xs.sum(), rel=1e-9)
    for q in (0.5, 0.9, 0.99):
        est = h.quantile(q)
        exact = float(np.quantile(xs, q, method="inverted_cdf"))
        ratio = est / exact
        assert 1.0 / h.growth <= ratio <= h.growth, \
            f"{dist} p{q}: {est} vs exact {exact} (x{ratio:.4f})"


def test_histogram_extremes_exact_and_empty_nan():
    h = LogHistogram()
    assert math.isnan(h.quantile(0.5))
    for v in (2.0, 8.0, 32.0):
        h.observe(v)
    assert h.quantile(0.0) == 2.0          # clamped to exact min
    assert h.quantile(1.0) == 32.0         # clamped to exact max


def test_histogram_zero_and_negative_share_zero_bucket():
    h = LogHistogram()
    for v in (0.0, -1.5, 4.0):
        h.observe(v)
    assert h.zero_count == 2
    assert h.count == 3
    assert h.quantile(0.5) == 0.0          # zero-bucket, inside [min,max]
    assert h.min == -1.5 and h.max == 4.0


def test_histogram_merge_associative():
    rng = np.random.default_rng(3)
    parts = []
    for _ in range(3):
        h = LogHistogram()
        for x in rng.lognormal(0.0, 1.0, 400):
            h.observe(x)
        parts.append(h)

    def merged(order):
        acc = LogHistogram()
        for i in order:
            acc.merge(parts[i])
        return acc

    a = merged([0, 1, 2])
    b = merged([2, 0, 1])
    sa, sb = a.state(), b.state()
    # bucket/count merging is exactly associative; only the float sum
    # accumulates rounding
    assert sa.pop("sum") == pytest.approx(sb.pop("sum"), rel=1e-12)
    assert sa == sb
    assert a.count == sum(p.count for p in parts)
    for q in (0.5, 0.9, 0.99):
        assert a.quantile(q) == b.quantile(q)
    with pytest.raises(ValueError, match="growth"):
        a.merge(LogHistogram(growth=2.0))


def test_histogram_concurrent_observe():
    h = LogHistogram()
    per_thread, n_threads = 5000, 4
    xs = np.random.default_rng(9).lognormal(0.0, 1.0, per_thread)

    def work():
        for x in xs:
            h.observe(x)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == per_thread * n_threads
    assert h.zero_count + sum(h.buckets.values()) == h.count
    assert h.total == pytest.approx(xs.sum() * n_threads, rel=1e-6)


# --------------------------------------------------------------------------- #
#  Registry: counters/gauges/labels, snapshot, prometheus, validation
# --------------------------------------------------------------------------- #

def test_registry_counters_labels_and_monotonicity():
    reg = MetricsRegistry()
    reg.inc("requests/rejected", reason="shed_capacity")
    reg.inc("requests/rejected", 2, reason="deferred_ttl_expired")
    reg.inc("requests/finished", 3)
    snap = reg.snapshot()
    assert snap["counters"]["requests/rejected{reason=shed_capacity}"] == 1
    assert snap["counters"][
        "requests/rejected{reason=deferred_ttl_expired}"] == 2
    assert snap["counters"]["requests/finished"] == 3
    with pytest.raises(ValueError, match="negative"):
        reg.counter("requests/finished").inc(-1)


def test_registry_gauge_sources_sampled():
    reg = MetricsRegistry()
    state = {"v": 1.0}
    reg.add_source("test", lambda: {"pool/occupancy": state["v"]})
    reg.sample()
    assert reg.gauge("pool/occupancy").value == 1.0
    state["v"] = 0.25
    snap = reg.snapshot()                  # snapshot() re-samples
    assert snap["gauges"]["pool/occupancy"] == 0.25


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.inc("requests/finished", 2)
    reg.set_gauge("slots/active", 3)
    for v in (0.1, 0.2, 0.4):
        reg.observe("request/ttft_s", v)
    text = reg.prometheus_text()
    assert "# TYPE repro_requests_finished_total counter" in text
    assert "repro_requests_finished_total 2" in text
    assert "# TYPE repro_slots_active gauge" in text
    assert "# TYPE repro_request_ttft_s summary" in text
    assert 'repro_request_ttft_s{quantile="0.5"}' in text
    assert "repro_request_ttft_s_count 3" in text
    assert "repro_request_ttft_s_sum" in text


def test_validate_metrics_snapshot_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.inc("requests/finished", 4)
    for v in np.random.default_rng(0).uniform(0.01, 2.0, 100):
        reg.observe("request/ttft_s", v)
    path = reg.export_json(str(tmp_path / "m.json"))
    info = validate_metrics_snapshot(path, require=["request/ttft_s"])
    assert info["histograms"] == 1
    assert info["quantiles"]["request/ttft_s"]["p50"] > 0

    with pytest.raises(ValueError, match="required metric"):
        validate_metrics_snapshot(path, require=["no/such/metric"])

    doc = json.loads(open(path).read())
    doc["counters"]["requests/finished"] = -1
    with pytest.raises(ValueError, match="non-monotonic"):
        validate_metrics_snapshot(doc)

    doc = json.loads(open(path).read())
    doc["histograms"]["request/ttft_s"]["count"] += 5
    with pytest.raises(ValueError, match="bucket sum"):
        validate_metrics_snapshot(doc)

    with pytest.raises(ValueError, match="schema"):
        validate_metrics_snapshot({"schema": "bogus"})


def test_request_log_bounded_with_eviction_counter():
    from repro.runtime.metrics import RequestTrace

    reg = MetricsRegistry(request_log_size=4)
    for i in range(7):
        reg.record_request(RequestTrace(uid=i, submit_t=float(i)))
    assert len(reg.request_log) == 4
    assert reg.request_log_evicted == 3
    assert reg.snapshot()["request_log"] == {"logged": 4, "evicted": 3}


def test_tracker_chunked_prefill_metrics():
    """Chunked-admission instrumentation: per-request chunk counts land
    in the ``request/prefill_chunks`` histogram, interleave stalls
    accumulate as a counter, and the worst inter-token gap is recorded
    per finished request (the stat the chunked-admit TPOT gate reads)."""
    import time

    reg = MetricsRegistry()
    tr = RequestTracker(reg)
    tr.submit(1)
    tr.admitted(1)
    tr.prefill_chunks(1, 4)
    tr.interleave_stall(0.25)
    tr.interleave_stall(0.5)
    tr.token(1)
    time.sleep(0.02)
    tr.token(1)
    tr.token(1)
    tr.finished(1)
    snap = reg.snapshot()
    assert snap["counters"]["decode/interleave_stall_s"] == \
        pytest.approx(0.75)
    assert reg.histogram("request/prefill_chunks").quantile(0.5) >= 4
    trace = list(reg.request_log)[-1]
    assert trace.max_gap_s >= 0.02           # the slept gap was captured
    assert reg.histogram("request/max_gap_s").count == 1


def test_tracker_reject_classification_counts():
    reg = MetricsRegistry()
    tr = RequestTracker(reg)
    tr.submit(1)
    tr.rejected(1, "shed_capacity", "pool too small")
    tr.submit(2)
    tr.rejected(2, "deferred_ttl_expired", "starved")
    snap = reg.snapshot()
    assert snap["counters"]["requests/rejected{reason=shed_capacity}"] == 1
    assert snap["counters"][
        "requests/rejected{reason=deferred_ttl_expired}"] == 1
    outcomes = [t.outcome for t in reg.request_log]
    assert outcomes == ["shed", "shed"]


# --------------------------------------------------------------------------- #
#  Engine lifecycle: dense + paged, arrivals, sheds, restores
# --------------------------------------------------------------------------- #

def test_dense_engine_records_request_lifecycle():
    cfg = _cfg()
    params = init_params(cfg, KEY)
    reg = MetricsRegistry()
    eng = make_dense_engine(params, cfg, 2, 64, metrics=reg)
    rng = np.random.default_rng(1)
    reqs = [_Req(i, rng.integers(0, cfg.vocab, 6), 4) for i in range(3)]
    fin, steps = eng.run(init_cache(cfg, 2, 64, dtype=jnp.float32), reqs)
    assert len(fin) == 3
    snap = reg.snapshot()
    c = snap["counters"]
    assert c["requests/submitted"] == 3
    assert c["requests/admitted"] == 3
    assert c["requests/finished"] == 3
    assert c["tokens/generated"] == sum(len(f.tokens) for f in fin) == 12
    h = snap["histograms"]
    assert h["request/ttft_s"]["count"] == 3
    assert h["request/queue_wait_s"]["count"] == 3
    assert h["request/tpot_s"]["count"] == 3
    assert h["decode/step_s"]["count"] == steps
    assert snap["gauges"]["slots/active"] == 0.0
    traces = list(reg.request_log)
    assert sorted(t.uid for t in traces) == [0, 1, 2]
    assert all(t.outcome == "finished" for t in traces)
    assert all(t.ttft_s > 0 and t.e2e_s >= t.ttft_s for t in traces)
    assert all(t.n_tokens == 4 for t in traces)
    validate_metrics_snapshot(snap, require=["request/ttft_s",
                                             "requests/finished"])


def test_paged_engine_classified_shed_counters():
    """The two PoolExhausted shed paths land as distinctly-labeled
    counters and classified codes on RejectedRequest."""
    cfg = _cfg()
    params = init_params(cfg, KEY)
    rng = np.random.default_rng(5)

    reg = MetricsRegistry()
    eng, kv = make_paged_engine(params, cfg, 2, 64, n_pages=6,
                                page_tokens=8, offload=False, metrics=reg)
    try:
        fin, _ = eng.run(kv.init_cache(),
                         [_Req(0, rng.integers(0, cfg.vocab, 8), 8),
                          _Req(1, rng.integers(0, cfg.vocab, 30), 4)])
    finally:
        kv.close()
    assert [f.uid for f in fin] == [0]
    assert eng.rejected[0].code == "shed_capacity"
    assert "pool too small for request 1" in eng.rejected[0].reason
    c = reg.snapshot()["counters"]
    assert c["requests/rejected{reason=shed_capacity}"] == 1
    assert c["requests/finished"] == 1

    reg2 = MetricsRegistry()
    eng2, kv2 = make_paged_engine(params, cfg, 2, 64, n_pages=6,
                                  page_tokens=8, offload=False,
                                  metrics=reg2)
    try:
        fin2, _ = eng2.run(kv2.init_cache(),
                           [_Req(0, rng.integers(0, cfg.vocab, 8), 12),
                            _Req(1, rng.integers(0, cfg.vocab, 8), 8)],
                           admit_patience=5)
    finally:
        kv2.close()
    assert [f.uid for f in fin2] == [0]
    assert eng2.rejected[0].code == "deferred_ttl_expired"
    c2 = reg2.snapshot()["counters"]
    assert c2["requests/rejected{reason=deferred_ttl_expired}"] == 1
    shed_traces = [t for t in reg2.request_log if t.outcome == "shed"]
    assert [t.uid for t in shed_traces] == [1]


def test_engine_respect_arrivals_replays_queue_wait():
    cfg = _cfg()
    params = init_params(cfg, KEY)
    reg = MetricsRegistry()
    eng = make_dense_engine(params, cfg, 2, 64, metrics=reg)
    rng = np.random.default_rng(2)

    class _ArrReq(_Req):
        def __init__(self, uid, prompt, max_new, arrival_s):
            super().__init__(uid, prompt, max_new)
            self.arrival_s = arrival_s

    reqs = [_ArrReq(0, rng.integers(0, cfg.vocab, 6), 3, 0.0),
            _ArrReq(1, rng.integers(0, cfg.vocab, 6), 3, 0.05)]
    fin, _ = eng.run(init_cache(cfg, 2, 64, dtype=jnp.float32), reqs,
                     respect_arrivals=True)
    assert sorted(f.uid for f in fin) == [0, 1]
    traces = {t.uid: t for t in reg.request_log}
    # request 1's submit is pinned to its arrival instant, 50 ms after
    # request 0's
    assert traces[1].submit_t - traces[0].submit_t \
        >= 0.05 - 1e-3
    assert all(t.queue_wait_s >= 0 for t in traces.values())


def test_queue_wait_excludes_the_prefill():
    """``admit_t`` is the start of the admit: a request admitted at once
    waits ~0 however long its prefill takes, and the prefill lands in
    ``request/prefill_s`` and TTFT instead."""
    import time

    from repro.runtime.engine import ContinuousBatcher

    prefill_s, vocab = 0.05, 8

    def prefill_one(prompt):
        time.sleep(prefill_s)
        return 1, None

    def decode(cache, tokens):
        return jnp.zeros((tokens.shape[0], 1, vocab)), cache

    reg = MetricsRegistry()
    eng = ContinuousBatcher(1, prefill_one, lambda c, *_: c, decode,
                            metrics=reg)
    eng.run(None, [_Req(0, np.arange(4), 2)])
    (trace,) = reg.request_log
    assert trace.queue_wait_s < prefill_s / 2
    assert trace.ttft_s >= prefill_s
    assert reg.histogram("request/prefill_s").quantile(0.5) >= prefill_s


def test_paged_engine_gauges_and_session_restore_counter(tmp_path):
    cfg = _cfg()
    params = init_params(cfg, KEY)
    reg = MetricsRegistry()
    eng, kv = make_paged_engine(params, cfg, 2, 64, n_pages=18,
                                page_tokens=8, offload=False, metrics=reg,
                                disk_dir=str(tmp_path), park_idle_s=1e9)
    try:
        cache = kv.init_cache()
        prompt = np.arange(8) % cfg.vocab
        eng.run(cache, [_Req(10, prompt, 3, session="s1")])
        assert kv.is_parked("s1")
        eng.run(cache, [_Req(11, prompt, 3, session="s1")])
    finally:
        kv.close()
    snap = reg.snapshot()
    c = snap["counters"]
    assert c["requests/finished"] == 2
    assert c["requests/restored"] == 1
    restored = [t for t in reg.request_log if t.restored]
    assert [t.uid for t in restored] == [11]
    assert restored[0].ttft_s > 0      # first token of turn 2 still timed
    g = snap["gauges"]
    for key in ("kv/pages_free", "kv/prefix_hit_rate", "slots/free",
                "mem/device/used_bytes", "mem/host/peak_bytes"):
        assert key in g, f"missing gauge {key}"
    assert snap["histograms"]["request/prefill_s"]["count"] == 2


# --------------------------------------------------------------------------- #
#  Tracer ring-eviction surfacing (satellite: truncated-trace warning)
# --------------------------------------------------------------------------- #

def test_chrome_trace_carries_eviction_metadata(tmp_path, caplog):
    tr = Tracer(capacity=4)
    for i in range(12):
        with tr.span(f"s{i}", track="decode"):
            pass
    assert tr.evicted > 0
    doc = tr.chrome_trace()
    assert doc["metadata"]["evicted"] == tr.evicted
    assert doc["metadata"]["complete"] is False
    path = str(tmp_path / "t.json")
    with caplog.at_level(logging.WARNING, "repro.runtime.telemetry"):
        tr.export_chrome_trace(path)
    assert any("truncated" in r.message for r in caplog.records)
    info = validate_chrome_trace(path)
    assert info["evicted"] == tr.evicted


def test_chrome_trace_complete_when_nothing_evicted(tmp_path, caplog):
    tr = Tracer(capacity=64)
    with tr.span("only", track="decode"):
        pass
    path = str(tmp_path / "t.json")
    with caplog.at_level(logging.WARNING, "repro.runtime.telemetry"):
        tr.export_chrome_trace(path)
    assert not caplog.records
    info = validate_chrome_trace(path)
    assert info["evicted"] == 0
