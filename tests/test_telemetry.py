"""Unit tests for the unified runtime tracer (``runtime.telemetry``):
ring-buffer bounds, concurrent emission, the disabled no-op path,
Chrome-trace schema validity, exclusive-time stall attribution, clock
unification across subsystems, and the uniform stats surfaces."""
import json
import threading
import time

import numpy as np
import pytest

from repro.runtime.telemetry import (
    COMPONENTS, NULL_TRACER, CounterEvent, InstantEvent, SpanEvent,
    StallRecord, Tracer, clock, format_summary, stall_summary,
    validate_chrome_trace)


# --------------------------------------------------------------------------- #
#  ring buffer
# --------------------------------------------------------------------------- #

def test_ring_buffer_wraparound():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.instant(f"e{i}")
    evs = tr.events()
    assert len(evs) == 8
    assert tr.evicted == 12
    # the ring keeps the NEWEST events
    assert [e.name for e in evs] == [f"e{i}" for i in range(12, 20)]


def test_stall_ring_bounded():
    tr = Tracer(capacity=4)
    for i in range(10):
        with tr.token_step(i):
            pass
    assert len(tr.stalls()) == 4
    assert tr.stalls_evicted == 6
    assert [r.index for r in tr.stalls()] == [6, 7, 8, 9]


def test_deterministic_sampling():
    tr = Tracer(capacity=1000, sample=0.5)
    for i in range(100):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 50        # exactly 1-in-2, no RNG
    tr2 = Tracer(capacity=1000, sample=0.5)
    for i in range(100):
        tr2.instant(f"e{i}")
    assert [e.name for e in tr.events()] == [e.name for e in tr2.events()]


def test_capacity_validation():
    with pytest.raises(ValueError):
        Tracer(capacity=0)
    with pytest.raises(ValueError):
        Tracer(sample=0.0)
    with pytest.raises(ValueError):
        Tracer(sample=1.5)


# --------------------------------------------------------------------------- #
#  concurrency
# --------------------------------------------------------------------------- #

def test_concurrent_emit_from_many_threads():
    tr = Tracer(capacity=10_000)
    n_threads, per = 4, 100
    barrier = threading.Barrier(n_threads)

    def emit(k):
        barrier.wait()
        for i in range(per):
            with tr.span(f"w{k}/s{i}", track=f"worker-{k}"):
                pass
            tr.counter(f"w{k}/c", i, track=f"worker-{k}")

    threads = [threading.Thread(target=emit, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()
    assert len(evs) == n_threads * per * 2
    assert tr.evicted == 0
    # every thread's track is present and complete
    for k in range(n_threads):
        spans = [e for e in evs if isinstance(e, SpanEvent)
                 and e.track == f"worker-{k}"]
        assert len(spans) == per


def test_concurrent_token_steps_are_thread_local():
    """Two threads with open token steps attribute phases to their OWN
    step, not each other's."""
    tr = Tracer()
    out = {}

    def run(name, comp):
        with tr.token_step(0, track=name):
            with tr.phase(comp):
                time.sleep(0.01)
        out[name] = [r for r in tr.stalls()]

    t1 = threading.Thread(target=run, args=("a", "disk_wait"))
    t2 = threading.Thread(target=run, args=("b", "compute"))
    t1.start(); t2.start(); t1.join(); t2.join()
    recs = tr.stalls()
    assert len(recs) == 2
    by_track = {}
    for ev in tr.events():
        if isinstance(ev, SpanEvent) and ev.cat == "decode":
            by_track[ev.track] = ev
    assert set(by_track) == {"a", "b"}
    # each record has only its own component nonzero
    comps = sorted((r.disk_wait_s > 0, r.compute_s > 0) for r in recs)
    assert comps == [(False, True), (True, False)]


# --------------------------------------------------------------------------- #
#  disabled path
# --------------------------------------------------------------------------- #

def test_disabled_tracer_is_a_noop():
    tr = Tracer(enabled=False)
    tr.instant("x")
    tr.counter("c", 1.0)
    tr.span_event("s", 0.0, 1.0)
    with tr.span("s2"):
        pass
    with tr.token_step(0) as step:
        assert step is None
        with tr.phase("compute"):
            pass
    assert tr.events() == []
    assert tr.stalls() == []
    assert tr.current_step() is None


def test_null_tracer_shared_and_disabled():
    assert NULL_TRACER.enabled is False
    with NULL_TRACER.token_step(0):
        with NULL_TRACER.phase("compute"):
            pass
    assert NULL_TRACER.events() == []


# --------------------------------------------------------------------------- #
#  stall attribution
# --------------------------------------------------------------------------- #

def test_components_partition_wall_time():
    tr = Tracer()
    with tr.token_step(0):
        with tr.phase("compute"):
            time.sleep(0.02)
        with tr.phase("disk_wait"):
            time.sleep(0.01)
    (rec,) = tr.stalls()
    assert rec.compute_s >= 0.015
    assert rec.disk_wait_s >= 0.005
    # components sum to wall by construction (sched_idle absorbs the rest)
    assert rec.accounted_s == pytest.approx(rec.wall_s, rel=1e-6)
    assert rec.sched_idle_s >= 0.0


def test_nested_phase_is_exclusive():
    """disk_wait inside compute charges disk_wait, not both."""
    tr = Tracer()
    with tr.token_step(0):
        with tr.phase("compute"):
            time.sleep(0.01)
            with tr.phase("disk_wait"):
                time.sleep(0.02)
            time.sleep(0.01)
    (rec,) = tr.stalls()
    assert rec.disk_wait_s >= 0.015
    assert rec.compute_s >= 0.015
    # exclusive: compute does NOT include the nested disk wait
    assert rec.compute_s < rec.wall_s - rec.disk_wait_s + 1e-6
    assert rec.accounted_s == pytest.approx(rec.wall_s, rel=1e-6)


def test_noncanonical_phase_folds_into_other():
    tr = Tracer()
    with tr.token_step(0):
        with tr.phase("weird_custom_phase"):
            time.sleep(0.005)
    (rec,) = tr.stalls()
    assert rec.other_s >= 0.004
    assert rec.accounted_s == pytest.approx(rec.wall_s, rel=1e-6)


def test_phase_outside_step_still_emits_span():
    tr = Tracer()
    with tr.phase("compute", track="solo"):
        pass
    assert tr.stalls() == []
    (ev,) = tr.events()
    assert isinstance(ev, SpanEvent) and ev.track == "solo"


def test_abandoned_phase_closed_on_error():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.token_step(0):
            with tr.phase("compute"):
                raise RuntimeError("boom")
    (rec,) = tr.stalls()
    assert rec.accounted_s == pytest.approx(rec.wall_s, rel=1e-6)


def test_summary_and_format():
    tr = Tracer()
    for i in range(3):
        with tr.token_step(i):
            with tr.phase("compute"):
                time.sleep(0.002)
    summ = tr.summary()
    assert summ["n"] == 3.0
    assert summ["compute"] > 0.0
    assert set(COMPONENTS) <= set(summ)
    line = format_summary(summ)
    assert "tpot" in line and "compute" in line
    assert tr.summary(last_n=1)["n"] == 1.0
    empty = stall_summary([])
    assert empty["n"] == 0.0 and empty["wall"] == 0.0


def test_min_dur_suppresses_span_not_attribution():
    tr = Tracer()
    with tr.token_step(0):
        with tr.phase("disk_wait", min_dur=10.0):
            time.sleep(0.002)
    (rec,) = tr.stalls()
    assert rec.disk_wait_s > 0.0            # attribution always lands
    spans = [e for e in tr.events() if isinstance(e, SpanEvent)
             and e.name == "disk_wait"]
    assert spans == []                      # span suppressed under min_dur


# --------------------------------------------------------------------------- #
#  Chrome trace export + validator
# --------------------------------------------------------------------------- #

def test_chrome_trace_schema(tmp_path):
    tr = Tracer()
    with tr.token_step(0, track="decode"):
        with tr.phase("compute"):
            pass
    tr.span_event("layer_read[0]", clock(), clock() + 1e-3,
                  cat="prefetch", track="prefetcher", nbytes=123)
    tr.counter("resident", 2, track="prefetcher")
    tr.instant("fault:error:layer_read", cat="fault", track="faults")
    path = str(tmp_path / "trace.json")
    tr.export_chrome_trace(path)

    with open(path) as f:
        doc = json.load(f)
    assert "traceEvents" in doc and doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    phs = {e["ph"] for e in evs}
    assert {"M", "X", "C", "i"} <= phs
    for e in evs:
        assert e["pid"] == 1
        if e["ph"] != "M":
            assert e["ts"] >= 0.0            # normalized to the run start
        if e["ph"] == "X":
            assert e["dur"] >= 0.0

    info = validate_chrome_trace(path, require_tracks=("prefetcher",
                                                       "decode"))
    assert "prefetcher" in info["tracks"]
    assert info["phases"]["X"] >= 2


def test_validator_rejects_bad_traces(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"nope": []}))
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace(str(p))
    p.write_text(json.dumps({"traceEvents": []}))
    with pytest.raises(ValueError, match="empty"):
        validate_chrome_trace(str(p))
    p.write_text(json.dumps(
        {"traceEvents": [{"ph": "Z", "name": "x"}]}))
    with pytest.raises(ValueError, match="unknown ph"):
        validate_chrome_trace(str(p))

    tr = Tracer()
    tr.instant("x", track="decode")
    good = str(tmp_path / "good.json")
    tr.export_chrome_trace(good)
    with pytest.raises(ValueError, match="required tracks missing"):
        validate_chrome_trace(good, require_tracks=("prefetcher",))


# --------------------------------------------------------------------------- #
#  clock unification (satellite: one timeline across subsystems)
# --------------------------------------------------------------------------- #

def test_fired_faults_on_telemetry_clock():
    from repro.runtime.faults import FaultInjector, FaultSpec

    inj = FaultInjector([FaultSpec(op="layer_read", times=1)])
    t0 = clock()
    with pytest.raises(OSError):
        inj.check("layer_read", key=0)
    t1 = clock()
    (f,) = inj.fired
    assert t0 <= f.t <= t1


def test_worker_health_on_telemetry_clock():
    from repro.runtime.iopolicy import WorkerHealth

    t0 = clock()
    h = WorkerHealth(name="w")
    h.progress()
    t1 = clock()
    assert t0 <= h.last_progress_t <= t1
    assert 0.0 <= h.seconds_since_progress() <= clock() - t0 + 1e-6


def test_fault_injector_emits_live_instants():
    from repro.runtime.faults import FaultInjector, FaultSpec

    tr = Tracer()
    inj = FaultInjector([FaultSpec(op="layer_read", times=1)],
                        tracer=tr)
    with pytest.raises(OSError):
        inj.check("layer_read", key=2)
    (ev,) = tr.events()
    assert isinstance(ev, InstantEvent)
    assert ev.track == "faults" and "layer_read" in ev.name


# --------------------------------------------------------------------------- #
#  ingestion adapters
# --------------------------------------------------------------------------- #

def test_ingest_failover_event_splits():
    from repro.runtime.failover import FailoverEvent

    tr = Tracer()
    ev = FailoverEvent(
        token_index=5, failed_stage=1, generation=1, n_stages_before=4,
        n_stages_after=3, plan={}, halda=None, detect_s=0.1,
        resolve_s=0.2, rebuild_s=0.3, replay_s=0.4, tokens_lost=0,
        replayed_tokens=7)
    t_end = 100.0
    tr.ingest_failover_event(ev, t_end=t_end)
    spans = [e for e in tr.events() if isinstance(e, SpanEvent)]
    assert [s.name for s in spans] == [
        "failover/detect", "failover/resolve", "failover/rebuild",
        "failover/replay"]
    # contiguous, ending at t_end, durations matching the splits
    assert spans[-1].t_end == pytest.approx(t_end)
    assert spans[0].t_start == pytest.approx(t_end - ev.recovery_s)
    for s, d in zip(spans, (0.1, 0.2, 0.3, 0.4)):
        assert s.duration == pytest.approx(d)
    for a, b in zip(spans[:-1], spans[1:]):
        assert a.t_end == pytest.approx(b.t_start)


# --------------------------------------------------------------------------- #
#  uniform stats surfaces (satellite: stall counters through stats())
# --------------------------------------------------------------------------- #

def test_block_offloader_uniform_stats():
    from repro.runtime.iopolicy import FAST_TEST_POLICY
    from repro.runtime.kvcache import BlockOffloader
    from repro.runtime.streaming import PrefetchStats

    tr = Tracer()
    off = BlockOffloader(policy=FAST_TEST_POLICY, tracer=tr)
    try:
        page = {"k": np.ones((2, 4), np.float32)}
        off.offload(7, page)
        off.schedule(7)
        off.get(7, timeout=10.0)
        st = off.stats()
        assert isinstance(st, PrefetchStats)
        assert st.layers_served == 1
        assert st.total_bytes_read == 32
        assert st.stall_s >= 0.0
        assert st.retries == 0
    finally:
        off.close()
    tracks = tr.tracks()
    assert "kv-offloader" in tracks
    names = [e.name for e in tr.events() if isinstance(e, SpanEvent)]
    assert any(n.startswith("kv_d2h") for n in names)
    assert any(n.startswith("kv_h2d") for n in names)


def test_kv_stats_carries_fetch_stall_fields():
    from repro.runtime.kvcache import KVStats

    st = KVStats(n_pages=4, page_tokens=8, page_bytes=64,
                 active_pages_highwater=2, active_tokens_highwater=16,
                 prefix_hits=0, cow_copies=0, evictions=0,
                 offloaded_bytes=0, fetched_bytes=0, fetch_events=[])
    assert st.fetch_stall_s == 0.0 and st.fetch_retries == 0


def test_stall_record_component_accessor():
    r = StallRecord(index=0, t_start=0.0, t_end=1.0, compute_s=0.5,
                    disk_wait_s=0.25, sched_idle_s=0.25)
    assert r.component("compute") == 0.5
    assert r.wall_s == 1.0
    assert r.accounted_s == pytest.approx(1.0)


# --------------------------------------------------------------------------- #
#  drift report (core.latency.telemetry_crosscheck)
# --------------------------------------------------------------------------- #

def _local_dev(bps=1e9):
    from repro.core.profiles import GiB, OS, QUANTS, DeviceProfile

    return DeviceProfile(name="t", os=OS.LINUX, ram_avail=8 * GiB,
                         cpu_flops={q: 50e9 for q in QUANTS},
                         disk_seq_bps=bps, disk_rand_bps=bps)


def test_telemetry_crosscheck_disk_term():
    from repro.core.latency import telemetry_crosscheck
    from repro.runtime.streaming import PrefetchEvent

    layer_bytes, n_layers = 1 << 20, 4
    dev = _local_dev(1e9)
    # per-pass modeled: 4 MiB / 1 GB/s ≈ 4.19 ms; make measured match
    per_layer = layer_bytes / 1e9
    evs = [PrefetchEvent(i, i * 1.0, i * 1.0 + per_layer, layer_bytes)
           for i in range(n_layers)]
    stalls = [StallRecord(index=0, t_start=0.0, t_end=0.01)]
    rep = telemetry_crosscheck(dev, layer_bytes, n_layers,
                               stalls=stalls, prefetch_events=evs)
    disk = rep.term("disk")
    assert disk is not None
    assert disk.ratio == pytest.approx(1.0, rel=1e-6)
    assert disk.consistent and rep.consistent
    assert rep.drifted == ()
    assert "disk" in rep.as_dict()
    assert "DRIFT" not in rep.report()


def test_telemetry_crosscheck_detects_drift():
    from repro.core.latency import telemetry_crosscheck
    from repro.runtime.streaming import PrefetchEvent

    layer_bytes, n_layers = 1 << 20, 4
    # model says 1 GB/s but the "disk" delivered 100x slower reads
    dev = _local_dev(1e9)
    per_layer = layer_bytes / 1e9 * 100
    evs = [PrefetchEvent(i, 0.0, per_layer, layer_bytes)
           for i in range(n_layers)]
    stalls = [StallRecord(index=0, t_start=0.0, t_end=1.0)]
    rep = telemetry_crosscheck(dev, layer_bytes, n_layers,
                               stalls=stalls, prefetch_events=evs)
    assert rep.drifted == ("disk",)
    assert not rep.consistent
    assert "DRIFT" in rep.report()


def test_telemetry_crosscheck_comms_term():
    from repro.core.latency import telemetry_crosscheck

    dev = _local_dev()
    stalls = [StallRecord(index=0, t_start=0.0, t_end=0.01,
                          comms_s=2 * dev.t_comm)]
    rep = telemetry_crosscheck(dev, 1024, 4, stalls=stalls, n_hops=2)
    comms = rep.term("comms")
    assert comms is not None
    assert comms.ratio == pytest.approx(1.0, rel=1e-6)
    assert rep.term("disk") is None      # no prefetch timeline given


# --------------------------------------------------------------------------- #
#  streaming sources: stall surfaced through stats()
# --------------------------------------------------------------------------- #

def test_prefetcher_stats_surface_stall_uniformly(tmp_path):
    """RingBankPrefetcher.stats() reports measured stall_s (was a
    hardcoded 0.0) and LayerPrefetcher attributes waits to disk_wait."""
    import dataclasses as dc

    import jax

    from repro.configs import get_config
    from repro.models import init_params
    from repro.runtime.paramstore import ParamStore, save_param_store
    from repro.runtime.streaming import StreamingParamSource

    cfg = dc.replace(get_config("qwen2.5-14b").reduced(), n_layers=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    sdir = str(tmp_path / "store")
    save_param_store(params, cfg, sdir)

    tr = Tracer()
    with StreamingParamSource(ParamStore(sdir), window=2,
                              tracer=tr) as src:
        with tr.token_step(0):
            for i in range(cfg.n_layers):
                src.layer(i)
        st = src.stats()
    assert st.stall_s >= 0.0
    (rec,) = tr.stalls()
    # waiting on layer 0 before the worker staged it counts as disk_wait
    assert rec.disk_wait_s >= 0.0
    assert any(e.track == "prefetcher" for e in tr.events()
               if isinstance(e, SpanEvent))
