"""The serving loop's spans on the profiler's clock: with no profiler
session a span site makes no ``TraceAnnotation``; under a session every
static span name lands on the host plane of the recorded trace, counted
as the engine counted its work; and a warm second engine build lowers and
compiles nothing, profiler on or off."""
import dataclasses
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import init_params
from repro.runtime import telemetry
from repro.runtime.kvcache import make_paged_engine
from repro.runtime.telemetry import Tracer

pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats has no __module__")

KEY = jax.random.PRNGKey(0)
SPANS = ("batcher.step", "kv.begin_step", "kv.sync_tables",
         "batcher.readback", "batcher.admit", "kv.plan_admit",
         "batcher.chunk", "batcher.wait_arrival")


@dataclasses.dataclass
class _Req:
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival_s: float = 0.0


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                              n_layers=2)
    return cfg, init_params(cfg, KEY)


def _requests(cfg, arrivals=(0.02, 0.02, 0.02, 0.05)):
    """Two share a prompt (a prefix hit), prompts span several 8-token
    chunks, and the run starts idle, waiting for the first arrival."""
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab, 19)
    prompts = [shared, shared.copy(), rng.integers(0, cfg.vocab, 13),
               rng.integers(0, cfg.vocab, 9)]
    return [_Req(i, p, 4, a) for i, (p, a) in enumerate(zip(prompts,
                                                             arrivals))]


def _serve(cfg, params, tracer=None):
    eng, kv = make_paged_engine(params, cfg, 2, 64, n_pages=32,
                                page_tokens=8, prefill_chunk=8,
                                offload=False, tracer=tracer)
    try:
        fin, _ = eng.run(kv.init_cache(), _requests(cfg),
                         respect_arrivals=True)
    finally:
        kv.close()
    assert len(fin) == 4
    return eng, kv


def _host_events(trace_dir):
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out.extend((ev.name, {k: v for k, v in ev.stats})
                           for ev in line.events)
    return out


class _Counting(telemetry.TraceAnnotation):
    made = 0

    def __init__(self, name, **kwargs):
        type(self).made += 1
        super().__init__(name, **kwargs)


def test_no_profiler_session_makes_no_annotation(model, monkeypatch):
    cfg, params = model
    monkeypatch.setattr(telemetry, "TraceAnnotation", _Counting)
    _Counting.made = 0
    eng, _ = _serve(cfg, params)
    assert eng._step_idx > 0
    assert _Counting.made == 0


@pytest.mark.parametrize("tracer", [None, "ring"])
def test_profiler_trace_holds_each_static_span(model, tmp_path, tracer):
    """Under a profiler session the spans are annotations on the host
    plane, whether the engine's tracer is the disabled default or a
    recording one: one ``batcher.step`` per decode step, one
    ``kv.sync_tables`` per table round trip, one ``batcher.admit`` per
    admit, with their args as stats and no value in a name."""
    cfg, params = model
    tr = Tracer() if tracer else None
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng, kv = _serve(cfg, params, tr)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    count = {n: sum(1 for name, _ in events if name == n) for n in SPANS}
    assert all(count[n] > 0 for n in SPANS), count
    assert count["batcher.step"] == eng._step_idx
    assert count["kv.sync_tables"] == kv.table_syncs \
        == kv.stats().table_syncs
    assert count["batcher.admit"] == 4
    admits = sorted((s["uid"], s["prompt_tokens"], s["prefix_hit_tokens"])
                    for name, s in events if name == "batcher.admit")
    assert admits == [(0, 19, 0), (1, 19, 24), (2, 13, 0), (3, 9, 0)]
    steps = [s["index"] for name, s in events if name == "batcher.step"]
    assert sorted(steps) == list(range(eng._step_idx))
    if tr is not None:
        ring = {e.name for e in tr.events()}
        assert set(SPANS) <= ring
        assert not any("[" in n for n in ring)


@pytest.fixture(scope="module")
def warmed(model):
    cfg, params = model
    _serve(cfg, params)
    return cfg, params


@pytest.mark.parametrize("profiler", [False, True], ids=["off", "on"])
def test_warm_engine_build_compiles_nothing(warmed, tmp_path, profiler):
    cfg, params = warmed
    log = telemetry.compile_log()
    t0 = telemetry.clock()
    if profiler:
        jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(cfg, params)
    finally:
        if profiler:
            jax.profiler.stop_trace()
    # no lowering and no backend compile (nor a cache load): a changed
    # program would need both. A "trace" event is left out: JAX times one
    # whenever its C++ dispatch cache misses, also where the jaxpr itself
    # comes from JAX's trace cache, so it tracks dispatch-cache state
    # (other tests in the process fill it), not the programs.
    assert [s for s in log.spans if s.t_start >= t0
            and s.kind in ("lower", "compile")] == []
