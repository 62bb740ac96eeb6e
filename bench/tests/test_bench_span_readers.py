"""The readers of the program's own spans (``step_host_ms``,
``admit_host_ms``, ``table_syncs_per_step``, ``setup_lower_s``): on a
trace recorded on a TPU v5e (``fixtures/spans.xplane.pb.gz``, by
``tools/record_spans.py``: a tiny paged
engine run through the harness, with ``spans.json`` holding what the
run counted itself), and on synthetic traces: a span in which the chip
ran nothing, spans that do not pair with the harness's, and a program
that emits none."""
import gzip
import json
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]

from bench import spec  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402
from bench.layer_metrics._spans import host_spans, idle_s  # noqa: E402

READERS = ("step_host_ms", "admit_host_ms", "table_syncs_per_step",
           "setup_lower_s")


def _obs(trace, steps, t0=0.0):
    return types.SimpleNamespace(trace=trace, t0=t0,
                                 traced_steps=lambda: [None] * steps)


def _read(name, obs):
    return spec.reader(name)(obs)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    meta = json.loads((HERE / "fixtures" / "spans.json").read_text())
    path = tmp_path_factory.mktemp("spans") / "spans.xplane.pb"
    path.write_bytes(gzip.decompress(
        (HERE / "fixtures" / "spans.xplane.pb.gz").read_bytes()))
    trace = tr.load(path)
    return meta, trace, _obs(trace, meta["traced_steps"], meta["t0"])


def _log_from(meta):
    from repro.runtime.telemetry import CompileLog, CompileSpan

    log = CompileLog()
    log.t_start = meta["compile_log"]["t_start"]
    log.spans.extend(CompileSpan(*s) for s in meta["compile_log"]["spans"])
    return log


def test_recorded_trace_pairs_program_and_harness_spans(recorded):
    meta, trace, _ = recorded
    assert meta["device"]["platform"] == "tpu" and trace.devices
    steps = host_spans(_obs(trace, 0), "batcher.step")
    outer = host_spans(_obs(trace, 0), "engine.step")
    assert len(steps) == len(outer) == meta["traced_steps"] > 0
    assert all(o.start <= s.start and s.end <= o.end
               for s, o in zip(steps, outer))
    assert len(host_spans(_obs(trace, 0), "batcher.admit")) \
        == meta["admits"] > 0
    assert len(host_spans(_obs(trace, 0), "kv.sync_tables")) \
        == meta["table_syncs"]


def test_recorded_trace_readers(recorded, monkeypatch):
    from repro.runtime import telemetry

    meta, trace, obs = recorded
    log = _log_from(meta)
    monkeypatch.setattr(telemetry, "compile_log", lambda: log)
    got = {m: _read(m, obs) for m in READERS}
    assert all(v is not None for v in got.values()), got
    steps = host_spans(obs, "batcher.step")
    step_ms = sorted(s.dur * 1e3 for s in steps)
    assert 0 <= got["step_host_ms"] <= step_ms[len(step_ms) // 2]
    assert got["admit_host_ms"] >= 0
    assert 0 <= got["table_syncs_per_step"] \
        <= meta["table_syncs"] / meta["traced_steps"]
    lowered = sum(s[3] - s[2] for s in meta["compile_log"]["spans"]
                  if s[0] in ("trace", "lower"))
    assert 0 < got["setup_lower_s"] <= lowered
    assert got["setup_lower_s"] <= meta["t0"] - log.t_start


def _trace(host, ops=()):
    dev = tr.DeviceTrace(0, [], [tr.Event("op", a, b - a) for a, b in ops])
    return tr.Trace([dev], sorted((tr.Event(n, a, b - a)
                                   for n, a, b in host),
                                  key=lambda e: e.start))


def test_a_step_without_device_work_is_all_idle():
    host = [("engine.step", 0.0, 1.0), ("batcher.step", 0.1, 0.9),
            ("engine.step", 2.0, 3.0), ("batcher.step", 2.0, 3.0)]
    # the chip ran only outside the first step, half of the second
    obs = _obs(_trace(host, ops=[(1.2, 1.5), (2.25, 2.75)]), steps=2)
    assert _read("step_host_ms", obs) == pytest.approx((800 + 500) / 2)


def test_admit_idle_leaves_out_its_interleaved_steps():
    host = [("engine.admit", 0.0, 1.0), ("batcher.admit", 0.0, 1.0),
            ("engine.step", 0.4, 0.6), ("batcher.step", 0.4, 0.6)]
    # the chip ran a chunk (0.1-0.3) and half of the interleaved step
    obs = _obs(_trace(host, ops=[(0.1, 0.3), (0.45, 0.55)]), steps=1)
    assert _read("admit_host_ms", obs) == pytest.approx(1000 - 200 - 200)
    assert _read("step_host_ms", obs) == pytest.approx(100)


def test_syncs_counted_inside_steps_only():
    host = [("engine.step", 0.0, 1.0), ("batcher.step", 0.1, 0.9),
            ("kv.sync_tables", 0.2, 0.3), ("kv.sync_tables", 0.4, 0.5),
            ("engine.step", 2.0, 3.0), ("batcher.step", 2.1, 2.9),
            ("kv.sync_tables", 1.2, 1.3)]        # in an admit, not a step
    obs = _obs(_trace(host), steps=2)
    assert _read("table_syncs_per_step", obs) == 1.0


@pytest.mark.parametrize("case", ["count", "outside", "admit_count"])
def test_mismatched_spans_read_as_nothing(case):
    host = [("engine.step", 0.0, 1.0), ("batcher.step", 0.1, 0.9),
            ("engine.admit", 2.0, 3.0), ("batcher.admit", 2.1, 2.9)]
    steps = 1
    if case == "count":
        steps = 2                   # the harness traced another step
    elif case == "outside":
        host[1] = ("batcher.step", 0.5, 1.5)
    else:
        host.append(("batcher.admit", 4.0, 4.5))
    obs = _obs(_trace(host, ops=[(0.2, 0.3)]), steps)
    if case == "admit_count":
        assert _read("step_host_ms", obs) is not None
        assert _read("admit_host_ms", obs) is None
    else:
        assert _read("step_host_ms", obs) is None
        assert _read("table_syncs_per_step", obs) is None
        assert _read("admit_host_ms", obs) is not None


def test_a_program_without_spans_reads_as_nothing(monkeypatch):
    from repro.runtime import telemetry

    obs = _obs(_trace([("engine.step", 0.0, 1.0),
                       ("engine.admit", 2.0, 3.0)], ops=[(0.2, 0.3)]), 1)
    for m in READERS[:3]:
        assert _read(m, obs) is None
    monkeypatch.delattr(telemetry, "compile_log")
    assert _read("setup_lower_s", obs) is None
    assert _read("step_host_ms", _obs(None, 1)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_trace_without_the_chip_reads_as_nothing(name, monkeypatch):
    from repro.runtime import telemetry
    from repro.runtime.telemetry import CompileLog, CompileSpan

    log = CompileLog()
    log.spans.append(CompileSpan("trace", "step", -2.0, -1.0))
    monkeypatch.setattr(telemetry, "compile_log", lambda: log)
    host = [("engine.step", 0.0, 1.0), ("batcher.step", 0.1, 0.9),
            ("kv.sync_tables", 0.2, 0.3),
            ("engine.admit", 2.0, 3.0), ("batcher.admit", 2.1, 2.9)]
    with_chip = _obs(_trace(host, ops=[(0.2, 0.3)]), steps=1)
    assert _read(name, with_chip) is not None
    # the harness run on the CPU: its trace has host spans only
    without = _obs(tr.Trace([], with_chip.trace.host), steps=1)
    assert _read(name, without) is None


def test_idle_inside_a_span():
    busy = [(0.0, 1.0), (2.0, 3.0), (4.0, 5.0)]
    ends = [e for _, e in busy]
    assert idle_s(busy, ends, 0.5, 4.5) == pytest.approx(2.0)
    assert idle_s(busy, ends, 1.0, 2.0) == pytest.approx(1.0)
    assert idle_s(busy, ends, 2.2, 2.8) == pytest.approx(0.0)
    assert idle_s(busy, ends, 6.0, 7.0) == pytest.approx(1.0)


def test_compile_log_counts_nested_spans_once():
    from repro.runtime.telemetry import CompileLog, CompileSpan

    log = CompileLog()
    log.spans.extend([
        CompileSpan("trace", "step", 0.0, 1.0),
        CompileSpan("trace", "callee", 0.2, 0.4),      # inside step's
        CompileSpan("lower", "jit(step)", 1.0, 1.5),
        CompileSpan("compile", "jit(step)", 1.5, 3.0),
        CompileSpan("trace", "late", 9.0, 10.0)])
    assert log.seconds(("trace", "lower")) == pytest.approx(2.5)
    assert log.seconds(("trace", "lower"), t_end=5.0) == pytest.approx(1.5)
    assert log.seconds(("compile",)) == pytest.approx(1.5)
