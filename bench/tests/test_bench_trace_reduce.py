"""Trace reduction on a small trace recorded on a TPU v5e
(``fixtures/probe.xplane.pb``: three runs of a jitted step holding one
``paged_verify`` kernel, each inside an ``engine.step`` annotation), and
the peaks table."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import trace_reduce as tr  # noqa: E402
from bench import work  # noqa: E402


@pytest.fixture(scope="module")
def trace():
    return tr.load(HERE / "fixtures" / "probe.xplane.pb")


def test_device_plane_modules_and_ops(trace):
    assert len(trace.devices) == 1
    dev = trace.devices[0]
    runs = tr.module_runs(dev, "jit_step")
    assert len(runs) == 3
    assert tr.module_name(runs[0].name) == "jit_step"
    assert all(1e-6 < e.dur < 1e-1 for e in runs)
    kern = tr.op_events(dev, "paged_verify")
    assert len(kern) == 3
    # each kernel run lies inside a run of its program
    for k in kern:
        assert any(r.start <= k.start and k.end <= r.end + 1e-6
                   for r in runs)


def test_busy_union_within_program_runs(trace):
    dev = trace.devices[0]
    busy = dev.busy()
    assert all(a[1] <= b[0] for a, b in zip(busy, busy[1:]))
    runs = sum(e.dur for e in tr.module_runs(dev, "jit_step"))
    assert 0 < tr.total(busy) <= runs * 1.001
    totals = tr.op_totals(dev)
    assert totals["paged_verify"] == pytest.approx(
        sum(e.dur for e in tr.op_events(dev, "paged_verify")))


def test_breakdown_names_gaps_by_host_span(trace):
    dev = trace.devices[0]
    lo, hi = dev.ops[0].start, dev.ops[-1].end
    b = tr.breakdown(trace, lo, hi, ("engine.step",))
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "paged_verify"
    names = {n for n, _ in b["idle_gaps"]}
    assert names <= {"engine.step", "host:none"}
    gap = sum(s for _, s in b["idle_gaps"])
    assert gap == pytest.approx(hi - lo - tr.total(dev.busy()), rel=1e-6) \
        or len(b["idle_gaps"]) == 10


def test_op_name_parses_hlo_text():
    assert tr.op_name("%paged_verify.12 = bf16[4] custom-call(...)") == \
        "paged_verify"
    assert tr.op_name("%copy-start = (bf16[2]) copy-start(%w)") == \
        "copy-start"
    assert tr.op_name("fusion.3 = f32[] fusion()") == "fusion"
    assert tr.module_name("jit__decode_paged_jit(123456)") == \
        "jit__decode_paged_jit"


def test_union_and_gaps():
    iv = tr.union([(0, 1), (0.5, 2), (3, 4)])
    assert iv == [(0, 2), (3, 4)]
    assert tr.total(iv) == 3
    assert tr.gaps(iv, -1, 5) == [(-1, 0), (2, 3), (4, 5)]
    assert tr.gaps(iv, 0.5, 1.5) == []


def test_unknown_device_kind_is_an_error(tmp_path):
    assert work.peak_for("TPU v5 lite").flops == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        work.peak_for("TPU v99")
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps({"devices": {}}))
    with pytest.raises(KeyError):
        work.peak_for("TPU v5 lite", p)
