"""The harness end to end on the CPU at a tiny size: a cell added as
files alone is found and run by name; the comparison passes the served
path and fails it with the timed path broken underneath (a token
altered where it is produced; the decode step handing back its KV state
unchanged); and the float8 control of the reference fails the limit."""
import argparse
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]

import bench_tiny  # noqa: E402
from bench import run as R  # noqa: E402
from bench import serve_paged as sp  # noqa: E402
from bench import spec, traffic  # noqa: E402

SEED = 2**31 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("tiny"))


def _run(root, fault=None, trace=0):
    args = argparse.Namespace(workload="tiny.chat", seed=SEED, seconds=2.0,
                              trace=trace)
    return R.run(args, root=root, chip=False, fault=fault,
                 t_process=time.perf_counter())


def test_a_cell_added_as_files_runs_by_name(root):
    cell = spec.Cell(spec.load_benchmark(root), "tiny.chat", root)
    assert cell.config["name"] == "tiny-qwen2"
    res = _run(root)
    assert res["correct"], res["check"]
    assert res["attempted"] == round(bench_tiny.CELL["rate_per_s"] * 2.0)
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "ttft_p90_s", "itl_p50_ms",
                                   "itl_p95_ms"}
    assert res["log"]["compiles_in_window"] == 0
    line = R.result_line(res)
    assert line.index('"check"') > line.index('"device"')


def test_trace_run_reports_the_counted_layer_metrics(root):
    res = _run(root, trace=1)
    assert res["correct"]
    # the CPU has no device trace: only the harness's own counts remain
    assert set(res["metrics"]) == {"queue_wait_p90_s", "slot_occupancy",
                                   "prefix_hit_share"}
    assert 0 < res["metrics"]["prefix_hit_share"]["value"] < 100


def _altered_token(eng):
    inner = eng.decode

    def decode(cache, tokens):
        logits, cache = inner(cache, tokens)
        return logits.at[0, 0].set(jnp.roll(logits[0, 0], 1)), cache
    eng.decode = decode


def _state_unchanged(eng):
    inner = eng.decode

    def decode(cache, tokens):
        logits, _ = inner(cache, tokens)
        return logits, cache
    eng.decode = decode


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(root, fault):
    res = _run(root, fault=fault)
    assert not res["correct"]
    gap = res["check"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_float8_control_fails_the_limit():
    mix, conf = bench_tiny.MIX, bench_tiny.CONFIG
    limit = bench_tiny.CELL["limits"]["served_logit_gap"]
    prep = sp.prepare(conf, mix, SEED, None, warm=False)
    reqs = traffic.open_loop(mix, rate=6.0, seconds=1.5,
                             vocab=prep.dm.vocab, seed=SEED)
    obs, done, _, _ = sp.serve(prep, reqs, seconds=1.5, trace=False,
                               drain=True)
    gaps, ctrl = sp.check(prep, reqs, done, SEED, 3, control=True)
    assert len(gaps) == len(ctrl) > 0
    assert gaps.max() <= limit < ctrl.max()
