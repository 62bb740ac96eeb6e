"""The chat mix's generator and the window arithmetic."""
import json
import os
import shutil
import subprocess
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

from bench import accounting as acc  # noqa: E402
from bench import traffic  # noqa: E402

MIX = json.loads((ROOT / "bench" / "mixes" / "chat.json").read_text())


def _gen(seed, rate=0.72, seconds=51.0, vocab=152064):
    return traffic.open_loop(MIX, rate=rate, seconds=seconds, vocab=vocab,
                             seed=seed)


def test_same_seed_same_trace_large_seeds_ok():
    a, b = _gen(2**31 + 77), _gen(2**31 + 77)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    c = _gen(2**31 + 78)
    assert not np.array_equal(a[0].prompt, c[0].prompt)


def test_every_seed_replays_the_same_schedule():
    a, b = _gen(3), _gen(123456789012)
    assert len(a) == len(b) == round(0.72 * 51)
    key = lambda rs: [(len(r.prompt), r.max_new_tokens, r.system,  # noqa
                       r.arrival_s) for r in rs]
    assert key(a) == key(b)
    assert all(0 <= r.arrival_s < 51.0 for r in a)
    assert [r.arrival_s for r in a] == sorted(r.arrival_s for r in a)


def test_lengths_are_chunk_aligned_and_clipped():
    sp = MIX["system_prompts"]["tokens"]
    for r in _gen(9, rate=5.0, seconds=60.0):
        user = len(r.prompt) - sp
        assert user % 64 == 0 and 64 <= user <= 1536
        assert 16 <= r.max_new_tokens <= 512
        assert len(r.prompt) + r.max_new_tokens <= traffic.max_context(MIX)
        assert r.prompt.min() >= 3 and r.prompt.max() < 152064
    assert traffic.chunk_remainders(MIX, 256) == [64, 128, 192, 256]


def test_shared_prefixes_follow_zipf():
    reqs = _gen(4, rate=50.0, seconds=60.0)
    systems = traffic.system_prompts(MIX, 152064, 4)
    for r in reqs:
        assert np.array_equal(r.prompt[:512], systems[r.system])
    share = Counter(r.system for r in reqs)
    w = 1 / np.arange(1, 5)
    want = w / w.sum()
    for k in range(4):
        assert share[k] / len(reqs) == pytest.approx(want[k], abs=0.03)


def test_quantile_and_failed_rank_above_answered():
    assert acc.quantile([], 0.9) is None
    assert acc.quantile([5, 1, 3, 2, 4], 0.5) == 3
    assert acc.quantile(list(range(1, 11)), 0.9) == 9
    vals = acc.ttft_values({1: 0.5, 2: 3.0}, failed_due=[10.0],
                           run_end=12.0)
    assert max(vals) > 3.0 and all(np.isfinite(vals))
    assert acc.quantile(vals, 0.9) == max(vals)


def test_tokens_after_the_window_are_not_counted():
    stamps = {1: [0.5, 1.0, 2.5], 2: [1.5, 3.5]}
    assert acc.tokens_in_window(stamps, 0.0, 2.0) == 3
    assert sorted(acc.inter_token_gaps(stamps, 0.0, 2.0)) == [0.5]
    assert acc.busy_time([(0, 1), (0.5, 3), (4, 9)], 0.5, 5) == 3.5


def _run(cwd, env_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=env_path)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen2.5-14b-l12.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT, str(ROOT / "src"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
