"""Model families found by name. The grouped-query family that Qwen2 and
Mixtral share gives what the benchmark gave before families existed:
the same weight leaves, program config, work counts, weights and
reference, against values recorded from that code
(``fixtures/gqa_golden.json``). A family added as a file of a copy of
the checkout, with a configuration, mix and cell, is the one a run of
that cell uses, and a reference it breaks fails the cell."""
import argparse
import dataclasses
import hashlib
import json
import re
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]

import bench_tiny  # noqa: E402
from bench import reference, spec, work  # noqa: E402
from bench import run as R  # noqa: E402
from bench.model_spec import Dims  # noqa: E402
from bench.weights import make_weights  # noqa: E402

GOLDEN = json.loads((HERE / "fixtures" / "gqa_golden.json").read_text())
CONFIGS = HERE.parents[0] / "configs"
NAMES = sorted(GOLDEN["configs"])
SEED = 2**31 + 13

#: a family under a model_type of its own: the grouped-query code,
#: counting the calls a run makes to it
PROBE = '''"""A test family: the grouped-query decoder, its own model_type."""
import jax

from bench.families import _gqa

calls = {"hidden": 0, "program_config": 0}
dims, leaf_shapes, kernels = _gqa.dims, _gqa.leaf_shapes, _gqa.kernels
decode_step, prefill_chunk = _gqa.decode_step, _gqa.prefill_chunk


def program_config(dm):
    calls["program_config"] += 1
    return _gqa.program_config(dm)


def hidden(params, dm, tokens, mode="f32"):
    calls["hidden"] += 1
    return _gqa.hidden(params, dm, tokens, mode)
'''
#: the same family, its reference with noise on every hidden state
NOISY = PROBE + '''

def hidden(params, dm, tokens, mode="f32"):
    calls["hidden"] += 1
    h = _gqa.hidden(params, dm, tokens, mode)
    return h + jax.random.normal(jax.random.key(0), h.shape)
'''


def _dims(name):
    conf = json.loads((CONFIGS / f"{name}.json").read_text())
    return spec.family(conf["model_type"]).dims(conf)


def _tree_hash(params) -> str:
    flat = {}

    def walk(node, path):
        if isinstance(node, dict):
            for k in node:
                walk(node[k], path + [k])
        else:
            flat["/".join(path)] = node

    walk(params, [])
    h = hashlib.sha256()
    for p in sorted(flat):
        a = np.asarray(flat[p])
        h.update(p.encode())
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# -- the grouped-query family as before ------------------------------------- #

@pytest.mark.parametrize("name", NAMES)
def test_model_spec_dims_are_the_family_dims(name):
    dm = _dims(name)
    assert Dims.load(CONFIGS / f"{name}.json") == dm
    assert dm.model_type == {"qwen2.5-14b-l12": "qwen2",
                             "mixtral-8x7b-l4": "mixtral"}[name]


@pytest.mark.parametrize("name", NAMES)
def test_leaf_shapes_as_before(name):
    dm = _dims(name)
    got = {p: [list(s), k, sc] for p, (s, k, sc)
           in spec.family(dm.model_type).leaf_shapes(dm).items()}
    assert got == GOLDEN["configs"][name]["leaf_shapes"]


@pytest.mark.parametrize("name", NAMES)
def test_program_config_as_before(name):
    dm = _dims(name)
    cfg = spec.family(dm.model_type).program_config(dm)
    got = json.loads(json.dumps(dataclasses.asdict(cfg)))
    assert got == GOLDEN["configs"][name]["program_config"]


@pytest.mark.parametrize("count", ["decode_step", "paged_verify",
                                   "prefill_chunk", "paged_prefill"])
@pytest.mark.parametrize("name", NAMES)
def test_work_counts_as_before(name, count):
    dm, fn = _dims(name), getattr(work, count)
    if count in ("decode_step", "paged_verify"):
        got = [fn(dm, lengths) for lengths in GOLDEN["verify_lengths"]]
    else:
        got = [fn(dm, prefix, chunk) for prefix, chunk in GOLDEN["prefill"]]
    assert [[w.flops, w.bytes] for w in got] == \
        GOLDEN["configs"][name][count]


@pytest.mark.parametrize("conf", [bench_tiny.CONFIG, bench_tiny.MOE],
                         ids=lambda c: c["name"])
def test_weights_and_reference_bit_equal(conf):
    dm = spec.family(conf["model_type"]).dims(conf)
    params = make_weights(dm, GOLDEN["seed"])
    rec = GOLDEN["tiny"][conf["name"]]
    assert _tree_hash(params) == rec["weights"]
    toks = np.random.default_rng(GOLDEN["seed"]).integers(
        3, dm.vocab, (2, 48)).astype(np.int32)
    for mode in ("f32", "fp8"):
        h = np.asarray(reference.hidden(params, dm, toks, mode))
        assert hashlib.sha256(h.tobytes()).hexdigest() == \
            rec[f"hidden_{mode}"], mode


def test_a_family_is_loaded_once():
    qwen2 = spec.family("qwen2")
    assert spec.family("qwen2") is qwen2
    assert spec.family("mixtral").hidden is qwen2.hidden     # one jit each


# -- a family added as a file ---------------------------------------------- #

def _run(root, workload):
    args = argparse.Namespace(workload=workload, seed=SEED, seconds=2.0,
                              trace=0)
    return R.run(args, root=root, chip=False, t_process=time.perf_counter())


def _root(tmp, model_type, source):
    conf = dict(bench_tiny.CONFIG, name=f"tiny-{model_type}",
                model_type=model_type)
    return bench_tiny.make_root(tmp, config=conf,
                                workload=f"{model_type}.chat",
                                families={model_type: source})


def test_a_family_added_as_a_file_runs_by_name(tmp_path):
    root = _root(tmp_path, "tiny_probe", PROBE)
    res = _run(root, "tiny_probe.chat")
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["log"]["served_tokens_compared"] > 0
    fam = spec.family("tiny_probe")
    assert Path(fam.__file__) == \
        root / "bench" / "families" / "tiny_probe.py"
    assert fam.calls["program_config"] >= 1 and fam.calls["hidden"] >= 1


def test_a_family_whose_reference_is_noisy_is_not_correct(tmp_path):
    root = _root(tmp_path, "tiny_noisy", NOISY)
    res = _run(root, "tiny_noisy.chat")
    assert spec.family("tiny_noisy").calls["hidden"] >= 1
    assert not res["correct"]
    gap = res["check"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_an_unknown_model_type_names_the_path_it_looked_for(tmp_path):
    conf = dict(bench_tiny.CONFIG, model_type="no_such_family")
    root = bench_tiny.make_root(tmp_path, config=conf)
    path = root / "bench" / "families" / "no_such_family.py"
    with pytest.raises(FileNotFoundError, match=re.escape(str(path))):
        _run(root, "tiny.chat")
    with pytest.raises(ValueError, match="not a module name"):
        spec.family("../qwen2")
