"""A tiny cell for CPU tests: a copy of the benchmark's files in a
temporary root, with a small configuration, mix and cell (and, where
given, model families) added as files and named in its own
BENCHMARK.json."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIG = {
    "name": "tiny-qwen2", "model_type": "qwen2", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "vocab_size": 512,
    "qkv_bias": True, "sliding_window": None,
    "serving": {"dtype": "bfloat16", "page_tokens": 16, "prefill_chunk": 32,
                "slots": 4, "n_pages": 41},
}
MOE = dict(CONFIG, name="tiny-mixtral", model_type="mixtral",
           num_local_experts=4, num_experts_per_tok=2, qkv_bias=False)
MIX = {
    "kind": "open_loop", "size_seed": 5,
    "system_prompts": {"count": 4, "tokens": 32, "zipf_s": 1.0},
    "user_tokens": {"median": 32, "sigma": 0.5, "min": 16, "max": 64,
                    "round_to": 16},
    "output_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
}
CELL = {"rate_per_s": 6.0, "sample": 3, "gap_statistic": "max",
        "limits": {"served_logit_gap": 0.15}}


def make_root(tmp: Path, config=CONFIG, mix=MIX, cell=CELL,
              workload="tiny.chat", families=None) -> Path:
    """A checkout holding the benchmark's files and one more cell, added
    as files only; ``families`` maps a model_type to the source of its
    ``bench/families/<model_type>.py``."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for model_type, source in (families or {}).items():
        (root / "bench" / "families" / f"{model_type}.py").write_text(source)
    cname, tname = config["name"], workload.split(".", 1)[1]
    (root / "bench" / "configs" / f"{cname}.json").write_text(
        json.dumps(config))
    (root / "bench" / "mixes" / f"{tname}_tiny.json").write_text(
        json.dumps(mix))
    (root / "bench" / "cells" / f"{workload}.json").write_text(
        json.dumps(cell))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": cname, "source": "test",
                             "file": f"bench/configs/{cname}.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": workload, "config": cname,
                               "traffic": f"{tname}_tiny", "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(workload)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
