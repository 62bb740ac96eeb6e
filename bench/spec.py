"""Find a cell's files by the names ``BENCHMARK.json`` gives.

- configuration ``<c>``: the ``file`` its entry names (sizes, source,
  what was cut), in Hugging Face keys;
- its model family: ``bench/families/<model_type>.py``, by the
  configuration's own ``model_type`` key, with the sizes, weight
  leaves, program config, reference and work counts of that
  architecture (what a family provides: ``bench/families/__init__.py``);
- traffic ``<t>``: ``bench/mixes/<t>.json``, read by the generator that
  its ``kind`` names in ``traffic.GENERATORS``;
- the cell ``<workload>``: ``bench/cells/<workload>.json``, its fixed
  rate and the limits of its correctness check;
- per-layer metric ``<m>``: ``bench/layer_metrics/<m>.py`` with
  ``read(obs) -> float | None`` (dots in the name become ``_``).

A later cell or architecture adds files and entries; nothing here
changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _by_name(items: List[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


class Cell:
    """Everything a run of one workload reads, resolved from names."""

    def __init__(self, bench: dict, workload: str, root: Path = ROOT):
        self.bench = bench
        self.root = root
        self.workload = _by_name(bench["workloads"], workload, "workload")
        self.name = workload
        self.chips = int(self.workload["chips"])
        entry = _by_name(bench["configs"], self.workload["config"],
                         "config")
        self.config = json.loads((root / entry["file"]).read_text())
        self.family = family(self.config["model_type"], root)
        self.mix = json.loads(
            (root / "bench" / "mixes" / f"{self.workload['traffic']}.json"
             ).read_text())
        self.params = json.loads(
            (root / "bench" / "cells" / f"{workload}.json").read_text())

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"] if self._has(m)]

    def per_layer(self) -> List[dict]:
        return [m for m in self.bench["per_layer"] if self._has(m)]

    def _has(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells


def family(model_type: str, root: Path = ROOT):
    """The model family of ``model_type``: ``bench/families/<model_type>.py``
    under ``root``. A process loads each once, from the first root that
    asks for it, and every later call returns that module (loading it
    again would make new jitted reference functions, compiled anew)."""
    if not re.fullmatch(r"[A-Za-z0-9_]+", model_type):
        raise ValueError(f"model_type {model_type!r} is not a module name")
    name = f"bench_family_{model_type}"
    if name in sys.modules:
        return sys.modules[name]
    path = root / "bench" / "families" / f"{model_type}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no model family for model_type "
                                f"{model_type!r}: {path} is not there")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def reader(metric: str, root: Path = ROOT) -> Callable:
    """The ``read`` function of a per-layer metric, found by its name."""
    path = root / "bench" / "layer_metrics" / \
        f"{metric.replace('.', '_')}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_layer_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_layer_metrics(metrics: List[dict], obs,
                       root: Path = ROOT) -> Dict[str, dict]:
    """Each metric's reader over the observation; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        v = reader(m["name"], root)(obs)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
