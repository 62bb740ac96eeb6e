"""Operations and bytes each kernel and step needs, from shapes alone.

Counted from what the algorithm needs, whatever implements it: live
tokens only (not the pages a kernel visits), the top-k experts a token
uses (not the experts a lossless dispatch computes), one pass over the
weights a step must read. So a faster implementation raises a share and
cannot push it past 1. A multiply-add is two operations.

Each model family (``bench/families/<model_type>.py``) counts its own:
``decode_step``, ``prefill_chunk`` and, for each attention kernel's
trace name, its per-layer count in ``kernels``. The functions of those
names here forward to the family of the sizes they are given.

Peaks come from ``peaks.json`` by the device's ``device_kind``; a kind
that is not there is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Sequence

from . import spec

BF16 = 2
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def scale(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)


@dataclasses.dataclass(frozen=True)
class Peak:
    kind: str
    flops: float                 # bf16 operations per second
    hbm_bytes: float             # bytes per second

    def least_time(self, w: Work) -> float:
        """The roofline: the larger of compute time and memory time."""
        return max(w.flops / self.flops, w.bytes / self.hbm_bytes)


def peak_for(device_kind: str, path=PEAKS_FILE) -> Peak:
    table = json.loads(Path(path).read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add it with its source")
    p = table["devices"][device_kind]
    return Peak(device_kind, p["bf16_flops"], p["hbm_bytes_per_s"])


def share(least: float, measured: float) -> float:
    """``least / measured``; above 1 means the work or the peak is
    counted wrong, which is an error and is never clipped."""
    if measured <= 0:
        raise ValueError("no measured time")
    s = least / measured
    if s > 1.0:
        raise ValueError(f"share {s:.4f} above 1: the operations or bytes "
                         "are counted too high, or the time leaves out "
                         "part of the work")
    return s


# -- counts by the sizes' model family -------------------------------------- #

def paged_verify(dm, lengths: Sequence[int]) -> Work:
    """One decode position per sequence against its live cache, one
    layer; ``lengths`` counts the positions each attends to."""
    return spec.family(dm.model_type).kernels["paged_verify"](dm, lengths)


def paged_prefill(dm, prefix: int, chunk: int) -> Work:
    """A chunk of ``chunk`` queries after ``prefix`` cached positions,
    causal, one layer."""
    return spec.family(dm.model_type).kernels["paged_prefill"](
        dm, prefix, chunk)


def decode_step(dm, lengths: Sequence[int]) -> Work:
    """One decode step of the live sequences (``lengths`` as for
    ``paged_verify``)."""
    return spec.family(dm.model_type).decode_step(dm, lengths)


def prefill_chunk(dm, prefix: int, chunk: int) -> Work:
    """One prefill chunk of one sequence, with its full logits."""
    return spec.family(dm.model_type).prefill_chunk(dm, prefix, chunk)


def active_experts(dm, tokens: int) -> float:
    """Experts a step of ``tokens`` tokens must read, under seeded random
    routing: E (1 - (1 - K/E)^T)."""
    E, K = dm.experts, dm.top_k
    return E * (1.0 - (1.0 - K / E) ** tokens)
