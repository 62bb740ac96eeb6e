"""Operations and bytes each kernel and step needs, from shapes alone.

Counted from what the algorithm needs, whatever implements it: live
tokens only (not the pages a kernel visits), the top-k experts a token
uses (not the experts a lossless dispatch computes), one pass over the
weights a step must read. So a faster implementation raises a share and
cannot push it past 1. A multiply-add is two operations.

Peaks come from ``peaks.json`` by the device's ``device_kind``; a kind
that is not there is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Sequence

from .model_spec import Dims

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


# -- attention kernels (one layer) ------------------------------------------ #

def paged_verify(dm: Dims, lengths: Sequence[int]) -> Work:
    """One decode position per sequence against its live cache.
    ``lengths`` counts the positions each attends to (the new one
    included). q and out once; K and V of every live position once."""
    H, hk, hd = dm.heads, dm.kv_heads, dm.head_dim
    n = sum(lengths)
    flops = 4.0 * H * hd * n                        # QK^T and PV
    byts = BF16 * (2 * hk * hd * n + 2 * len(lengths) * H * hd)
    return Work(flops, byts)


def paged_prefill(dm: Dims, prefix: int, chunk: int) -> Work:
    """A chunk of ``chunk`` queries after ``prefix`` cached positions,
    causal: query t sees prefix + t + 1 positions."""
    H, hk, hd = dm.heads, dm.kv_heads, dm.head_dim
    pairs = chunk * prefix + chunk * (chunk + 1) / 2
    flops = 4.0 * H * hd * pairs
    byts = BF16 * (2 * hk * hd * (prefix + chunk) + 2 * chunk * H * hd)
    return Work(flops, byts)


# -- whole steps ---------------------------------------------------------- #

def active_experts(dm: Dims, tokens: int) -> float:
    """Experts a step of ``tokens`` tokens must read, under seeded random
    routing: E (1 - (1 - K/E)^T)."""
    E, K = dm.experts, dm.top_k
    return E * (1.0 - (1.0 - K / E) ** tokens)


def _dense_flops_per_token(dm: Dims) -> float:
    """Matrix-product operations per token outside attention scores,
    over all layers and the output head (top-k experts only)."""
    d, H, hk, hd, f = dm.d, dm.heads, dm.kv_heads, dm.head_dim, dm.d_ff
    proj = d * (H + 2 * hk) * hd + H * hd * d
    ffn = 3 * d * f * (dm.top_k if dm.experts else 1)
    router = d * dm.experts
    return 2.0 * (dm.layers * (proj + ffn + router) + d * dm.vocab)


def _weight_bytes(dm: Dims, tokens: int) -> float:
    """Weights a step of ``tokens`` tokens reads once: every dense
    weight, the experts it routes to, the output head and the tokens'
    embedding rows."""
    d, H, hk, hd, f = dm.d, dm.heads, dm.kv_heads, dm.head_dim, dm.d_ff
    proj = d * (H + 2 * hk) * hd + H * hd * d
    if dm.experts:
        ffn = 3 * d * f * active_experts(dm, tokens) + d * dm.experts
    else:
        ffn = 3 * d * f
    return BF16 * (dm.layers * (proj + ffn) + d * dm.vocab + tokens * d)


def decode_step(dm: Dims, lengths: Sequence[int]) -> Work:
    """One decode step of the live sequences (``lengths`` as for
    ``paged_verify``): weights once, attention per layer, logits out."""
    B = len(lengths)
    attn = paged_verify(dm, lengths).scale(dm.layers)
    kv_write = BF16 * dm.layers * 2 * dm.kv_heads * dm.head_dim * B
    logits = BF16 * B * dm.vocab
    return Work(_dense_flops_per_token(dm) * B,
                _weight_bytes(dm, B) + kv_write + logits) + attn


def prefill_chunk(dm: Dims, prefix: int, chunk: int) -> Work:
    """One prefill chunk of one sequence, with its full logits."""
    attn = paged_prefill(dm, prefix, chunk).scale(dm.layers)
    kv_write = BF16 * dm.layers * 2 * dm.kv_heads * dm.head_dim * chunk
    logits = BF16 * chunk * dm.vocab
    return Work(_dense_flops_per_token(dm) * chunk,
                _weight_bytes(dm, chunk) + kv_write + logits) + attn


