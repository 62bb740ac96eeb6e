"""Window arithmetic: what counts inside the measured window, and the
percentiles the end-to-end metrics report.

All times are on one host clock (seconds). The window is
``[t0, t0 + seconds]``; a request is due at ``t0 + arrival_s``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence


def quantile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank quantile: the smallest value with at least a share
    ``q`` of the values at or below it. None for no values."""
    if not values:
        return None
    v = sorted(values)
    return v[min(max(math.ceil(q * len(v)) - 1, 0), len(v) - 1)]


def ttft_values(answered: Dict[int, float], failed_due: Sequence[float],
                run_end: float) -> List[float]:
    """TTFTs of every request due in the window. A failed or shed
    request misses every limit: it is given the longest answered TTFT
    plus the time from its due instant to the end of the run, so it
    ranks above every answered request and stays finite."""
    worst = max(answered.values(), default=0.0)
    return list(answered.values()) + [worst + max(run_end - due, 0.0)
                                      + 1e-6 for due in failed_due]


def inter_token_gaps(stamps: Dict[int, Sequence[float]], t0: float,
                     t1: float) -> List[float]:
    """Gaps between consecutive tokens of the same request whose later
    token was emitted inside the window."""
    out = []
    for ts in stamps.values():
        for a, b in zip(ts, ts[1:]):
            if t0 <= b <= t1:
                out.append(b - a)
    return out


def tokens_in_window(stamps: Dict[int, Sequence[float]], t0: float,
                     t1: float) -> int:
    return sum(1 for ts in stamps.values() for t in ts if t0 <= t <= t1)


def busy_time(intervals, t0: float, t1: float) -> float:
    """Length of the union of ``intervals`` clipped to [t0, t1]."""
    tot, end = 0.0, t0
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, t1)
        if e > s:
            tot += e - s
            end = e
    return tot
