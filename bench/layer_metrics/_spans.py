"""Helpers the readers of the program's own spans share: a span's events
from the traced host plane, paired one to one with the harness's span
around the same call, and the time inside each in which the chip ran
nothing. Where the program emits no such span (a program older than
its spans) the readers find nothing and report nothing."""
from __future__ import annotations

import bisect
import statistics
from typing import List, Optional

from bench import trace_reduce as tr
from bench.layer_metrics._common import device

#: the program's span -> the harness's span around the same call
AROUND = {"batcher.step": "engine.step", "batcher.admit": "engine.admit"}


def host_spans(obs, name: str) -> List[tr.Event]:
    if obs.trace is None:
        return []
    return [e for e in obs.trace.host if e.name == name]


def paired(obs, name: str) -> Optional[List[tr.Event]]:
    """The program's ``name`` spans, or None unless each lies inside its
    own harness span (``AROUND``), one to one; the steps also number the
    harness's traced steps."""
    own, outer = host_spans(obs, name), host_spans(obs, AROUND[name])
    if not own or len(own) != len(outer):
        return None
    if name == "batcher.step" and len(own) != len(obs.traced_steps()):
        return None
    if any(not (o.start <= e.start and e.end <= o.end)
           for e, o in zip(own, outer)):
        return None
    return own


def idle_s(busy: List[tr.Interval], ends: List[float], a: float,
           b: float) -> float:
    """Seconds of [a, b] in which no interval of ``busy`` (merged, in
    order; ``ends`` their ends) ran."""
    covered = 0.0
    i = bisect.bisect_right(ends, a)
    while i < len(busy) and busy[i][0] < b:
        covered += min(busy[i][1], b) - max(busy[i][0], a)
        i += 1
    return (b - a) - covered


def median_idle_ms(obs, name: str, nested: Optional[str] = None
                   ) -> Optional[float]:
    """Median over the program's ``name`` spans of the device-idle time
    inside each (ms), less the idle time inside the ``nested`` spans that
    lie within it."""
    dev = device(obs)
    spans = paired(obs, name)
    if dev is None or spans is None:
        return None
    busy = dev.busy()
    ends = [e for _, e in busy]
    inner = host_spans(obs, nested) if nested else []

    def own_idle(s: tr.Event) -> float:
        return idle_s(busy, ends, s.start, s.end) - sum(
            idle_s(busy, ends, n.start, n.end) for n in inner
            if s.start <= n.start and n.end <= s.end)

    return statistics.median(own_idle(s) for s in spans) * 1e3
