"""Reduce a profiler trace (``.xplane.pb``) to device times.

Read with ``jax.profiler.ProfileData``. On a TPU each chip is a plane
``/device:TPU:<n>``; its line ``XLA Modules`` holds one event per run of
a compiled program (``jit_<name>(<fingerprint>)``) and ``XLA Ops`` one
event per operation (named by its HLO text, ``%<op name> = ...``). The
host's ``TraceAnnotation`` spans are on plane ``/host:CPU``. Device and
host times share one timebase to within about a millisecond; nothing
here relies on closer alignment than that.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start, end) in seconds

_DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_OP_NAME = re.compile(r"^%?([\w.\-]+?)(?:\.\d+)?\s*=")
_MODULE_NAME = re.compile(r"^(.*?)(?:\(\d+\))?$")


@dataclasses.dataclass
class Event:
    name: str
    start: float                        # seconds
    dur: float                          # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class DeviceTrace:
    """What one chip did: program runs and operations, in time order."""

    index: int
    modules: List[Event]
    ops: List[Event]

    def busy(self) -> List[Interval]:
        return union((e.start, e.end) for e in self.ops)


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    host: List[Event]                   # TraceAnnotation and other host spans


def op_name(hlo_text: str) -> str:
    """``'%paged_verify.1 = bf16[...] custom-call(...)'`` -> ``paged_verify``."""
    m = _OP_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text.split(" ", 1)[0]


def module_name(name: str) -> str:
    """``'jit__decode_paged_jit(1234)'`` -> ``jit__decode_paged_jit``."""
    return _MODULE_NAME.match(name).group(1)


def load(path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            mods = _events(lines.get("XLA Modules"))
            ops = _events(lines.get("XLA Ops"))
            devices.append(DeviceTrace(int(m.group(2)), mods, ops))
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend(_events(ln))
    devices.sort(key=lambda d: d.index)
    host.sort(key=lambda e: e.start)
    return Trace(devices, host)


def _events(line) -> List[Event]:
    if line is None:
        return []
    out = [Event(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
           for ev in line.events]
    out.sort(key=lambda e: e.start)
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``busy`` (merged) inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def module_runs(dev: DeviceTrace, contains: str) -> List[Event]:
    """Runs of the compiled programs whose name contains ``contains``."""
    return [e for e in dev.modules if contains in module_name(e.name)]


def op_events(dev: DeviceTrace, name: str) -> List[Event]:
    """Operations whose HLO name is ``name`` (any ``.<n>`` suffix)."""
    return [e for e in dev.ops if op_name(e.name) == name]


def op_totals(dev: DeviceTrace) -> Dict[str, float]:
    """Seconds per operation name, over the whole trace."""
    t: Dict[str, float] = defaultdict(float)
    for e in dev.ops:
        t[op_name(e.name)] += e.dur
    return dict(t)


def host_span_at(host: Sequence[Event], t: float,
                 names: Sequence[str]) -> Optional[str]:
    """The innermost of ``names`` among host spans covering instant t."""
    best = None
    for e in host:
        if e.start > t:
            break
        if e.name in names and e.end >= t and (
                best is None or e.start >= best.start):
            best = e
    return best.name if best else None


def breakdown(trace: Trace, lo: float, hi: float,
              span_names: Sequence[str], top: int = 10) -> dict:
    """The device operations that took most time on chip 0, and its
    longest idle gaps in [lo, hi], each named by the host span in which
    it fell (``host:none`` where the harness was in none of them)."""
    dev = trace.devices[0]
    ops = sorted(op_totals(dev).items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(dev.busy(), lo, hi), key=lambda g: g[0] - g[1])[:top]
    named = [[host_span_at(trace.host, (s + e) / 2, span_names)
              or "host:none", e - s] for s, e in idle]
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": named}
