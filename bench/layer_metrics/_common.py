"""Helpers the per-layer readers share: device times of the traced
window, matched to the harness's own records of the same calls."""
from __future__ import annotations

import statistics
from typing import List, Optional

from bench import trace_reduce as tr

DECODE_PROGRAM = "decode_paged_jit"
CHUNK_PROGRAM = "prefill_chunk_jit"


def device(obs):
    if obs.trace is None or not obs.trace.devices:
        return None
    return obs.trace.devices[0]


def runs(obs, program: str) -> Optional[List[tr.Event]]:
    dev = device(obs)
    if dev is None:
        return None
    return tr.module_runs(dev, program) or None


def decode_runs(obs):
    """Device runs of the decode-step program, one per traced step, or
    None where the trace and the harness disagree on the count."""
    r = runs(obs, DECODE_PROGRAM)
    if r is None or len(r) != len(obs.traced_steps()):
        return None
    return r


def chunk_runs(obs):
    """(record, device run) of each traced prefill chunk, in call order."""
    r = runs(obs, CHUNK_PROGRAM)
    ch = obs.traced_chunks()
    if r is None or len(r) != len(ch):
        return None
    return list(zip(ch, r))


def kernel_events(obs, name: str, per_call: int, calls: int):
    dev = device(obs)
    if dev is None:
        return None
    ev = tr.op_events(dev, name)
    if not ev or len(ev) != per_call * calls:
        return None
    return ev


def median_ms(events) -> Optional[float]:
    if not events:
        return None
    return statistics.median(e.dur for e in events) * 1e3
