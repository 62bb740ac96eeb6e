"""KV manager: block-table and length round trips (the program's
``kv.sync_tables`` spans) inside the traced decode steps, per step.
Read, like the other span readers, only from a trace that holds the
chip's plane."""
import bisect

from bench.layer_metrics._common import device
from bench.layer_metrics._spans import host_spans, paired


def read(obs):
    steps = paired(obs, "batcher.step")
    if device(obs) is None or steps is None:
        return None
    starts = [s.start for s in steps]
    n = 0
    for e in host_spans(obs, "kv.sync_tables"):
        i = bisect.bisect_right(starts, e.start) - 1
        n += i >= 0 and e.end <= steps[i].end
    return n / len(steps)
