"""Kernels: the paged decode-attention kernel's share of its roofline
(%). Least time: per call, the larger of its operations over peak and
its bytes (live K/V, q, out) over HBM bandwidth (``work.paged_verify``),
summed over the traced steps and layers; over the kernel's device time
summed from the trace."""
from bench import work
from bench.layer_metrics._common import kernel_events


def read(obs):
    steps = obs.traced_steps()
    if obs.peak is None or not steps:
        return None
    ev = kernel_events(obs, "paged_verify", obs.dm.layers, len(steps))
    if ev is None:
        return None
    least = sum(obs.peak.least_time(work.paged_verify(obs.dm, s.lengths))
                for s in steps) * obs.dm.layers
    return 100.0 * work.share(least, sum(e.dur for e in ev))
