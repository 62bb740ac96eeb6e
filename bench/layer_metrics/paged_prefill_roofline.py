"""Kernels: the paged prefill-attention kernel's share of its roofline
(%), as ``paged_verify_roofline`` with ``work.paged_prefill``: a chunk's
queries against its live prefix, causal."""
from bench import work
from bench.layer_metrics._common import kernel_events


def read(obs):
    chunks = obs.traced_chunks()
    if obs.peak is None or not chunks:
        return None
    ev = kernel_events(obs, "paged_prefill", obs.dm.layers, len(chunks))
    if ev is None:
        return None
    least = sum(obs.peak.least_time(work.paged_prefill(obs.dm, c.prefix,
                                                       c.size))
                for c in chunks) * obs.dm.layers
    return 100.0 * work.share(least, sum(e.dur for e in ev))
