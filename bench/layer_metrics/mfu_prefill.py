"""Whole step: model operations of the traced prefill chunks
(``work.prefill_chunk``) over their device time, over the chip's bf16
peak (%)."""
from bench import work
from bench.layer_metrics._common import chunk_runs


def read(obs):
    pairs = chunk_runs(obs)
    if obs.peak is None or pairs is None:
        return None
    flops = sum(work.prefill_chunk(obs.dm, c.prefix, c.size).flops
                for c, _ in pairs)
    t = sum(e.dur for _, e in pairs)
    return 100.0 * work.share(flops / obs.peak.flops, t)
