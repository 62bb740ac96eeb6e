"""Whole step: model operations of the traced decode steps (top-k
experts only, ``work.decode_step``) over their device time, over the
chip's bf16 peak (%)."""
from bench import work
from bench.layer_metrics._common import decode_runs


def read(obs):
    r = decode_runs(obs)
    if obs.peak is None or r is None:
        return None
    flops = sum(work.decode_step(obs.dm, s.lengths).flops
                for s in obs.traced_steps())
    t = sum(e.dur for e in r)
    return 100.0 * work.share(flops / obs.peak.flops, t)
