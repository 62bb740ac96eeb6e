"""Model step: median device time of one full-length prefill chunk
(``kvcache._prefill_chunk_jit``), from the trace (ms)."""
from bench.layer_metrics._common import chunk_runs, median_ms


def read(obs):
    pairs = chunk_runs(obs)
    if pairs is None:
        return None
    return median_ms([ev for rec, ev in pairs
                      if rec.size == obs.prefill_chunk])
