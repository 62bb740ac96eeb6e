"""Model step: median device time of one run of the decode-step program
(``kvcache._decode_paged_jit``), from the trace (ms)."""
from bench.layer_metrics._common import decode_runs, median_ms


def read(obs):
    return median_ms(decode_runs(obs))
