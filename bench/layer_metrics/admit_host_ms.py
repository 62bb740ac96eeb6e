"""Scheduler: median over the traced admits (the program's
``batcher.admit`` spans) of the time inside the admit in which the chip
ran nothing, less that inside the decode steps (``batcher.step``)
interleaved between its prefill chunks: the host's share of an admit
(ms)."""
from bench.layer_metrics._spans import median_idle_ms


def read(obs):
    return median_idle_ms(obs, "batcher.admit", nested="batcher.step")
