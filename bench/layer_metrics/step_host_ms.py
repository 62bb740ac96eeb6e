"""Scheduler: median over the traced decode steps (the program's
``batcher.step`` spans) of the time inside the step in which the chip
ran nothing: the host's share of a step (ms)."""
from bench.layer_metrics._spans import median_idle_ms


def read(obs):
    return median_idle_ms(obs, "batcher.step")
