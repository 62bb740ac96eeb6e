"""Scheduler: 90th percentile of the time a due request waited for a
slot, from its due instant to the start of its admit; an unanswered
request ranks above every answered one."""
from bench import accounting as acc


def read(obs):
    return acc.quantile(obs.waits(obs.admit_start), 0.90)
