"""Scheduler: mean share of the decode slots that held a request, over
the decode steps of the run (%)."""


def read(obs):
    if not obs.steps:
        return None
    used = sum(len(s.lengths) for s in obs.steps)
    return 100.0 * used / (len(obs.steps) * obs.slots)
