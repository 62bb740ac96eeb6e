"""Device: the share of the time the server had work (a slot decoding or
an admit running, on the host's clock) in which no operation ran on the
chip, over the traced window (%)."""
from bench import trace_reduce as tr
from bench.layer_metrics._common import device


def read(obs):
    dev = device(obs)
    work_s = obs.traced_work_s
    if dev is None or work_s <= 0:
        return None
    return 100.0 * (1.0 - tr.total(dev.busy()) / work_s)
