"""Device: share of the traced window in which no operation ran on the chip
(1 - union of the device's operation intervals over the window)."""
from benchmark.layer_metrics import _programs


def read(facts, trace):
    return _programs.idle_share(trace)
