"""1 - (union of the intervals in which an operation ran on the device)
over the traced window, in the full-resolution cell."""
from benchmark.layer_metrics import device_idle_pct


def read(observed):
    return device_idle_pct(observed)
