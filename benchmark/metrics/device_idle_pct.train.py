"""The share of the traced steps in which no operation ran on a device
(the mean over the cell's device planes), in the training cell: what the
host loop, the loader and the uploads leave the chips waiting for."""
from benchmark.layer_metrics import device_idle_pct


def read(observed):
    return device_idle_pct(observed)
