"""The backward pass's share of the training step's device time: device
seconds of the operations jax staged under ``transpose(`` (the gradient of
every layer, the loss's included; not the recomputation, which has a share
of its own) over the seconds of all the traced operations, both the mean of
the device planes (``benchmark/layer_scopes.py``: each operation joined to
its instruction in the HLO the trace carries, charged its self time).  A
trace without the HLO, or a step that joins to nothing, reads nothing."""
from benchmark.layer_scopes import share_pct


def read(observed):
    return share_pct(observed, passes=("backward",))
