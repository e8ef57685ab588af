"""The optimizer's share of the training step's device time: device
seconds of the operations under the step's ``optimizer`` scope (the
gradient norm, the clip, the schedule and the AdamW update of every
parameter, which a data-parallel step runs whole on every chip) over the
seconds of all the traced operations (``benchmark/layer_scopes.py``).  A
program without the scope reads nothing, never 0."""
from benchmark.layer_scopes import share_pct


def read(observed):
    return share_pct(observed, layers=("optimizer",))
