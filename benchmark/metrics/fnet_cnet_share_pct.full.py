"""Both encoders' share of the full-resolution call's device time: device
seconds of the operations under the ``fnet`` and ``cnet`` scopes (the
sequential feature encoder's scan and the context encoder with its
context-bias convolutions) over the seconds of all the traced operations
(``benchmark/layer_scopes.py``).  Where ``encoder_share_pct.full`` reads
the fnet scan's loops only, this joins every operation to its scope
through the HLO the trace carries.  Reads nothing without that HLO."""
from benchmark.layer_scopes import share_pct


def read(observed):
    return share_pct(observed, layers=("fnet", "cnet"))
