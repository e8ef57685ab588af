"""IGEV-Stereo's 3-D stage's share of the call's device time: device
seconds of the operations under the ``gev`` scope (the group-wise volume,
the 3-D convolutions and excitations, the regression, the two pyramids)
over the seconds of all the traced operations
(``benchmark/layer_scopes.py``).  Where ``gev_share_pct.igev`` finds the
stage by the rank of its results, this joins every operation to its scope
through the HLO the trace carries.  Reads nothing without that HLO."""
from benchmark.layer_scopes import share_pct


def read(observed):
    return share_pct(observed, layers=("gev",))
