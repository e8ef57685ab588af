"""The recomputation's share of the training step's device time: device
seconds of the operations jax staged under ``rematted_computation`` (the
refinement loop's forward, run again in the backward because the step
remats each iteration, ``remat_gru``) over the seconds of all the traced
operations (``benchmark/layer_scopes.py``).  Reads nothing where the trace
has no HLO or the step recomputes nothing."""
from benchmark.layer_scopes import share_pct


def read(observed):
    return share_pct(observed, passes=("recompute",))
