"""Device time under the ``gru_iter`` scope over device busy time, from
the trace, in the full-resolution cell."""
from benchmark.layer_metrics import scope_share_pct


def read(observed):
    return scope_share_pct(observed, "gru_iter")
