"""95th percentile of the latency from the due instant over all requests
of a window offered above capacity (the generator's own sample): grows
with the window, so it is no end-to-end metric there."""
from benchmark.layer_metrics import latency_percentile_ms


def read(observed):
    return latency_percentile_ms(observed, 95)
