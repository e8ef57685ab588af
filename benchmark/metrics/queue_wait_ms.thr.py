"""Mean wait in the batcher's queue over the window (``/metrics``
``serve_queue_wait_seconds``), in the cell judged on throughput."""
from benchmark.layer_metrics import histogram_mean_ms


def read(observed):
    return histogram_mean_ms(observed, "serve_queue_wait_seconds")
