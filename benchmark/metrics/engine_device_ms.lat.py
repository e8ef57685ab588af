"""Mean device time of one dispatch as the engine clocks it
(``/metrics`` ``serve_device_seconds``)."""
from benchmark.layer_metrics import histogram_mean_ms


def read(observed):
    return histogram_mean_ms(observed, "serve_device_seconds")
