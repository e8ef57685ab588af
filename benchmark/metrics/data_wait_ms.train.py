"""Mean time a step's ``next(batches)`` kept the training loop waiting over
the window: ``train_phase_seconds{phase="data_wait"}`` (the ``train.
data_wait`` span, ``telemetry/spans.py`` ``Phases``) sum over count.  A
loader that keeps up reads microseconds; a loader-bound job reads the
step's shortfall.  A program without that span reads nothing."""
from benchmark import prom

LABEL = 'phase="data_wait"'


def read(observed):
    counters = observed.get("counters") or {}
    count = prom.total(counters, "train_phase_seconds_count", LABEL)
    if count <= 0:
        return None
    return 1e3 * prom.total(counters, "train_phase_seconds_sum",
                            LABEL) / count
