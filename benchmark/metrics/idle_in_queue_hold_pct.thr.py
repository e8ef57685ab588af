"""Share of the traced stretch that the worker's spans cover
(``host_spans``) in which the device was idle while the worker
waited for work (``serve.wait_work``: an empty queue, or requests the batcher
held back), in the cell judged on throughput."""
from benchmark.host_spans import QUEUE_HOLD_SPANS, idle_share_pct


def read(observed):
    return idle_share_pct(observed, QUEUE_HOLD_SPANS)
