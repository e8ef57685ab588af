"""Share of the traced stretch that the worker's spans cover
(``host_spans``) in which the device was idle while the worker
was in the engine's own phases (``serve.assemble`` / ``upload`` / ``fetch`` /
``account`` / ``respond``)."""
from benchmark.host_spans import ENGINE_SPANS, idle_share_pct


def read(observed):
    return idle_share_pct(observed, ENGINE_SPANS)
