"""Host time of one dispatch after its outputs are ready: mean per dispatch
over the window of ``serve_phase_seconds`` for the phases ``fetch``,
``account`` and ``respond`` (``/metrics``), in the cell judged on
throughput."""
from benchmark.host_spans import phase_ms_per_dispatch


def read(observed):
    return phase_ms_per_dispatch(observed, ("fetch", "account", "respond"))
