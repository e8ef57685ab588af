"""Host time of one dispatch before its launch: mean per dispatch over the
window of ``serve_phase_seconds`` for the phases ``assemble`` and ``upload``
(``/metrics``), in the cell judged on throughput."""
from benchmark.host_spans import phase_ms_per_dispatch


def read(observed):
    return phase_ms_per_dispatch(observed, ("assemble", "upload"))
