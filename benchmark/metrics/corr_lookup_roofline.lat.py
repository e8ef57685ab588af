"""The pyramid lookup kernel's share of its roofline: least time by the
table's peaks for the reads, writes and interpolations the algorithm needs
(``flops.lookup_work``; memory-bound on the v5e) over the kernel's device
time in the trace."""
from benchmark import flops
from benchmark.layer_metrics import kernel_roofline_pct


def read(observed):
    return kernel_roofline_pct(observed, "corr_lookup", flops.lookup_work)
