"""The no-volume lookup kernel's share of its roofline: least time by
the table's peaks for the per-tap products and the feature reads the
algorithm needs (``flops.alt_lookup_work``) over the kernel's device time
in the trace."""
from benchmark import flops
from benchmark.layer_metrics import kernel_roofline_pct


def read(observed):
    return kernel_roofline_pct(observed, "corr_alt", flops.alt_lookup_work)
