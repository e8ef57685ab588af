"""The no-volume lookup kernel's share of its roofline in the
full-resolution cell, where the lookup is one launch a level in float32:
least time by the table's peaks for the per-tap products and the feature
reads the algorithm needs (``flops.alt_lookup_work``, which counts the left
features read once a lookup, not once a launch) over the kernel's device
time in the trace.  The lookups are counted from the elements the launches
wrote, so the number of launches a lookup does not enter."""
from benchmark import flops
from benchmark.layer_metrics import kernel_roofline_pct


def read(observed):
    return kernel_roofline_pct(observed, "corr_alt", flops.alt_lookup_work)
