"""The whole step's share of the chip's bf16 peak: model FLOPs counted
from the shapes (``benchmark/flops.py``) of the pairs completed in the
window, over the window."""
from benchmark.layer_metrics import step_mfu_pct


def read(observed):
    return step_mfu_pct(observed)
