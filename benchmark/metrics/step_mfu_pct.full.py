"""The whole step's share of the chip's bf16 peak in the full-resolution
cell: model FLOPs counted from the shapes (``benchmark/flops.py``, 62.24
TFLOP a 1984x2880 pair at 32 iterations) of the pairs completed in the
window, over the window."""
from benchmark.layer_metrics import step_mfu_pct


def read(observed):
    return step_mfu_pct(observed)
