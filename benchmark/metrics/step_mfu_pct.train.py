"""The whole training step's share of the chips' bf16 peak: model FLOPs of
the pairs TRAINED in the window (``flops_train.trained_pair_flops``: three
times the forward's, recomputation not counted; 5.58 TFLOP a 320x720 pair
at 22 iterations), over the window, over the peak of the chips the cell
holds."""
from benchmark import flops_train, harness


def read(observed):
    if not observed.get("pairs_completed"):
        return None
    cell = observed["cell"]
    h, w = cell["workload"]["traffic"]["image_hw"]
    per_pair = flops_train.trained_pair_flops(
        cell["config"]["model"], h, w, cell["workload"]["iters"])
    peak = harness.peaks_for(observed["device_kind"])["bf16_flops_per_s"]
    return (100.0 * per_pair * observed["pairs_completed"]
            / observed["seconds"] / (peak * cell["chips"]))
