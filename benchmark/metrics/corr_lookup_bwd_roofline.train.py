"""The lookup kernel's BACKWARD launches' share of their roofline in the
training cell: least time by the table's peaks for the backward of the
lookups the trace holds (``flops_train.lookup_bwd_work``: the taps'
cotangent read, the touched volume entries written) over those launches'
device time.  The lookups are counted from the trace alone: the elements
the launches wrote as the first of their results (the finest level's
cotangent: the all-levels launch the step compiles to) over that level's
elements for one pair.  A program whose backward falls to one launch a
level writes no tuple and reads nothing here."""
from benchmark import flops, flops_train, harness


def read(observed):
    tr = observed.get("trace")
    k = tr and tr["kernels"].get("corr_lookup_bwd")
    if not k or not k.get("out_elements") or k["seconds"] <= 0:
        return None
    cell = observed["cell"]
    model = cell["config"]["model"]
    h, w = cell["workload"]["traffic"]["image_hw"]
    lookups = k["out_elements"] / flops_train.lookup_bwd_level0_elements(
        model, h, w)
    itemsize = 2 if model["mixed_precision"] and not model["corr_fp32"] else 4
    work = flops_train.lookup_bwd_work(model, h, w, itemsize)
    least, _bound = flops.least_seconds(
        {key: v * lookups for key, v in work.items()},
        harness.peaks_for(observed["device_kind"]))
    return 100.0 * least / k["seconds"]
