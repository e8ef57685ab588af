"""The gradient all-reduce's share of the device's busy time in a training
cell that spans chips: device seconds of the step's all-reduce operations
(the workload's ``trace.kernels.allreduce`` pattern) over busy seconds, both
the mean of the device planes ``trace_reduce.reduce_profile`` finds.  An
all-reduce that runs under other operations counts whole: the share says
what the collective costs the chips, not what of it is exposed.  A step on
one chip has no such operation and reads nothing."""


def read(observed):
    tr = observed.get("trace")
    k = tr and tr["kernels"].get("allreduce")
    if not k or k["seconds"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * k["seconds"] / tr["busy_s"]
