"""The encoders' loop over device busy time, from the trace: device time of
the program's loops (the ``loops`` scope of the workload's ``trace.scopes``:
every ``%while``) less the refinement loop's (``gru_iter``).  At full
resolution the feature encoder is a scan over the two images of a pair
(``models/raft_stereo.py``, the sequential fnet), the program's only other
loop of any length, and it is the full-resolution stem that this
configuration adds beside the refinement loop.

The TPU's trace names an operation after its HLO instruction and carries no
scope path, so the context encoder's operations, which run outside any
loop, cannot be told from their neighbours: they are NOT in this number
(0.16 of the encoders' 0.93 s in a call of two pairs, my chip run, PR 28).
A program whose fnet runs batched has no such loop and reads nothing."""


def read(observed):
    tr = observed.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    loops = tr["scopes"].get("loops") or 0.0
    seconds = loops - (tr["scopes"].get("gru_iter") or 0.0)
    if seconds <= 0:
        return None
    return 100.0 * seconds / tr["busy_s"]
