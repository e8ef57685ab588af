"""Faults planted under the timed path, for ``test_faults.py``: each has to
make ``correct`` come out false.  Nothing outside the tests imports this."""

SHIFT_PX = 4.0


def alter_served_answer() -> None:
    """``child_patch`` of a serving cell: the upper half of every answer
    leaves the server a few pixels off (an answer altered where it is
    produced: after the device, before the wire)."""
    from raft_stereo_tpu.serving import http

    sound = http._encode_disparity

    def broken(disp, fmt, confidence=None):
        disp = disp.copy()
        disp[: disp.shape[0] // 2] += SHIFT_PX
        return sound(disp, fmt, confidence=confidence)

    http._encode_disparity = broken


def roll_batch_rows(monkeypatch) -> None:
    """Bulk cell: every pair of a call gets its neighbour's answer (a wrong
    un-batching)."""
    import numpy as np

    from raft_stereo_tpu.eval.runner import InferenceRunner

    sound = InferenceRunner.run_batch

    def broken(self, images1, images2):
        flows, seconds = sound(self, images1, images2)
        return np.roll(flows, 1, axis=0), seconds

    monkeypatch.setattr(InferenceRunner, "run_batch", broken)


def shift_batch_rows(monkeypatch) -> None:
    """Bulk cell: the answers come back a few pixels off in their upper
    half."""
    from raft_stereo_tpu.eval.runner import InferenceRunner

    sound = InferenceRunner.run_batch

    def broken(self, images1, images2):
        flows, seconds = sound(self, images1, images2)
        flows = flows.copy()
        flows[:, : flows.shape[1] // 2] += SHIFT_PX
        return flows, seconds

    monkeypatch.setattr(InferenceRunner, "run_batch", broken)
