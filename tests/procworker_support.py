"""Spawn-safe dataset helpers for the process-worker loader tests.

Process loader workers unpickle the dataset by importing its defining
module; classes defined inside a test function (or a pytest module not
on the child's import path) cannot cross the spawn boundary, so the
killing dataset lives here (the tests dir is on sys.path — conftest.py —
and spawn children inherit the parent's sys.path).
"""

import os
import signal

import numpy as np


class KillOnceDataset:
    """8 deterministic samples; the FIRST decode of ``kill_index``
    SIGKILLs the decoding process (the OOM-killed worker) after fsyncing
    a marker file, so the respawned worker's retry decodes normally."""

    def __init__(self, marker: str, kill_index: int = 5):
        self.marker = marker
        self.kill_index = kill_index

    def __len__(self):
        return 8

    def __getitem__(self, i, epoch=0):
        if i == self.kill_index and not os.path.exists(self.marker):
            with open(self.marker, "w") as f:
                f.write("killed\n")
                f.flush()
                os.fsync(f.fileno())
            os.kill(os.getpid(), signal.SIGKILL)
        return {"x": np.full((2, 2), float(i) + 100.0 * epoch)}


class BackendProbeDataset:
    """Every sample reports its process id and whether THAT process has
    initialised a jax backend — a chip belongs to one process at a time,
    so a loader worker spawned by the process that trains on it must
    never reach for a device."""

    def __len__(self):
        return 8

    def __getitem__(self, i, epoch=0):
        import sys

        # No public query exists; a worker that never imported the module
        # cannot have initialised anything.
        bridge = sys.modules.get("jax._src.xla_bridge")
        up = bool(bridge is not None and bridge.backends_are_initialized())
        return {"pid": np.array(os.getpid()), "backend_up": np.array(up)}
