"""Entry ``bulk_runner_staged``: ``bulk_runner`` as it stands — the same
caller, window, traced calls and result — for pairs so large that the plain
reference has to run as several programs.  ``bulk_runner.run`` ends in
``post.main``; for the length of a run this entry puts
``post_staged.main`` there, which differs from it in the reference's
programs alone (``reference_staged.py``: 27.97 GB in one program at
1984x2880, against the chip's 15.75).
"""

from __future__ import annotations

from unittest import mock

from benchmark import harness, post, post_staged
from benchmark.entries import bulk_runner


def run(cell: dict, seed: int, seconds: float, trace: bool,
        rig: harness.TestRig = harness.NO_RIG) -> dict:
    with mock.patch.object(post, "main", post_staged.main):
        return bulk_runner.run(cell, seed, seconds, trace, rig)
