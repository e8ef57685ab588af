#!/usr/bin/env python3
"""One cell, one run:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration, traffic and metric readers by the names in
``BENCHMARK.json``, hands the run to the workload's ``entry``
(``benchmark/entries/<entry>.py``), and prints one JSON object as the last
line of stdout: with ``--trace 0`` the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.  Exit code non-zero, and no result
line, without the chips the cell asks for.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, memory  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rig: harness.TestRig = harness.NO_RIG) -> int:
    """``rig`` is for the benchmark's own tests and its control (a tiny
    size, the control's precision, a planted fault): ``main`` passes none,
    and nothing in a cell's files stands in for one."""
    cell = rig.resized(harness.load_cell(workload))
    entry = importlib.import_module(
        "benchmark.entries." + cell["workload"]["entry"])
    r = entry.run(cell, seed, seconds, trace, rig)
    device = dict(r["device"])
    peak, peak_source = memory.peak_bytes(r["memory"])
    device["memory_peak_bytes"] = peak
    print(f"memory_peak_bytes {peak} from {peak_source}", flush=True)
    compared = r["compared"]
    correct = all(c["ok"] for c in compared)
    breakdown = None
    if trace:
        if not r["trace"]:
            raise harness.BenchError("the traced run produced no trace")
        device["busy_s"] = r["trace"]["busy_s"]
        device["window_s"] = r["trace"]["window_s"]
        breakdown = r["trace"]["breakdown"]
        metrics = harness.read_per_layer(cell, r["observed"])
    else:
        metrics = {m["name"]: harness.metric_entry(r["e2e"][m["name"]],
                                                   m["unit"])
                   for m in cell["end_to_end"]}
    harness.emit(correct, r["attempted"], r["failed"], metrics, device,
                 compared, extra=r.get("extra"), breakdown=breakdown)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import raft_stereo_tpu  # noqa: F401 — the system under test
    except ImportError as e:
        sys.stderr.write(f"benchmark: the program is not in this "
                         f"directory ({e})\n")
        return 2
    try:
        return run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except harness.BenchError as e:
        sys.stderr.write(f"benchmark: {e}\n")
        return 3


if __name__ == "__main__":
    sys.exit(main())
