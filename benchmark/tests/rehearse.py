"""Each cell end to end on the CPU at a tiny size (first rehearsal of the
on-chip-measurement guide):

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse.py [workload ...] [--trace]

Sizes come from ``tiny_overrides.json``, a file only this script and the
tests read, as the fields of a ``harness.TestRig``; the harness's command
line has no way to name it, and no cell's file can hold what it holds.  The
numbers it prints are no device's.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    from benchmark import harness, run

    with open(os.path.join(HERE, "tiny_overrides.json")) as f:
        tiny = json.load(f)
    trace = "--trace" in argv
    names = [a for a in argv if not a.startswith("--")] or sorted(tiny)
    for name in names:
        print(f"==== {name} (tiny, this machine's default backend)",
              flush=True)
        run.run_cell(name, seed=2147483653, seconds=4.0, trace=trace,
                     rig=harness.TestRig(**tiny[name]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
