"""The control at a cell's own size, on the chip, on three seeds or more:

    python3 benchmark/tests/control_on_chip.py <workload> [--seeds 3] [--seconds 12]

For a configuration that states float32 the program runs with its own
bfloat16 path switched on through the whole harness (a short window at the
cell's own load) and ``correct`` has to come out false.  For one that states
bfloat16 the plain reference stands in the program's place with int8
products, on the cell's own pairs, beside the program's own call of the
window on the same seeds (``limits_on_chip.py``, which prints both readings
and puts both through ``compare.decide`` with the cell's own limits, as a
run's answers go).  Exit code 1 where a seed's control comes out correct.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--pairs", type=int, default=2)
    args = ap.parse_args(argv)
    from benchmark import control, harness, run

    cell = harness.load_cell(args.workload)
    seeds = [2147480000 + 7919 * k for k in range(args.seeds)]
    if not cell["config"]["model"]["mixed_precision"]:
        for seed in seeds:
            print(f"==== control: the program's bfloat16 path, seed {seed}",
                  flush=True)
            run.run_cell(args.workload, seed, args.seconds, False,
                         rig=control.program_control_rig(cell["config"]))
        return 0
    from benchmark.tests import limits_on_chip

    return limits_on_chip.main(
        [args.workload, "--seeds", ",".join(str(s) for s in seeds),
         "--answers", str(args.pairs), "--control_answers", str(args.pairs)])

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
