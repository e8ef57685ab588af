"""``limits_on_chip.py`` for a cell of entry ``bulk_runner_staged``, whose
pairs the plain reference holds only as several programs
(``reference_staged.py``): the readings the cell's limit is set from, on the
chip, at the cell's own size, many seeds in one process.

    python3 benchmark/tests/limits_on_chip_staged.py <workload> --seeds 1,2,3 \
        [--control_seeds 2] [--bf16_corr_seeds 1] [--out chiprun_out/limits.jsonl]

Per seed: the program's own first call of the window (``run_batch`` of the
cell's ``pairs_per_call`` pairs) and, for its last row, the plain reference
in float32 at ``highest`` and with every product's inputs rounded to the
unit's precision; for the first ``--control_seeds`` seeds also with them
rounded to int8 (the control, in the program's place), and for the first
``--bf16_corr_seeds`` the program again with ``corr_fp32`` off and the
runner's own rule at >= 16 iterations overridden (what the limit does not
have to guard, read all the same).  All go through
``compare.answer_numbers`` and ``compare.decide`` with the cell's own
limits, as a run's answers do.  One JSON line a seed.  Exit code 1 where a
seed's program comes out not correct or its control correct.

``--tiny`` runs the same on this machine's default backend at a 60x100
size (a rehearsal: its numbers are no device's).
"""

import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control_seeds", type=int, default=0)
    ap.add_argument("--bf16_corr_seeds", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import control, harness

    cell = harness.load_cell(args.workload)
    rig = harness.NO_RIG
    if args.tiny:
        rig = harness.TestRig(
            sizes={"iters": 2, "traffic": {"image_hw": [60, 100]}},
            require_accelerator=False)
        cell = rig.resized(cell)
    harness.use_cache_in_process()
    os.environ.update(cell["config"].get("env", {}))
    harness.require_chips(cell["chips"], rig.require_accelerator)
    import jax
    import numpy as np

    from benchmark import (compare, reference_staged, scenes, traffic,
                           weights)
    from benchmark.tests.limits_on_chip import readings
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.eval.runner import InferenceRunner

    model, wl = cell["config"]["model"], cell["workload"]
    tr = wl["traffic"]
    hw, b, iters = tuple(tr["image_hw"]), tr["pairs_per_call"], wl["iters"]
    cfg = RaftStereoConfig.from_dict(model)
    tail, limits = wl["compare"]["unit"], wl["compare"]["limits"]
    refs = {name: reference_staged.make_disparity(
        model, iters, control.LOWER[name] if name != "f32" else None)
        for name in ("f32", tail["precision"], "int8")}
    names = list(limits) + ["p99_gap_units", "p99_gap_px", "mean_gap_px",
                            "unit_p99_gap_px"]
    bad = 0
    out = open(args.out, "a") if args.out else None
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        w = weights.make_weights(model, seed)
        pool = scenes.make_pairs(seed, tr["pool_pairs"], hw)
        idx = traffic.pair_order(b, tr["pool_pairs"], seed)[:b]
        pair = pool[int(idx[b - 1])]

        def program(config, **runner_kw):
            runner = InferenceRunner(config, weights.nest(w), iters=iters,
                                     **runner_kw)
            flows, _ = runner.run_batch([pool[i][0] for i in idx],
                                        [pool[i][1] for i in idx])
            return np.asarray(flows[b - 1]).copy()

        got = {"program": program(cfg)}
        if k < args.bf16_corr_seeds:
            got["program_bf16_corr"] = program(
                dataclasses.replace(cfg, corr_fp32=False),
                corr_fp32_auto=False)
        t1 = time.monotonic()
        with jax.default_matmul_precision("highest"):
            want = np.asarray(refs["f32"](w, *pair))
            unit = np.asarray(refs[tail["precision"]](w, *pair))
            if k < args.control_seeds:
                got["control"] = np.asarray(refs["int8"](w, *pair))
        answer = {side: readings(np, compare, g, want, unit, tail)
                  for side, g in got.items()}
        verdicts = {side: compare.decide([nums], limits)
                    for side, nums in answer.items()}
        ok = {side: all(c["ok"] for c in v) for side, v in verdicts.items()}
        bad += (not ok["program"]) + bool(ok.get("control"))
        line = {"seed": seed, "pair": int(idx[b - 1]),
                "program_s": t1 - t0, "references_s": time.monotonic() - t1,
                "answer": answer, "verdicts": verdicts}
        for side, nums in answer.items():
            print(f"seed {seed} {side}: " + " ".join(
                f"{n} {nums[n]:.4g}" for n in names)
                + f" correct {str(ok[side]).lower()}", flush=True)
        print(f"seed {seed}: programs {line['program_s']:.0f}s, references "
              f"{line['references_s']:.0f}s", flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
