"""The readings a bfloat16 cell's limits are set from, on the chip, at the
cell's own size, many seeds in one process:

    python3 benchmark/tests/limits_on_chip.py <workload> --seeds 1,2,3 \
        [--answers 4] [--control_answers 2] [--out chiprun_out/limits.jsonl]

Per seed: the program's own call of the window (``run_batch`` of the cell's
``pairs_per_call`` pairs, drawn as the window's first call draws them), and
for sampled rows of it the plain reference three times over: float32 at
``highest`` (what ``correct`` compares with), with every product's inputs
rounded to the configuration's stated bfloat16 (the unit the gaps are
counted in, ``compare.py``), and with them rounded to int8 (the control, in
the program's place).  Both go through ``compare.answer_numbers`` and
``compare.decide`` with the cell's own limits, as a run's answers do.  One
JSON line a seed, with each answer's numbers and the histogram of its gaps
in the unit, so that a limit can be set between the two readings.  Exit
code 1 where a seed's program comes out not correct or its control correct.

``--tiny`` runs the same on this machine's default backend at the tests'
size (a rehearsal: its numbers are no device's).
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

BINS = 2000        # of 0.01 unit-p99 each: any tail can be read later


def readings(np, compare, got, want, unit, tail):
    """The cell's own numbers for one answer, and the histogram of its gaps
    in units of the unit's p99 gap."""
    nums = compare.answer_numbers(got, want, unit, tail)
    gap = np.abs(got.astype(np.float64) - want) / nums["unit_p99_gap_px"]
    nums["hist_gap_over_unit_p99"] = np.histogram(
        gap, BINS, (0.0, BINS / 100.0))[0].tolist()
    return nums


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--answers", type=int, default=4)
    ap.add_argument("--control_answers", type=int, default=2)
    ap.add_argument("--out", default=None)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import control, harness

    cell = harness.load_cell(args.workload)
    rig = harness.NO_RIG
    if args.tiny:
        with open(os.path.join(HERE, "tiny_overrides.json")) as f:
            rig = harness.TestRig(**json.load(f)[args.workload])
        cell = rig.resized(cell)
    harness.use_cache_in_process()
    os.environ.update(cell["config"].get("env", {}))
    harness.require_chips(cell["chips"], rig.require_accelerator)
    import time

    import jax
    import numpy as np

    from benchmark import compare, reference, scenes, traffic, weights
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.eval.runner import InferenceRunner

    model, wl = cell["config"]["model"], cell["workload"]
    tr = wl["traffic"]
    hw, b, iters = tuple(tr["image_hw"]), tr["pairs_per_call"], wl["iters"]
    cfg = RaftStereoConfig.from_dict(model)

    def ref_fn(lower):
        def f(w, l, r):
            table = dict(w, __lower__=control.LOWER[lower]) if lower else w
            return reference.disparity(model, table, l, r, iters)
        return jax.jit(f)

    tail, limits = wl["compare"]["unit"], wl["compare"]["limits"]
    refs = {"f32": ref_fn(None), "int8": ref_fn("int8"),
            tail["precision"]: ref_fn(tail["precision"])}
    bad = 0
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        w = weights.make_weights(model, seed)
        runner = InferenceRunner(cfg, weights.nest(w), iters=iters)
        pool = scenes.make_pairs(seed, tr["pool_pairs"], hw)
        idx = traffic.pair_order(b, tr["pool_pairs"], seed)[:b]
        flows, _ = runner.run_batch([pool[i][0] for i in idx],
                                    [pool[i][1] for i in idx])
        rng = np.random.default_rng([seed, 0xC0DE])
        rows = [b - 1] + [int(r) for r in rng.choice(
            b - 1, min(args.answers, b) - 1, replace=False)]
        got = {r: np.asarray(flows[r]).copy() for r in rows}
        del runner, flows
        t1 = time.monotonic()
        answers, verdicts = [], {}
        with jax.default_matmul_precision("highest"):
            for k, r in enumerate(rows):
                pair = pool[int(idx[r])]
                want = np.asarray(refs["f32"](w, *pair))
                unit = np.asarray(refs[tail["precision"]](w, *pair))
                a = {"row": r, "pair": int(idx[r]),
                     "program": readings(np, compare, got[r], want, unit,
                                         tail)}
                if k < args.control_answers:
                    a["control"] = readings(
                        np, compare, np.asarray(refs["int8"](w, *pair)),
                        want, unit, tail)
                answers.append(a)
        for side in ("program", "control"):
            verdicts[side] = compare.decide(
                [a[side] for a in answers if side in a], limits)
        sound = all(c["ok"] for c in verdicts["program"])
        failed = not all(c["ok"] for c in verdicts["control"])
        bad += (not sound) + (not failed)
        line = {"seed": seed, "program_s": t1 - t0,
                "references_s": time.monotonic() - t1, "answers": answers,
                "verdicts": verdicts}
        names = list(limits) + ["p99_gap_units", "p99_gap_px",
                                "unit_p99_gap_px"]
        for side in ("program", "control"):
            print(f"seed {seed} {side}: " + " | ".join(
                " ".join(f"{n} {a[side][n]:.4g}" for n in names)
                for a in answers if side in a), flush=True)
        print(f"seed {seed} ({line['program_s']:.0f}s + "
              f"{line['references_s']:.0f}s): program correct "
              f"{str(sound).lower()}, control correct "
              f"{str(not failed).lower()}", flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
