"""The readings a training cell's limits are set from, on the chip, at the
cell's own size, many seeds in one process.

    python3 benchmark/tests/limits_on_chip_train.py <workload> --seeds 1,2,3 \
        [--control_seeds 2] [--out chiprun_out/limits.jsonl]

Per seed: the entry's own ``prepare`` and *call A* (``train()`` from the
seeded checkpoint, ``steps_compared`` steps, stopped by ``should_stop``;
the train step compiles once and the later seeds hit it), then the plain
reference's replay of the batches that loop trained on, and
``post_train.training_numbers`` of what the loop wrote against it, counted
also in the cell's unit (the reference with every product's inputs rounded
to the precision the configuration states).  For the first
``--control_seeds`` seeds the control — the reference with both inputs of
every product rounded to int8, straight-through — goes where the program's
artefacts go, and two faults are read where they show: the reference's own
loss of the first batch with its truth rolled by a sample, and with half
its truth, against its loss of the sound batch (``loss_gap_rel``'s upper
readings: a fault of the data path moves the loss, hardly the gradient),
and a gradient that never reaches the feature encoder (``grad_gap_rel_fnet``
is then 1, in units one over the unit's own gap).  The first seed also
clocks the reference's seconds a gradient, cold and warm.  One JSON line a
seed.  Exit code 1 where a seed's program comes out not correct or its
control correct by the cell's own limits.

``--tiny`` runs the same on this machine's default backend at a 64x96
crop (a rehearsal: its numbers are no device's).
"""

import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control_seeds", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import control, harness

    cell = harness.load_cell(args.workload)
    rig = harness.NO_RIG
    if args.tiny:
        rig = harness.TestRig(
            sizes={"iters": 3, "traffic": {
                "batch_size": 2 * cell["workload"]["traffic"]["data_parallel"],
                "image_hw": [64, 96], "pool_pairs": 8,
                "frame_hw": [80, 120]}},
            require_accelerator=False)
        cell = rig.resized(cell)
    harness.use_cache_in_process()
    os.environ.update(cell["config"].get("env", {}))
    harness.require_chips(cell["chips"], rig.require_accelerator)
    import jax

    from benchmark import compare, post_train, reference_train, weights
    from benchmark.entries import train_job

    model, wl = cell["config"]["model"], cell["workload"]
    limits, unit = wl["compare"]["limits"], wl["compare"]["unit"]
    lowered = {name: reference_train.straight_through(control.LOWER[name])
               for name in ("int8", unit["precision"])}
    bad = 0
    out = open(args.out, "a") if args.out else None
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        wd = harness.work_dir(cell["name"])
        job = train_job.prepare(cell, seed, wd, rig)
        a = train_job.call_a(cell, job, wd)
        t1 = time.monotonic()
        recipe = train_job.recipe_of(cell, seed)
        start = weights.make_weights(model, seed)
        batches = post_train.load_batches(a["batches"])
        if k == 0:          # the reference's seconds a gradient
            fn = reference_train.make_sample_grad(
                model, recipe, lowered[unit["precision"]])
            one = [jax.numpy.asarray(batches[0][name][:1]) for name in
                   ("image1", "image2", "flow", "valid")]
            clocks = []
            with jax.default_matmul_precision("highest"):
                for _ in range(3):
                    t = time.monotonic()
                    jax.block_until_ready(fn(start, *one, False))
                    clocks.append(time.monotonic() - t)
            print(f"reference: seconds a gradient, first (compile or cache "
                  f"read) {clocks[0]:.2f}, then {clocks[1]:.2f} "
                  f"{clocks[2]:.2f}; call A {a['seconds']:.1f}s", flush=True)

        def side(replayed):
            arrays, mu, _nu, steps = replayed
            return {"params": arrays, "mu": mu}, steps

        (want, want_steps), in_unit = map(side, reference_train.replay_pair(
            model, recipe, start, batches, lowered[unit["precision"]]))
        with open(a["steps"]) as f:
            sides = {"program": (post_train.load_state(a["state"]),
                                 json.load(f))}
        unit_nums = post_train.training_numbers(start, *in_unit, want,
                                                want_steps)
        faults = {}
        if k < args.control_seeds:
            sides["control"] = side(reference_train.replay(
                model, recipe, start, batches, lowered["int8"]))
            fn = reference_train.make_sample_grad(
                model, recipe, lowered[unit["precision"]])
            first = batches[0]
            with jax.default_matmul_precision("highest"):
                for name, truth in (
                        ("truth_rolled", {n: np.roll(first[n], 1, axis=0)
                                          for n in ("flow", "valid")}),
                        ("truth_halved", {"flow": 0.5 * first["flow"]})):
                    _, loss, _ = reference_train.batch_grad(
                        fn, start, dict(first, **truth), False)
                    faults[f"loss_gap_rel_{name}"] = abs(
                        float(loss) - want_steps[0]["loss"]
                    ) / want_steps[0]["loss"]
            faults["grad_gap_units_fnet_cut"] = (
                1.0 / unit_nums["grad_gap_rel_fnet"])
        numbers = {side: post_train.in_units(post_train.training_numbers(
            start, got, got_steps, want, want_steps), unit_nums)
            for side, (got, got_steps) in sides.items()}
        numbers["unit"] = unit_nums
        verdicts = {side: compare.decide([nums], limits)
                    for side, nums in numbers.items() if side != "unit"}
        ok = {side: all(c["ok"] for c in v) for side, v in verdicts.items()}
        bad += (not ok["program"]) + bool(ok.get("control"))
        line = {"seed": seed, "call_a_s": t1 - t0,
                "references_s": time.monotonic() - t1,
                "reference_steps": want_steps,
                "program_steps": sides["program"][1],
                "numbers": numbers, "verdicts": verdicts, "faults": faults}
        if faults:
            print(f"seed {seed} faults: " + " ".join(
                f"{n} {v:.4g}" for n, v in faults.items()), flush=True)
        for side, nums in numbers.items():
            print(f"seed {seed} {side}: " + " ".join(
                f"{n} {v:.4g}" for n, v in nums.items())
                + (f" correct {str(ok[side]).lower()}" if side in ok
                   else ""), flush=True)
        print(f"seed {seed}: prepare and call A {line['call_a_s']:.0f}s, "
              f"references {line['references_s']:.0f}s", flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
