"""``post.py`` for a cell whose pairs ``reference.disparity`` cannot hold in
one program (``reference_staged.py`` says why): the same request file, the
same reduction of the trace, the same comparison and result file, with the
plain reference run as ``reference_staged.make_disparity`` runs it.
``entries/bulk_runner_staged.py`` puts this ``main`` in ``post.main``'s
place for its cells.

A ``benchmark`` PR that lets ``post.py`` take the reference by the cell's
size dissolves this file (PERF.md section 7).
"""

from __future__ import annotations

import glob
import json
import os
import sys
import time

import numpy as np


def main(argv) -> int:
    with open(argv[0]) as f:
        p = json.load(f)
    from benchmark import (compare, control, harness, reference_staged,
                           scenes, weights)

    harness.use_cache_in_process()
    out = {"trace": None}
    if p.get("trace_dir"):
        from benchmark import trace_reduce

        files = glob.glob(os.path.join(p["trace_dir"], "**", "*.xplane.pb"),
                          recursive=True)
        if files:
            out["trace"] = trace_reduce.reduce_file(
                max(files, key=os.path.getmtime), p["scopes"], p["kernels"],
                p.get("trace_window_s"),
                host_stand_in=not p["require_accelerator"])

    harness.require_chips(p["chips"], p["require_accelerator"])
    import jax

    model = p["config"]["model"]
    t0 = time.monotonic()
    w = weights.make_weights(model, p["seed"])
    pool = scenes.make_pairs(p["seed"], p["pool_pairs"], tuple(p["image_hw"]))
    fwd = reference_staged.make_disparity(model, p["iters"])
    # a bfloat16 cell's unit: the same reference with every product's inputs
    # rounded to the precision the configuration states (compare.py)
    unit = p.get("unit")
    fwd_unit = unit and reference_staged.make_disparity(
        model, p["iters"], control.LOWER[unit["precision"]])
    sign = -1.0 if p["answer_is"] == "disparity" else 1.0
    per_answer = []
    with jax.default_matmul_precision("highest"):
        for req, pair, path in p["answers"]:
            got = np.load(path)
            want = sign * np.asarray(fwd(w, *pool[pair]))
            nums = compare.answer_numbers(
                got, want, unit and sign * np.asarray(fwd_unit(w, *pool[pair])),
                unit)
            per_answer.append(nums)
            print(f"answer {req} (pair {pair}): " + " ".join(
                f"{k} {v:.4g}" for k, v in nums.items()), flush=True)
    out["compared"] = compare.decide(per_answer, p["limits"])
    out["compared"].append({"name": "answers_compared",
                            "value": len(per_answer), "limit": 1,
                            "ok": len(per_answer) >= 1})
    out["per_answer"] = per_answer
    out["reference_s"] = time.monotonic() - t0
    print(f"reference: {len(per_answer)} answers in "
          f"{out['reference_s']:.1f}s", flush=True)
    with open(p["result_path"], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
