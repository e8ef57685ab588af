"""What runs once the window has closed and the timed program has gone: the
reduction of the trace, then the plain reference over the sampled answers.

    python -m benchmark.post <post.json>

Holds the chip while it runs (the reference is computed there, one pair at
a time, so that it fits beside nothing).
"""

from __future__ import annotations

import glob
import json
import os
import sys
import time

import numpy as np


def write_request(path: str, cell: dict, seed: int, answers, answer_is: str,
                  trace_dir, result_path: str, trace_window_s=None,
                  require_accelerator: bool = True) -> None:
    """What this process needs, as the entries hand it over.  ``answers``:
    ``[request id, pool pair, file of the served answer]``; ``answer_is``:
    ``"disparity"`` (the wire) or ``"flow"`` (the runner), its negative.
    ``require_accelerator`` is false only where a ``harness.TestRig`` says
    so."""
    wl = cell["workload"]
    with open(path, "w") as f:
        json.dump({"chips": cell["chips"], "seed": seed,
                   "require_accelerator": require_accelerator,
                   "config": cell["config"], "iters": wl["iters"],
                   "pool_pairs": wl["traffic"]["pool_pairs"],
                   "image_hw": list(wl["traffic"]["image_hw"]),
                   "answers": answers, "answer_is": answer_is,
                   "trace_dir": trace_dir, "trace_window_s": trace_window_s,
                   "scopes": wl["trace"]["scopes"],
                   "kernels": wl["trace"]["kernels"],
                   "limits": wl["compare"]["limits"],
                   "unit": wl["compare"].get("unit"),
                   "result_path": result_path}, f)


def main(argv) -> int:
    with open(argv[0]) as f:
        p = json.load(f)
    from benchmark import (compare, control, harness, reference, scenes,
                           weights)

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
    # the weights are an argument, not a constant of the program: one
    # compiled reference serves every seed from the compile cache
    fwd = jax.jit(lambda w, l, r: reference.disparity(model, w, l, r,
                                                      p["iters"]))
    # a bfloat16 cell's unit: the same reference with every product's inputs
    # rounded to the precision the configuration states (compare.py)
    unit = p.get("unit")
    fwd_unit = unit and jax.jit(lambda w, l, r: reference.disparity(
        model, dict(w, __lower__=control.LOWER[unit["precision"]]), l, r,
        p["iters"]))
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
