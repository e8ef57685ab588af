"""``post.py`` for a training cell: the reduction of the trace, then the
plain reference's replay (``reference_train.py``) of the batches call A's
loop trained on, from the same seeded weights, against what that loop
itself wrote.

    python -m benchmark.post_train <post.json>

What is compared, each number with a limit of its own (PERF.md section 2):

* ``loss_gap_rel``: the worst step's |loss - reference's| / |reference's|,
  the loss as the loop's logger wrote it;
* ``grad_gap_rel``: ||mu - mu_ref|| / ||mu_ref|| over every parameter, mu
  being Adam's first moment in the checkpoint the loop wrote.  After two
  steps mu = 0.1 x (0.9 g1 + g2), the clipped gradients themselves, which
  a checkpoint holds and a loss does not;
* ``grad_gap_rel_<module>``: the same over one module's parameters, so that
  a dead or wrong backward in one is not averaged away by the others;
* ``update_gap_rel``: the same on ``params_K - params_0``, the one number
  that says the optimizer moved the weights at all, by the schedule's rate
  and in Adam's direction: a state left unmoved reads 1.  Adam's first
  updates are ``lr x sign``-like, so a small gradient's rounding flips whole
  entries and sound runs read about a tenth; its limit stands between.

How far bfloat16's rounding carries into a gradient depends on the seed's
weights and scenes (``grad_gap_rel`` 0.0065 to 0.029 over ten seeds on the
chip, the int8 control 0.031 to 0.089), so, as the bulk cells do, a cell
that states bfloat16 counts in a unit that moves with the seed: the
reference's OWN replay with every product's inputs rounded to that
precision (``compare.unit``), straight-through.  ``grad_gap_units`` and
``grad_gap_units_<module>`` are the program's gaps over that replay's gaps.

Holds the chip while it runs; the reference takes one sample at a time a
chip (``reference_train.spread_over`` where the cell holds several).
"""

from __future__ import annotations

import glob
import json
import os
import sys
import time

import numpy as np

MODULES = ("fnet", "cnet", "context_zqr", "update_block")


def write_request(path: str, cell: dict, seed: int, recipe: dict,
                  artefacts: dict, trace_dir, result_path: str,
                  trace_window_s=None,
                  require_accelerator: bool = True) -> None:
    """What this process needs, as the entry hands it over: ``artefacts``
    holds the paths of call A's ``state``, ``batches`` and ``steps``."""
    wl = cell["workload"]
    with open(path, "w") as f:
        json.dump({"chips": cell["chips"], "seed": seed,
                   "require_accelerator": require_accelerator,
                   "config": cell["config"], "recipe": recipe,
                   "steps_compared": wl["steps_compared"],
                   "state": artefacts["state"],
                   "batches": artefacts["batches"],
                   "steps": artefacts["steps"],
                   "trace_dir": trace_dir, "trace_window_s": trace_window_s,
                   "scopes": wl["trace"]["scopes"],
                   "kernels": wl["trace"]["kernels"],
                   "limits": wl["compare"]["limits"],
                   "unit": wl["compare"].get("unit"),
                   "result_path": result_path}, f)


def load_batches(path: str) -> list:
    """``batches.npz`` (``<step>:<name>`` arrays) to a list of batches."""
    out = {}
    with np.load(path) as z:
        for key in z.files:
            i, name = key.split(":", 1)
            out.setdefault(int(i), {})[name] = z[key]
    return [out[i] for i in sorted(out)]


def load_state(path: str) -> dict:
    """``state.npz`` (``<group>:<parameter path>`` arrays) to
    ``{"params" | "mu" | "nu": {path: array}, "step": int}``."""
    out = {"params": {}, "mu": {}, "nu": {}}
    with np.load(path) as z:
        for key in z.files:
            if key == "step":
                out["step"] = int(z[key])
            else:
                group, name = key.split(":", 1)
                out[group][name] = z[key]
    return out


def _gap_rel(got: dict, want: dict, keys) -> float:
    """||got - want|| / ||want|| over the arrays named ``keys``."""
    num = sum(float(np.sum(np.square(np.asarray(got[k], np.float64)
                                     - np.asarray(want[k], np.float64))))
              for k in keys)
    den = sum(float(np.sum(np.square(np.asarray(want[k], np.float64))))
              for k in keys)
    return float(np.sqrt(num / den)) if den > 0 else float("inf")


def module_of(path: str) -> str:
    name = path.split("/")[1]
    return "context_zqr" if name.startswith("context_zqr") else name


def in_units(nums: dict, unit_nums: dict) -> dict:
    """``nums`` with each gradient gap also counted in the unit's own gap
    (``grad_gap_rel*`` to ``grad_gap_units*``)."""
    out = dict(nums)
    for name, value in nums.items():
        if name.startswith("grad_gap_rel"):
            out[name.replace("_rel", "_units", 1)] = (
                value / unit_nums[name] if unit_nums[name] > 0
                else float("inf"))
    return out


def training_numbers(start: dict, got: dict, got_steps: list,
                     want: dict, want_steps: list) -> dict:
    """``start``: the seeded table; ``got`` / ``want``: ``{"params", "mu"}``
    tables after the steps, of what is judged and of the reference;
    ``*_steps``: each step's ``loss`` (and, where the judged side has it
    too, ``grad_norm``)."""
    keys = sorted(want["mu"])
    finite = (sorted(got["mu"]) == keys and all(
        np.isfinite(got[g][k]).all() for g in ("params", "mu")
        for k in keys) and len(got_steps) >= len(want_steps))
    if not finite:
        return {name: float("inf") for name in
                ["loss_gap_rel", "grad_gap_rel", "update_gap_rel"]
                + [f"grad_gap_rel_{m}" for m in MODULES]}
    out = {
        "loss_gap_rel": max(abs(g["loss"] - w["loss"]) / abs(w["loss"])
                            for g, w in zip(got_steps, want_steps)),
        "grad_gap_rel": _gap_rel(got["mu"], want["mu"], keys)}
    if all("grad_norm" in g for g in got_steps):
        out["grad_norm_gap_rel"] = max(
            abs(g["grad_norm"] - w["grad_norm"]) / abs(w["grad_norm"])
            for g, w in zip(got_steps, want_steps))
    for m in MODULES:
        out[f"grad_gap_rel_{m}"] = _gap_rel(
            got["mu"], want["mu"], [k for k in keys if module_of(k) == m])
    moved = lambda t: {k: np.asarray(t["params"][k], np.float64)  # noqa: E731
                       - np.asarray(start[k], np.float64) for k in keys}
    out["update_gap_rel"] = _gap_rel(moved(got), moved(want), keys)
    return out


def main(argv) -> int:
    with open(argv[0]) as f:
        p = json.load(f)
    from benchmark import (compare, control, harness, reference_train,
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

    # a cell that holds several chips replays that many samples at once
    devices = jax.local_devices()[:p["chips"]]
    model = p["config"]["model"]
    t0 = time.monotonic()
    start = weights.make_weights(model, p["seed"])
    batches = load_batches(p["batches"])[:p["steps_compared"]]
    got = load_state(p["state"])
    with open(p["steps"]) as f:
        got_steps = json.load(f)
    if p.get("unit"):
        # a bfloat16 cell's unit: the same replay with every product's
        # inputs rounded to the precision the configuration states, from
        # the same compiled program
        (arrays, mu, _nu, want_steps), (u_arrays, u_mu, _, unit_steps) = (
            reference_train.replay_pair(
                model, p["recipe"], start, batches,
                reference_train.straight_through(
                    control.LOWER[p["unit"]["precision"]]), devices))
    else:
        arrays, mu, _nu, want_steps = reference_train.replay(
            model, p["recipe"], start, batches, devices=devices)
    want = {"params": arrays, "mu": mu}
    nums = training_numbers(start, got, got_steps, want, want_steps)
    if p.get("unit"):
        nums = in_units(nums, training_numbers(
            start, {"params": u_arrays, "mu": u_mu}, unit_steps, want,
            want_steps))
    for i, (g, w) in enumerate(zip(got_steps, want_steps)):
        print(f"step {i + 1}: loss {g['loss']:.6g} (reference "
              f"{w['loss']:.6g}; its grad_norm {w['grad_norm']:.6g}, epe "
              f"{w['epe']:.5g})", flush=True)
    print("training numbers: " + " ".join(f"{k} {v:.4g}"
                                          for k, v in nums.items()),
          flush=True)
    out["compared"] = compare.decide([nums], p["limits"])
    compared = min(len(batches), len(got_steps), got.get("step", 0))
    out["compared"].append({"name": "steps_compared", "value": compared,
                            "limit": p["steps_compared"],
                            "ok": compared >= p["steps_compared"]})
    out["numbers"] = nums
    out["reference_s"] = time.monotonic() - t0
    print(f"reference: {len(want_steps)} steps of {len(batches[0]['flow'])} "
          f"samples in {out['reference_s']:.1f}s", flush=True)
    with open(p["result_path"], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
