"""The process that owns the chip in a serving cell: it checks the chip,
makes the seeded checkpoint, then runs ``raft-serve`` through its normal
entry (``raft_stereo_tpu.cli.serve.main``) until SIGTERM has drained it,
and leaves what the device reported in ``child_result.json``.

    python -m benchmark.entries._serve_child <params.json>

Exit code 3: no chip (the parent then prints no result).
"""

from __future__ import annotations

import importlib
import json
import sys
import time


def main(argv) -> int:
    with open(argv[0]) as f:
        p = json.load(f)
    from benchmark import harness, weights

    t0 = time.monotonic()
    try:
        device = harness.require_chips(p["chips"], p["require_accelerator"])
    except harness.BenchError as e:
        sys.stderr.write(f"benchmark: {e}\n")
        return 3
    import jax

    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.training.checkpoint import save_weights

    t_chip = time.monotonic() - t0      # imports and reaching the chip
    model = dict(p["model"])
    tree = jax.block_until_ready(
        weights.nest(weights.make_weights(model, p["seed"])))
    t_weights = time.monotonic() - t0
    cfg = RaftStereoConfig.from_dict({**model, **p["program_overrides"]})
    save_weights(p["ckpt"], cfg, tree["params"],
                 batch_stats=tree.get("batch_stats"))
    del tree
    t_ckpt = time.monotonic() - t0
    with open(p["device_path"], "w") as f:
        json.dump({"device": device, "checkpoint_s": t_ckpt,
                   "checkpoint_parts_s": {
                       "reach_chip": t_chip,
                       "weights": t_weights - t_chip,
                       "save_weights": t_ckpt - t_weights}}, f)
    if p.get("child_patch"):        # tests only: break the timed path
        mod, fn = p["child_patch"].split(":")
        getattr(importlib.import_module(mod), fn)()

    from raft_stereo_tpu.cli import serve

    rc = serve.main(p["serve_argv"]) or 0
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    with open(p["result_path"], "w") as f:
        json.dump({"memory_stats": stats, "rc": rc}, f)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
