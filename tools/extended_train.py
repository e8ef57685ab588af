"""Extended-schedule continuation of the r05 hard-scene training — a second
one-cycle at half peak LR from the r05 checkpoint via the round-5
``warm_start`` path (the reference's own multi-stage practice: sceneflow
200k then fine-tune stages, train_stereo.py README recipes).

Trains ``--steps`` more on the SAME hard corpus (no new data), then runs
all four validators on the result and writes EXTENDED_TRAIN_r05.json with
before/after.  Run after tools/trained_eval.py; one process, so one owner
of the chip."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tests"))
sys.path.insert(0, _REPO)

WORK = "/tmp/trained_eval_r05"
DATA = os.path.join(WORK, "datasets")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", default=os.path.join(WORK, "ckpt", "r05"))
    ap.add_argument("--steps", type=int, default=3000)
    ap.add_argument("--lr", type=float, default=1e-4)
    args = ap.parse_args()

    import jax
    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    from raft_stereo_tpu.config import TrainConfig
    from raft_stereo_tpu.eval.runner import InferenceRunner
    from raft_stereo_tpu.eval.validate import (make_validation_fn,
                                               validate_eth3d,
                                               validate_kitti,
                                               validate_middlebury,
                                               validate_things)
    from raft_stereo_tpu.training.checkpoint import load_weights
    from raft_stereo_tpu.training.train_loop import train

    cfg, _variables = load_weights(args.ckpt)
    tcfg = TrainConfig(batch_size=8, train_iters=22, valid_iters=32,
                       lr=args.lr, num_steps=args.steps,
                       image_size=(320, 720), train_datasets=("sceneflow",),
                       validation_frequency=500, seed=29,
                       device_photometric=True)

    curve = []
    inner = make_validation_fn(cfg, tcfg, data_root=DATA,
                               datasets=("things",))

    def validate_fn(variables, model_cfg=None):
        res = inner(variables, model_cfg)
        curve.append(round(res["things-epe"], 3))
        print(json.dumps({"validation": res}), flush=True)
        return res

    t0 = time.time()
    state = train(cfg, tcfg, name="r05x", data_root=DATA,
                  checkpoint_dir=os.path.join(WORK, "ckpt"),
                  restore=args.ckpt, warm_start=True,
                  log_dir=os.path.join(WORK, "runs_ext"),
                  validate_fn=validate_fn)
    mins = (time.time() - t0) / 60
    variables = {"params": jax.device_get(state.params)}
    if state.batch_stats:
        variables["batch_stats"] = jax.device_get(state.batch_stats)

    runner = InferenceRunner(cfg, variables, iters=32)
    things = validate_things(runner, root=DATA)
    kitti = validate_kitti(runner, root=os.path.join(DATA, "KITTI"))
    eth3d = validate_eth3d(runner, root=os.path.join(DATA, "ETH3D"))
    midd = validate_middlebury(runner, root=os.path.join(DATA, "Middlebury"),
                               split="H")
    rec = {
        "metric": "extended_train_second_cycle",
        "warm_start_ckpt": args.ckpt,
        "extra_steps": args.steps, "peak_lr": args.lr,
        "baseline_6000step": {"things-epe": 0.758, "kitti-d1": 3.156,
                              "eth3d-epe": 0.179, "middleburyH-epe": 0.388},
        "validation_epe_curve_px": curve,
        "after": {**{k: round(v, 4) for k, v in things.items()},
                  **{k: round(v, 4) for k, v in kitti.items()},
                  **{k: round(v, 4) for k, v in eth3d.items()},
                  **{k: round(v, 4) for k, v in midd.items()}},
        "wall_min": round(mins, 1),
        "device": str(jax.devices()[0].device_kind),
    }
    with open(os.path.join(_REPO, "EXTENDED_TRAIN_r05.json"), "w") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
