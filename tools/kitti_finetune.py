"""KITTI fine-tune lifecycle — BASELINE config 5, the sparse-GT training
path, on-chip (reference: train_stereo.py:132-211 with KITTI aug params;
the RAFT-Stereo paper fine-tunes the sceneflow checkpoint on KITTI-2015).

What this proves that nothing else in the repo does:

* sparse ground truth flows through TRAINING on the TPU: the KITTI tree's
  16-bit disp_occ_0 pngs (zero = no LiDAR return) -> ``SparseAugmentor``
  (valid-mask-aware scaling/crop, data/augment.py) -> the valid∧max-flow
  mask path of ``training/loss.py`` — previously exercised only in CPU
  unit tests;
* the training mixture's ``"kitti"`` entry works end to end.  The
  reference's own fetch_dataloader CRASHES here — it passes ``split=`` to
  a KITTI __init__ that has no such kwarg
  (reference: core/stereo_datasets.py:298) — this repo fixed the recipe
  and this tool executes the fix;
* ``train(..., warm_start=True)``: weights-only restart from the r05
  sceneflow-trained orbax checkpoint, fresh one-cycle schedule — the
  reference's fine-tune semantics for --restore_ckpt.

Protocol: validate_kitti on the trained-from-scratch checkpoint (before),
fine-tune ``--steps`` on the hard KITTI tree through the real train loop,
validate_kitti again (after), and record a sparse-batch census (fraction
of valid GT pixels actually reaching the loss).  Writes
KITTI_FINETUNE_r05.json.  Run AFTER tools/trained_eval.py (reuses its
checkpoint and its hard KITTI tree; both are rebuilt here if missing).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tests"))
sys.path.insert(0, _REPO)

R05_WORK = "/tmp/trained_eval_r05"
ARTIFACT = os.path.join(_REPO, "KITTI_FINETUNE_r05.json")
KITTI_HW = (375, 1242)
D_MAX = 190.0


def ensure_kitti_tree(root: str, n: int = 70) -> str:
    if not os.path.isdir(os.path.join(root, "training", "image_2")):
        import golden_data as gd
        os.makedirs(os.path.dirname(root), exist_ok=True)
        orig = gd.hard_pair
        gd.hard_pair = lambda r, h, w: orig(r, h, w, d_max=D_MAX)
        try:
            gd.make_kitti(root, np.random.default_rng(20260731), n=n,
                          hw=KITTI_HW, hard=True)
        finally:
            gd.hard_pair = orig
    return root


def sparse_batch_census(loader) -> dict:
    """One real loader batch: prove sparse masks reach the loss inputs."""
    batch = next(iter(loader))
    valid = batch["valid"]
    flow = batch["flow"]
    vm = valid > 0.5
    return {
        "batch_valid_fraction": round(float(vm.mean()), 4),
        "batch_has_invalid": bool((~vm).any()),
        "valid_px_mean_abs_disp": round(float(np.abs(flow[vm]).mean()), 2),
        "batch_shape": list(valid.shape),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", default=os.path.join(R05_WORK, "ckpt", "r05"),
                    help="sceneflow-trained orbax checkpoint to fine-tune")
    ap.add_argument("--kitti_root",
                    default=os.path.join(R05_WORK, "datasets", "KITTI"))
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--batch_size", type=int, default=4)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU pre-flight (fresh tiny weights, 3 steps)")
    args = ap.parse_args()

    if args.smoke:
        # CPU pre-flight
        from _hermetic import force_cpu
        force_cpu(1)

    import jax
    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
    from raft_stereo_tpu.data.datasets import build_training_mixture
    from raft_stereo_tpu.data.loader import StereoLoader
    from raft_stereo_tpu.eval.runner import InferenceRunner
    from raft_stereo_tpu.eval.validate import validate_kitti
    from raft_stereo_tpu.training.checkpoint import load_weights
    from raft_stereo_tpu.training.train_loop import train

    global KITTI_HW, D_MAX
    work = "/tmp/kitti_finetune_r05"
    if args.smoke:
        KITTI_HW, D_MAX = (96, 160), 24.0
        work = "/tmp/kitti_finetune_smoke"
        args.steps, args.batch_size = 3, 2
        args.kitti_root = os.path.join(work, "datasets", "KITTI")
        n_tree = 6
    else:
        n_tree = 70
    os.makedirs(work, exist_ok=True)
    ensure_kitti_tree(args.kitti_root, n=n_tree)
    data_root = os.path.dirname(args.kitti_root)

    if args.smoke:
        # fresh tiny weights stand in for the r05 checkpoint
        from raft_stereo_tpu.models.raft_stereo import RAFTStereo
        from raft_stereo_tpu.training.checkpoint import save_weights
        cfg = RaftStereoConfig(hidden_dims=(32, 32, 32), fnet_dim=64,
                               corr_levels=2, corr_radius=3,
                               mixed_precision=True)
        model = RAFTStereo(cfg)
        import jax.numpy as jnp
        dummy = jnp.zeros((1, 64, 96, 3), jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), dummy, dummy,
                               iters=1, test_mode=True)
        args.ckpt = os.path.join(work, "seed_ckpt")
        save_weights(args.ckpt, cfg, variables["params"],
                     variables.get("batch_stats"))

    cfg, variables = load_weights(args.ckpt)

    # ---- before: the product-path KITTI validator on the warm-start weights
    runner = InferenceRunner(cfg, variables, iters=32 if not args.smoke
                             else 2)
    before = validate_kitti(runner, root=args.kitti_root)
    print(json.dumps({"phase": "before", **before}), flush=True)
    del runner

    # ---- fine-tune through the REAL train loop (sparse GT path)
    # KITTI aug params per the reference's fine-tune practice: tighter
    # scale range, no y-jitter (rectified real rig), saturation 0-1.4
    crop = (320, 1000) if not args.smoke else (64, 96)
    tcfg = TrainConfig(
        batch_size=args.batch_size, train_iters=22 if not args.smoke else 2,
        valid_iters=32 if not args.smoke else 2,
        lr=1e-4, num_steps=args.steps, image_size=crop,
        train_datasets=("kitti",),
        spatial_scale=(-0.2, 0.4), noyjitter=True,
        saturation_range=(0.0, 1.4),
        validation_frequency=10 ** 9, seed=31,
        device_photometric=not args.smoke)

    # census: one real sparse batch as the loss will see it
    mixture = build_training_mixture(tcfg, data_root)
    census_loader = StereoLoader(mixture, batch_size=args.batch_size,
                                 num_workers=0, seed=31)
    census = sparse_batch_census(census_loader)
    del census_loader
    print(json.dumps({"phase": "census", **census}), flush=True)
    assert census["batch_has_invalid"], \
        "sparse KITTI batch shows no invalid pixels — sparse path broken?"

    t0 = time.time()
    state = train(cfg, tcfg, name="kitti_ft", data_root=data_root,
                  checkpoint_dir=os.path.join(work, "ckpt"),
                  restore=args.ckpt, warm_start=True,
                  log_dir=os.path.join(work, "runs"))
    train_min = (time.time() - t0) / 60
    ft_variables = {"params": jax.device_get(state.params)}
    if state.batch_stats:
        ft_variables["batch_stats"] = jax.device_get(state.batch_stats)

    # ---- after
    runner = InferenceRunner(cfg, ft_variables,
                             iters=32 if not args.smoke else 2)
    after = validate_kitti(runner, root=args.kitti_root)
    print(json.dumps({"phase": "after", **after}), flush=True)

    rec = {
        "metric": "kitti_finetune_lifecycle",
        "warm_start_ckpt": args.ckpt,
        "steps": args.steps,
        "batch_hw_iters": [args.batch_size, *crop, tcfg.train_iters],
        "data": f"hard KITTI-layout tree (sparse disp_occ_0, d<=~{D_MAX:.0f}"
                f" px, true occlusions), {n_tree} pairs at "
                f"{KITTI_HW[0]}x{KITTI_HW[1]}",
        "sparse_batch": census,
        "before": {k: round(v, 4) for k, v in before.items()},
        "after": {k: round(v, 4) for k, v in after.items()},
        "d1_improved": bool(after["kitti-d1"] < before["kitti-d1"]),
        "train_wall_min": round(train_min, 1),
        "device": str(jax.devices()[0].device_kind),
    }
    out = ARTIFACT if not args.smoke else os.path.join(
        work, "KITTI_FINETUNE_smoke.json")
    with open(out, "w") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
