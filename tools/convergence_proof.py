"""Training convergence + exact-resume proof on real hardware, at the FULL
published architecture.

Trains the real SceneFlow-recipe model (3 GRU levels, hidden 128,
corr_levels 4, bf16 + remat, 22 GRU iterations, batch 8 at 320x720 —
reference: train_stereo.py:221-227) for 200 steps on synthetic warped-stereo
data (textured images, right view = true horizontal warp by a known
disparity field — the tests/golden_data.py generators), then proves:

1. **convergence** — mean loss over the last 50 steps < 0.7x the first 50
   (the model actually learns the disparity mapping);
2. **exact resume** — restoring the step-100 checkpoint and replaying the
   identical batch stream for steps 101-200 reproduces the uninterrupted
   run's final parameters BIT-EXACTLY (full train-state checkpoints:
   params + AdamW moments + step; reference saves weights only and cannot
   do this — train_stereo.py:184-186).  The SIGTERM half of preemption
   safety (signal -> checkpoint at step boundary) is covered on CPU by
   tests/test_training.py::test_sigterm_checkpoints_and_resumes; this
   script proves the arithmetic half on the chip.

Writes one JSON line (CONVERGENCE_r02.json artifact).
"""

from __future__ import annotations

import json
import os
import sys

import jax
import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tests"))
sys.path.insert(0, _REPO)

STEPS, CKPT_AT = 200, 100
# The SceneFlow recipe's shapes (reference: train_stereo.py:221-227).  At
# the measured ~0.9 s/step (BENCH_TRAIN_r03.json) the two runs cost ~4.5
# minutes of chip time.  --small restores the round-2 shrunken model for
# smoke runs off-chip.
H, W, BATCH, N_SCENES = 320, 720, 8, 16
ITERS = 22


def make_scenes():
    from golden_data import disparity_field, textured_image, warp_right

    rng = np.random.default_rng(42)
    scenes = []
    for _ in range(N_SCENES):
        left = textured_image(rng, H, W)
        disp = disparity_field(rng, H, W)
        right = warp_right(left, disp)
        # uint8 images: the loader contract (a float32 stream would 4x the
        # per-step batch upload).
        scenes.append((left, right, -disp))
    return scenes


class StepBatches:
    """Deterministic step-indexed batch stream: batch t is the same bytes in
    every run, and a resumed run can start mid-stream — the property exact
    resume needs from its data source."""

    def __init__(self, scenes, start: int, end: int):
        self.scenes, self.start, self.end = scenes, start, end

    def __iter__(self):
        for t in range(self.start, self.end + 1):  # +1: loop breaks at total
            idx = np.random.default_rng(1000 + t).integers(
                0, len(self.scenes), BATCH)
            l, r, f = zip(*(self.scenes[i] for i in idx))
            yield {"image1": np.stack(l), "image2": np.stack(r),
                   "flow": np.stack(f),
                   "valid": np.ones((BATCH, H, W), np.float32)}


def flat_params(state):
    return np.concatenate([np.ravel(np.asarray(jax.device_get(x)))
                           for x in jax.tree_util.tree_leaves(state.params)])


def main():
    import logging
    logging.basicConfig(level=logging.INFO)  # step-rate visibility (SUM_FREQ)
    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
    from raft_stereo_tpu.training.train_loop import train

    global H, W, BATCH, ITERS
    small = "--small" in sys.argv
    if small:
        H, W, BATCH, ITERS = 96, 128, 4, 8
        mcfg = RaftStereoConfig(n_gru_layers=2, hidden_dims=(64, 64),
                                fnet_dim=128, corr_levels=2,
                                mixed_precision=True)
    else:
        # The published architecture, exactly as defaulted (config.py
        # mirrors train_stereo.py:233-240): 3 GRU levels, hidden 128,
        # corr_levels 4, radius 4, bf16, remat_gru on.
        mcfg = RaftStereoConfig(mixed_precision=True)
    tcfg = TrainConfig(batch_size=BATCH, train_iters=ITERS, num_steps=STEPS,
                       image_size=(H, W), lr=1e-4,
                       validation_frequency=CKPT_AT, seed=7)
    scenes = make_scenes()

    losses = []
    import raft_stereo_tpu.training.logger as logger_mod
    orig_push = logger_mod.Logger.push

    def spy_push(self, metrics, lr=None):
        losses.append(float(metrics["loss"]))
        return orig_push(self, metrics, lr=lr)

    logger_mod.Logger.push = spy_push

    base = "/tmp/convergence_proof"
    import shutil
    shutil.rmtree(base, ignore_errors=True)

    # ---- run A: uninterrupted 0 -> 200
    state_a = train(mcfg, tcfg, name="mini", checkpoint_dir=f"{base}/a",
                    log_dir=f"{base}/runs_a",
                    loader=StepBatches(scenes, 1, STEPS))
    first, last = float(np.mean(losses[:50])), float(np.mean(losses[-50:]))

    # ---- run B: restore the step-100 checkpoint, replay steps 101-200
    state_b = train(mcfg, tcfg, name="mini-resumed",
                    checkpoint_dir=f"{base}/b", log_dir=f"{base}/runs_b",
                    restore=f"{base}/a/{CKPT_AT}_mini",
                    loader=StepBatches(scenes, CKPT_AT + 1, STEPS))

    pa, pb = flat_params(state_a), flat_params(state_b)
    bit_exact = bool(np.array_equal(pa, pb))
    max_diff = float(np.max(np.abs(pa - pb)))

    rec = {
        "metric": "training_convergence_and_exact_resume",
        "architecture": "small" if small else
                        "full (3 GRU, hidden 128, corr 4x4, bf16+remat)",
        "batch_hw_iters": [BATCH, H, W, ITERS],
        "steps": STEPS,
        "loss_first50": round(first, 4),
        "loss_last50": round(last, 4),
        "converged": last < 0.7 * first,
        "resume_bit_exact": bit_exact,
        "resume_max_param_diff": max_diff,
        "device": str(jax.devices()[0].device_kind),
    }
    print(json.dumps(rec))
    assert rec["converged"], rec
    assert bit_exact, rec


if __name__ == "__main__":
    main()
