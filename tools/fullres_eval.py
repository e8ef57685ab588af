"""Full-resolution PRODUCT eval on chip: the REAL Middlebury validator at
trainingF scale (VERDICT round 3, item 5).

Round 3 benched the full-res machinery (banded encoder, sequential fnet,
no-volume alt kernel) as bare forwards; this runs the
actual product surface — ``eval.validate.validate_middlebury`` (per-image
valid-mask/threshold semantics proven equal to the reference's validator,
tests/test_eval_parity.py) — over a synthetic MiddEval3 trainingF tree at
Jadeplant-class 1984x2880, on the TPU.

Configuration is the reference's own full-res recipe re-designed TPU-first:
the published accuracy architecture with the no-volume ``alt`` backend
(reference runs Middlebury-F ONLY via alt — README.md:121, core/corr.py:
64-107) + the banded encoder + bf16.  ``corr_fp32_auto=False``: at this
resolution fp32 correlation features would double the fused alt kernel's
VMEM footprint and push it off the fused path (kernels/corr_alt.py gate,
FULLRES_GATES_r03.json); the measured bf16 consequence at 32 iters is
+0.04 px EPE (BF16_DRIFT_r03.json) — the right trade at 5.7 MP, recorded in
the artifact.

Round 5: the tree is HARD layered scenes (true occlusions, textureless
surfaces) with disparities to ~560 px — the trainingF-scale analog of the
training corpus's 190/960 disparity-to-width ratio (real trainingF GT runs
to ~800 px at Jadeplant).  Writes FULLRES_EVAL_r05.json: EPE/D1 from the real validator, per-image
seconds (the runner's honest fetch-stop clock), and the XLA-compiled peak
HBM of the forward at this size.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tests"))
sys.path.insert(0, _REPO)

HW = (1984, 2880)       # Jadeplant-class trainingF frames, /32-aligned
D_MAX = 560.0           # training corpus disparity/width ratio at F scale
N_SCENES = 2
ITERS = 32


def build_tree(root: str) -> None:
    import golden_data as gd

    marker = os.path.join(root, ".complete")
    if os.path.exists(marker):
        return
    import shutil
    shutil.rmtree(os.path.join(root, "MiddEval3"),
                  ignore_errors=True)  # partial build from an interrupt
    t0 = time.time()
    orig = gd.hard_pair
    gd.hard_pair = lambda r, h, w: orig(r, h, w, d_max=D_MAX)
    try:
        gd.make_middlebury(root, np.random.default_rng(4), n=N_SCENES,
                           hw=HW, split="F", hard=True)
    finally:
        gd.hard_pair = orig
    open(marker, "w").write("ok")
    print(f"[tree] {N_SCENES} scenes at {HW[0]}x{HW[1]} in "
          f"{time.time() - t0:.0f}s", flush=True)


def main():
    import logging
    logging.basicConfig(level=logging.INFO)
    import jax
    import jax.numpy as jnp

    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.eval.runner import InferenceRunner
    from raft_stereo_tpu.eval.validate import validate_middlebury
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    root = "/tmp/fullres_eval_r05/Middlebury"
    os.makedirs(root, exist_ok=True)
    build_tree(root)

    # Weights: the round-4 trained checkpoint when present (the correlation
    # backends and the banded executor are parameter-free executors over
    # the same tree, so a checkpoint trained with reg_fused/plain encoding
    # drops straight into alt+banded), else random init.
    import dataclasses

    from raft_stereo_tpu.training.checkpoint import load_weights
    trained_ckpt = "/tmp/trained_eval_r05/ckpt/r05"
    if os.path.isdir(trained_ckpt):
        ckpt_cfg, variables = load_weights(trained_ckpt)
        cfg = dataclasses.replace(ckpt_cfg, corr_backend="alt",
                                  banded_encoder=True, mixed_precision=True)
        weights_note = "TRAINED (tools/trained_eval.py round-5 checkpoint (hard-scene trained))"
        model = RAFTStereo(cfg)
    else:
        cfg = RaftStereoConfig(corr_backend="alt", banded_encoder=True,
                               mixed_precision=True)
        model = RAFTStereo(cfg)
        img_s = jnp.zeros((1, 64, 96, 3), jnp.float32)
        variables = jax.jit(lambda r: model.init(r, img_s, img_s, iters=1,
                                                 test_mode=True)
                            )(jax.random.PRNGKey(0))
        weights_note = ("random-init (trained product numbers live in "
                        "TRAINED_EVAL_r05.json)")

    # Compiled peak HBM of the forward at the exact eval shape (the runtime
    # exposes no live memory stats).
    imgf = jnp.zeros((1,) + HW + (3,), jnp.float32)
    lowered = jax.jit(lambda v, a, b: model.apply(v, a, b, iters=ITERS,
                                                  test_mode=True)[1]
                      ).lower(variables, imgf, imgf)
    ma = lowered.compile().memory_analysis()
    peak_gib = ma.peak_memory_in_bytes / 2 ** 30

    runner = InferenceRunner(cfg, variables, iters=ITERS,
                             corr_fp32_auto=False)
    # First call absorbs compile; run the validator twice and keep the
    # second pass's per-image clock (the validator logs per-image EPE).
    res = validate_middlebury(runner, root=root, split="F")
    t0 = time.time()
    res = validate_middlebury(runner, root=root, split="F")
    per_image_s = (time.time() - t0) / N_SCENES

    rec = {
        "metric": "fullres_product_eval_middleburyF",
        "value": round(res["middleburyF-epe"], 3),
        "unit": "px EPE (validate_middlebury, HARD synthetic trainingF tree)",
        "d1_pct": round(res["middleburyF-d1"], 2),
        "size": f"{HW[0]}x{HW[1]}",
        "iters": ITERS,
        "config": "accuracy arch + alt (no-volume) + banded encoder + bf16",
        "corr_fp32_auto": False,
        "bf16_corr_note": "fp32 corr would leave the fused VMEM path at "
                          "this size; measured 32-iter bf16 dEPE is "
                          "<=0.05 px (BF16_DRIFT_r04.json trained rows; "
                          "r03 warm-up rows agree)",
        "per_image_s": round(per_image_s, 2),
        "compiled_peak_hbm_gib": round(peak_gib, 3),
        "n_scenes": N_SCENES,
        "weights": weights_note,
        "device": str(jax.devices()[0].device_kind),
    }
    print(json.dumps(rec))
    with open(os.path.join(_REPO, "FULLRES_EVAL_r05.json"), "w") as f:
        f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
