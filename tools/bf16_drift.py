"""Quantify the disparity-EPE consequence of the realtime preset's bf16
correlation (VERDICT round 2 missing #1 / next #4).

The shipped realtime preset runs the fused no-volume 'alt' lookup in
bfloat16 — a deliberate deviation from the reference, which forces fp32
features into its python alt backend (core/raft_stereo.py:95) but runs its
CUDA lookup in fp16 (sampler_kernel.cu:126).  Round 2 reported ~0.01
correlation-value drift and claimed EPE is unchanged without measuring it.
This tool measures it, on the chip, end to end:

* weights — BOTH of the offline-constructible realistic settings:
  (a) the actual torch reference realtime architecture, seeded init,
      imported via io.torch_import (realistic init scales);
  (b) the same model briefly TRAINED on-chip (300 steps, synthetic
      warped-stereo scenes, fp32 correlation) so predictions track ground
      truth and numeric drift is measured in a FUNCTIONING network rather
      than amplified through an untrained GRU;
* scenes — HARD layered stereo at 384x1248 (KITTI-class): true
  occlusions, depth discontinuities, textureless patches
  (tests/golden_data.py layered_scene), with per-band disparity ceilings
  pinned at exactly 48 / 96 / 192 px, spanning the real evaluation range
  (the reference's KITTI protocol clips at 192 px --
  evaluate_stereo.py:133-135).  With the --ckpt weights trained on the
  same distribution (round 5), every band is in-distribution;
* backends from IDENTICAL weights:
  bf16-alt (shipped), corr_fp32 alt (the knob), fp32 reg (reference-exact
  numerics).

Reports per-band EPE per backend, the EPE deltas vs fp32-reg, and the raw
prediction drift |disp_bf16 - disp_fp32reg|.  One JSON line per row.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import tempfile
from types import SimpleNamespace

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tests"))
sys.path.insert(0, os.path.join(_REPO, "tools"))
sys.path.insert(0, _REPO)

H, W = 384, 1248                  # KITTI-class, /32-aligned
# per-band disparity ceiling (round 5: HARD layered scenes with true
# occlusions at exactly this ceiling, not a scaled smooth ramp).
# Bands + scene generator + record schema now live in tools/drift_common
# (round 15), shared with tools/quant_drift.py so the bf16 and int8
# rows are directly comparable.
N_PER_BAND = 2
ITERS = (7, 32)                   # realtime demo depth, accuracy depth
TRAIN_STEPS = 300
TRAIN_HW = (320, 704)


def make_band_scenes():
    from drift_common import make_band_scenes as shared_scenes

    return shared_scenes(H, W, n_per_band=N_PER_BAND, seed=11)


def torch_seeded_pth(tmp) -> str:
    """The actual reference realtime architecture with seeded torch init."""
    for p in ("/root/reference", "/root/reference/core"):
        if p not in sys.path:
            sys.path.insert(0, p)
    import torch
    from core.raft_stereo import RAFTStereo as TorchRAFTStereo

    args = SimpleNamespace(hidden_dims=[128, 128, 128],
                           corr_implementation="reg", shared_backbone=True,
                           corr_levels=4, corr_radius=4, n_downsample=3,
                           context_norm="batch", slow_fast_gru=True,
                           n_gru_layers=2, mixed_precision=False)
    torch.manual_seed(7)
    model = TorchRAFTStereo(args)
    model.eval()
    pth = os.path.join(tmp, "rt_init.pth")
    torch.save(model.state_dict(), pth)
    return pth


def trained_variables(base_cfg):
    """Train the realtime architecture briefly on warped-stereo scenes
    (fp32 correlation during training: backend numerics must not leak into
    the weights being compared)."""
    from golden_data import disparity_field, textured_image, warp_right

    from raft_stereo_tpu.config import TrainConfig
    from raft_stereo_tpu.training.train_loop import train

    h, w = TRAIN_HW
    rng = np.random.default_rng(23)
    scenes = []
    for _ in range(12):
        left = textured_image(rng, h, w)
        disp = disparity_field(rng, h, w) * 6.0   # up to ~70 px
        right = warp_right(left, disp)
        scenes.append((left.astype(np.float32), right.astype(np.float32),
                       -disp))

    batch_n = 4

    class Stream:
        def __iter__(self):
            for t in range(TRAIN_STEPS + 1):
                idx = np.random.default_rng(500 + t).integers(
                    0, len(scenes), batch_n)
                l, r, f = zip(*(scenes[i] for i in idx))
                yield {"image1": np.stack(l), "image2": np.stack(r),
                       "flow": np.stack(f),
                       "valid": np.ones((batch_n, h, w), np.float32)}

    mcfg = dataclasses.replace(base_cfg, corr_fp32=True)
    tcfg = TrainConfig(batch_size=batch_n, train_iters=12,
                       num_steps=TRAIN_STEPS, image_size=(h, w), lr=2e-4,
                       validation_frequency=10 ** 9, seed=3)
    with tempfile.TemporaryDirectory() as td:
        state = train(mcfg, tcfg, name="drift", checkpoint_dir=td,
                      log_dir=os.path.join(td, "runs"), loader=Stream())
    import jax
    return {"params": jax.device_get(state.params),
            "batch_stats": jax.device_get(state.batch_stats) or {}}


def evaluate(tag, cfg_variables, scenes):
    # Shared drift harness (tools/drift_common.py): one record schema
    # for the whole low-precision gate family.  corr_fp32_auto off: this
    # tool MEASURES raw bf16-corr drift at deep iteration counts — the
    # very thing the runner's guard would mask.
    from drift_common import evaluate_variants

    return evaluate_variants(
        "bf16_corr_epe_drift", tag, cfg_variables, scenes,
        iters_list=ITERS, ref="fp32_reg", drift_of="bf16_alt",
        runner_kwargs={"corr_fp32_auto": False})


def main():
    import argparse

    import jax

    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.io.torch_import import import_torch_checkpoint

    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", default=None,
                    help="measure drift on THESE trained weights (orbax "
                         "checkpoint dir, e.g. the round-4 trained_eval "
                         "checkpoint) instead of the seeded/300-step pair")
    args = ap.parse_args()

    realtime = RaftStereoConfig.realtime()
    scenes = make_band_scenes()

    def three_configs(cfg, variables):
        return {
            "bf16_alt": (cfg, variables),
            "fp32corr_alt": (dataclasses.replace(cfg, corr_fp32=True),
                             variables),
            "fp32_reg": (dataclasses.replace(cfg, corr_backend="reg",
                                             mixed_precision=False),
                         variables),
        }

    if args.ckpt:
        # A CONVERGED network (tools/trained_eval.py trains to ~0.1 px
        # held-out EPE) — the strongest setting for the drift question:
        # round 3's "trained" rows were a 300-step warm-up and the large
        # per-pixel drift concentrated where that network was itself
        # unconverged.  Adds the shipped accuracy backend (reg_fused) as a
        # 4th variant from the same weights.
        from raft_stereo_tpu.training.checkpoint import load_weights
        cfg, variables = load_weights(args.ckpt)
        cfg = dataclasses.replace(cfg, corr_backend="alt",
                                  mixed_precision=True)
        variants = three_configs(cfg, variables)
        variants["bf16_fused"] = (
            dataclasses.replace(cfg, corr_backend="reg_fused"), variables)
        evaluate("trained_checkpoint", variants, scenes)
        return

    with tempfile.TemporaryDirectory() as td:
        pth = torch_seeded_pth(td)
        cfg, variables = import_torch_checkpoint(pth, slow_fast_gru=True)
        assert cfg.shared_backbone and cfg.n_downsample == 3
        cfg = dataclasses.replace(cfg, corr_backend="alt",
                                  mixed_precision=True)
        evaluate("torch_seeded_init", three_configs(cfg, variables), scenes)

    trained = trained_variables(realtime)
    evaluate("trained_300_steps", three_configs(realtime, trained), scenes)


if __name__ == "__main__":
    main()
