"""The int8 tier's accuracy gate: measure per-band EPE drift of the
post-training quantized path on trained weights, next to the bf16
numbers (ROADMAP open item 2; the BF16_DRIFT_r03-r05 methodology
extended down to int8).

What runs:

1. **Brief training** of the hermetic architecture on warped textured
   stereo (tools/early_exit_report.py's recipe) — drift must be measured
   in a FUNCTIONING network: an untrained GRU amplifies any numeric
   perturbation into meaningless divergence (the round-3 lesson).
2. **Calibration** (quant/calibrate.py) on pairs from the SAME
   distribution: percentile-clipped activation ranges -> the
   checkpoint-adjacent scale file (written next to the report) whose
   per-level corr scales the int8 variants compile with.
3. **Per-band evaluation** via the shared drift harness
   (tools/drift_common.py — same scenes, same record schema as
   bf16_drift, so the rows are directly comparable): variants from
   IDENTICAL weights:
     - ``fp32``       — full-precision reference (reg backend);
     - ``bf16``       — mixed-precision encoders (the r03-r05 subject);
     - ``int8``       — the r15 weights-only-compute tier: int8 encoder
                        weights (dequantized in-register) + int8
                        correlation pyramid with calibrated scales;
     - ``int8_w``     — weights-only ablation (quant_corr=False): how
                        much of the drift is weights vs pyramid;
     - ``int8_mxu``   — the r22 COMPUTE tier (turbo v2): encoder convs
                        multiply int8×int8→int32 with calibrated static
                        activation scales (quant/matmul.py) + the same
                        int8 pyramid — the extra drift over ``int8`` is
                        exactly the activation quantization.
4. **The gate**: worst |ΔEPE| of the int8 AND int8_mxu tiers at the
   d<=96 band must stay within ``--gate_px`` (default 0.05 px — the
   same budget the fp16 fetch was accepted at).  The record
   carries a ``gate`` object with a per-mode breakdown;
   scripts/quant_smoke.py asserts it in CI.

Writes QUANT_DRIFT_r22.json (+ the scale file) and prints one JSON line
per row.  CPU defaults keep it minutes-scale (tiny architecture, two
bands); on an accelerator pass --full for the KITTI-class geometry.
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
sys.path.insert(0, os.path.join(_REPO, "tools"))
sys.path.insert(0, _REPO)

OUT = os.environ.get("QUANT_DRIFT_OUT",
                     os.path.join(_REPO, "QUANT_DRIFT_r22.json"))
SCALES_OUT = os.environ.get("QUANT_SCALES_OUT",
                            os.path.join(_REPO, "quant_scales.json"))


def build_parser():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=180,
                    help="brief-training steps (0 = seeded init only — "
                         "NOT a meaningful drift setting, test use)")
    ap.add_argument("--train_hw", default="40x112")
    ap.add_argument("--train_iters", type=int, default=4)
    ap.add_argument("--train_disp_scale", type=float, default=4.0,
                    help="disparity amplitude multiplier of the warped "
                         "training scenes (~12 px base -> ~45 px at the "
                         "default): the eval bands clip at 48/96 px, so "
                         "training must SEE band-range disparities for "
                         "the drift measurement to run in-distribution "
                         "(the bf16_drift round-5 lesson)")
    ap.add_argument("--hw", default="80x256",
                    help="eval scene HxW (/32-aligned; bands need width "
                         "headroom past their disparity ceiling)")
    ap.add_argument("--bands", default="48,96",
                    help="comma list of band ceilings (px); the gate "
                         "reads the 96 band")
    ap.add_argument("--n_per_band", type=int, default=2)
    ap.add_argument("--iters", default="4,10",
                    help="comma list of GRU depths to evaluate")
    ap.add_argument("--calib_pairs", type=int, default=4,
                    help="calibration pairs (training distribution)")
    ap.add_argument("--percentile", type=float, default=99.9)
    ap.add_argument("--gate_px", type=float, default=0.05,
                    help="|dEPE| budget for the int8 tier at d<=96")
    ap.add_argument("--full", action="store_true",
                    help="KITTI-class geometry (384x1248, bands "
                         "48/96/192, iters 7/32, the bf16_drift "
                         "training recipe) — accelerator scale")
    return ap


def calibration_pairs(hw, n, seed=71, disp_scale=1.0):
    """In-distribution pairs for the calibration pass: the same warped
    textured stereo the brief training saw."""
    from golden_data import disparity_field, textured_image, warp_right

    rng = np.random.default_rng(seed)
    pairs = []
    for _ in range(n):
        left = textured_image(rng, *hw)
        disp = disparity_field(rng, *hw) * disp_scale
        right = warp_right(left, disp)
        pairs.append((left.astype(np.float32), right.astype(np.float32)))
    return pairs


def brief_train(cfg, steps: int, train_hw, train_iters: int,
                disp_scale: float):
    """Brief training on warped textured scenes with BAND-RANGE
    disparities (``disp_scale``) — tools/bf16_drift.py's recipe at CPU
    scale: the drift gate is only meaningful on a network functioning
    over the disparities the bands evaluate."""
    import dataclasses
    import tempfile

    import jax

    from golden_data import disparity_field, textured_image, warp_right

    from raft_stereo_tpu.config import TrainConfig
    from raft_stereo_tpu.training.train_loop import train

    h, w = train_hw
    rng = np.random.default_rng(23)
    scenes = []
    for _ in range(12):
        left = textured_image(rng, h, w)
        disp = disparity_field(rng, h, w) * disp_scale
        right = warp_right(left, disp)
        scenes.append((left.astype(np.float32),
                       right.astype(np.float32), -disp))

    batch_n = 2

    class Stream:
        def __iter__(self):
            for t in range(steps + 1):
                idx = np.random.default_rng(500 + t).integers(
                    0, len(scenes), batch_n)
                ls, rs, fs = zip(*(scenes[i] for i in idx))
                yield {"image1": np.stack(ls), "image2": np.stack(rs),
                       "flow": np.stack(fs),
                       "valid": np.ones((batch_n, h, w), np.float32)}

    tcfg = TrainConfig(batch_size=batch_n, train_iters=train_iters,
                       num_steps=steps, image_size=(h, w), lr=2e-4,
                       validation_frequency=10 ** 9, seed=3)
    mcfg = dataclasses.replace(cfg, corr_fp32=True)
    with tempfile.TemporaryDirectory() as td:
        state = train(mcfg, tcfg, name="quant_drift", checkpoint_dir=td,
                      log_dir=os.path.join(td, "runs"), loader=Stream())
    return {"params": jax.device_get(state.params),
            "batch_stats": jax.device_get(state.batch_stats) or {}}


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.full:
        args.hw, args.bands, args.iters = "384x1248", "48,96,192", "7,32"
        args.train_hw, args.train_iters = "320x704", 12
        args.steps, args.train_disp_scale = 300, 6.0
    hw = tuple(int(x) for x in args.hw.split("x"))
    train_hw = tuple(int(x) for x in args.train_hw.split("x"))
    iters_list = [int(x) for x in args.iters.split(",")]
    bands = {f"d<={c}": float(c) for c in args.bands.split(",")}

    import dataclasses

    import jax

    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    from drift_common import evaluate_variants, make_band_scenes
    from early_exit_report import model_config

    from raft_stereo_tpu import quant
    from raft_stereo_tpu.telemetry.events import bench_record, write_record

    cfg = model_config()
    t0 = time.perf_counter()
    if args.steps > 0:
        variables = brief_train(cfg, args.steps, train_hw,
                                args.train_iters, args.train_disp_scale)
    else:
        from early_exit_report import init_variables
        variables = init_variables(cfg)
    train_s = time.perf_counter() - t0
    print(json.dumps({"trained": {"steps": args.steps,
                                  "hw": list(train_hw),
                                  "disp_scale": args.train_disp_scale,
                                  "seconds": round(train_s, 1)}}),
          flush=True)

    # --- calibration: the checkpoint-adjacent scale file ---------------
    t0 = time.perf_counter()
    record = quant.calibrate(
        cfg, variables,
        calibration_pairs(train_hw, args.calib_pairs,
                          disp_scale=args.train_disp_scale),
        percentile=args.percentile)
    quant.save_scales(SCALES_OUT, record)
    corr_scales = quant.corr_scales(record)
    calib_s = time.perf_counter() - t0
    print(json.dumps({"calibration": {
        "scales_file": os.path.basename(SCALES_OUT),
        "pairs": args.calib_pairs, "percentile": args.percentile,
        "corr_scales": [round(s, 6) for s in corr_scales],
        "activation_sites": len(record["activations"]),
        "seconds": round(calib_s, 1)}}), flush=True)

    # --- variants from identical weights --------------------------------
    int8_cfg = dataclasses.replace(cfg, quant="int8",
                                   quant_corr_scales=corr_scales)
    # The compute tier's variant carries its tree PRE-quantized with the
    # calibrated activation scales baked into the packs (the runner
    # skips re-quantization on an already-quantized tree) — the same
    # tree construction the serving engine's _vars_for performs.
    act_scales = quant.conv_input_scales(record)
    mxu_vars = quant.quantize_variables(variables, act_scales=act_scales)
    variants = {
        "fp32": (cfg, variables),
        "bf16": (dataclasses.replace(cfg, mixed_precision=True),
                 variables),
        "int8": (int8_cfg, variables),
        "int8_w": (dataclasses.replace(int8_cfg, quant_corr=False),
                   variables),
        "int8_mxu": (dataclasses.replace(int8_cfg, quant="int8_mxu"),
                     mxu_vars),
    }
    scenes = make_band_scenes(hw[0], hw[1], bands,
                              n_per_band=args.n_per_band, seed=11)
    rows = evaluate_variants("int8_epe_drift", "brief_trained", variants,
                             scenes, iters_list=iters_list, ref="fp32",
                             drift_of="int8",
                             runner_kwargs={"corr_fp32_auto": False})

    # --- the gate --------------------------------------------------------
    gate_band = next((b for b in bands if b == "d<=96"),
                     next(iter(bands)))
    gate_rows = [r for r in rows if r["band"] == gate_band]
    per_mode = {
        mode: max((abs(r[f"depe_{mode}"]) for r in gate_rows),
                  default=None)
        for mode in ("int8", "int8_mxu")}
    finite = [v for v in per_mode.values() if v is not None]
    worst = max(finite) if finite else None
    gate = {"band": gate_band, "budget_px": args.gate_px,
            "worst_abs_depe_px": worst,
            "per_mode": per_mode,
            "pass": bool(worst is not None and worst <= args.gate_px)}
    if not gate["pass"]:
        print(f"WARNING: quant drift gate FAILED: worst |dEPE|={worst} "
              f"px > {args.gate_px} px at {gate_band} "
              f"(per mode: {per_mode}) — do not enable the turbo tier "
              f"on this checkpoint", flush=True)

    qvars = mxu_vars
    rec = bench_record({
        "metric": "int8_epe_drift_gate",
        "value": worst,
        "unit": f"worst |dEPE| px at {gate_band} vs fp32 "
                f"({hw[0]}x{hw[1]}, {args.steps} train steps, "
                f"{jax.devices()[0].platform})",
        "gate": gate,
        "train_steps": args.steps,
        "train_seconds": round(train_s, 1),
        "calibration": {"scales_file": os.path.basename(SCALES_OUT),
                        "percentile": args.percentile,
                        "pairs": args.calib_pairs,
                        "corr_scales": [round(s, 6)
                                        for s in corr_scales]},
        "param_bytes": quant.quantized_param_bytes(qvars),
        "rows": rows,
    })
    print(json.dumps(rec))
    write_record(OUT, rec, indent=1)
    print(f"quant drift -> {OUT} (scales -> {SCALES_OUT})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
