"""Adaptive GRU early exit: threshold sweep on the four validators.

The convergence-gated while-loop (models/raft_stereo.py,
``exit_threshold_px``) trades GRU iterations — ~89% of realtime inference
wall time (INFERENCE_PROFILE_r03.json) — for a bounded disparity-accuracy
cost.  This tool measures that trade end to end and writes the record the
serving tiers are calibrated against (config.REQUEST_TIERS):

1. train a model briefly on warped-stereo scenes so the GRU actually
   converges (an untrained GRU's update magnitudes are meaningless — the
   same reason tools/bf16_drift.py trains before measuring drift);
2. build the four mini-benchmarks (tests/golden_data.py: ETH3D / KITTI /
   FlyingThings / Middlebury-H trees with real on-disk formats) and run
   the REAL validators (eval/validate.py) at the fixed depth — the
   baseline EPE row;
3. sweep ``exit_threshold_px``: per threshold, per validator, the EPE
   delta vs the fixed baseline and the mean ``iters_used`` the gate
   actually ran;
4. bench per-image latency for each serving tier preset (interactive /
   balanced / quality) against the fixed-depth baseline — p50/p95 over
   the same eval pairs, WARN on regression (a tier must never be slower
   than fixed depth beyond noise);
5. pick the sweep's operating point: the loosest threshold whose worst
   validator ΔEPE stays within ``--max_depe`` (default 0.05 px), and
   assert it saves iterations (the acceptance bar: mean iters <= 60% of
   the fixed depth at that ΔEPE).

Run from the repo root (CPU works; numbers scale on an accelerator):

    JAX_PLATFORMS=cpu python tools/early_exit_report.py          # full
    JAX_PLATFORMS=cpu python tools/early_exit_report.py --steps 40 \\
        --iters 8 --out /tmp/EARLY_EXIT_smoke.json               # smoke

Writes ``EARLY_EXIT_<tag>.json`` (shared versioned bench header,
telemetry/events.py) and prints one JSON summary line per sweep row.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tests"))
sys.path.insert(0, _REPO)

DEFAULT_TAG = "r12"
VALIDATORS = ("eth3d", "kitti", "things", "middleburyH")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--iters", type=int, default=16,
                   help="fixed GRU depth the sweep compares against (the "
                        "early-exit cap)")
    p.add_argument("--min_iters", type=int, default=2,
                   help="early-exit floor for every sweep point")
    p.add_argument("--thresholds",
                   default="0.5,0.4,0.3,0.25,0.2,0.15,0.1,0.05,0.01",
                   help="comma list of exit_threshold_px values, loosest "
                        "first")
    p.add_argument("--steps", type=int, default=200,
                   help="brief-training steps before measuring (0 = "
                        "measure the random init; only for debugging — "
                        "an untrained GRU does not converge)")
    p.add_argument("--images", type=int, default=3,
                   help="images per validator tree")
    p.add_argument("--hw", default="60x90",
                   help="validator image size HxW (pads to /32)")
    p.add_argument("--train_hw", default="64x96")
    p.add_argument("--train_iters", type=int, default=8)
    p.add_argument("--max_depe", type=float, default=0.05,
                   help="worst-validator EPE delta (px) the chosen "
                        "operating point must stay within")
    p.add_argument("--lat_repeats", type=int, default=3,
                   help="latency-bench passes over the eval pairs per "
                        "tier")
    p.add_argument("--tag", default=DEFAULT_TAG)
    p.add_argument("--out", default=None,
                   help="output path; default EARLY_EXIT_<tag>.json")
    return p


def model_config():
    from raft_stereo_tpu.config import RaftStereoConfig

    # The hermetic test architecture: small enough that the full
    # train + 4-validator x N-threshold sweep runs on CPU in minutes,
    # same GRU update rule as the published configs.  fnet_norm="none"
    # because brief training backprops through the encoder and the
    # instance-norm executor is inference-only (models/norm.py barrier).
    return RaftStereoConfig(hidden_dims=(32, 32, 32), fnet_dim=64,
                            fnet_norm="none", corr_backend="reg")


def trained_variables(cfg, steps: int, train_hw, train_iters: int):
    """Brief training on warped textured scenes (golden_data's exact
    stereo geometry) so the update magnitudes carry a real convergence
    curve."""
    import jax

    from golden_data import disparity_field, textured_image, warp_right
    from raft_stereo_tpu.config import TrainConfig
    from raft_stereo_tpu.training.train_loop import train

    h, w = train_hw
    rng = np.random.default_rng(23)
    scenes = []
    for _ in range(10):
        left = textured_image(rng, h, w)
        disp = disparity_field(rng, h, w)
        right = warp_right(left, disp)
        scenes.append((left.astype(np.float32), right.astype(np.float32),
                       -disp))

    batch_n = 2

    class Stream:
        def __iter__(self):
            for t in range(steps + 1):
                idx = np.random.default_rng(500 + t).integers(
                    0, len(scenes), batch_n)
                l, r, f = zip(*(scenes[i] for i in idx))
                yield {"image1": np.stack(l), "image2": np.stack(r),
                       "flow": np.stack(f),
                       "valid": np.ones((batch_n, h, w), np.float32)}

    tcfg = TrainConfig(batch_size=batch_n, train_iters=train_iters,
                       num_steps=steps, image_size=(h, w), lr=2e-4,
                       validation_frequency=10 ** 9, seed=3)
    with tempfile.TemporaryDirectory() as td:
        state = train(cfg, tcfg, name="early_exit", checkpoint_dir=td,
                      log_dir=os.path.join(td, "runs"), loader=Stream())
    return {"params": jax.device_get(state.params),
            "batch_stats": jax.device_get(state.batch_stats) or {}}


def init_variables(cfg):
    import jax
    import jax.numpy as jnp

    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    dummy = jnp.zeros((1, 32, 48, 3), jnp.float32)
    return RAFTStereo(cfg).init(jax.random.PRNGKey(0), dummy, dummy,
                                iters=1, test_mode=True)


def build_benchmarks(data_root: str, n: int, hw) -> None:
    from golden_data import (make_eth3d, make_kitti, make_middlebury,
                             make_things)

    rng = np.random.default_rng(7)
    make_eth3d(os.path.join(data_root, "ETH3D"), rng, n=n, hw=hw)
    make_kitti(os.path.join(data_root, "KITTI"), rng, n=n, hw=hw)
    make_things(data_root, rng, n=n, hw=hw)
    make_middlebury(os.path.join(data_root, "Middlebury"), rng, n=n,
                    hw=hw, split="H")


def run_validators(runner, data_root: str) -> dict:
    """All four real validators; returns {"<name>-epe": ..} merged."""
    from raft_stereo_tpu.eval.validate import (validate_eth3d,
                                               validate_kitti,
                                               validate_middlebury,
                                               validate_things)

    out = {}
    out.update(validate_eth3d(runner, root=os.path.join(data_root,
                                                        "ETH3D")))
    out.update(validate_kitti(runner, root=os.path.join(data_root,
                                                        "KITTI")))
    out.update(validate_things(runner, root=data_root))
    out.update(validate_middlebury(runner,
                                   root=os.path.join(data_root,
                                                     "Middlebury"),
                                   split="H"))
    return out


def sweep_row(cfg, variables, iters, data_root, threshold, min_iters,
              baseline_epe) -> dict:
    from raft_stereo_tpu.eval.runner import InferenceRunner

    runner = InferenceRunner(cfg, variables, iters=iters,
                             exit_threshold_px=threshold,
                             exit_min_iters=min_iters)
    metrics = run_validators(runner, data_root)
    depe = {v: round(metrics[f"{v}-epe"] - baseline_epe[v], 4)
            for v in VALIDATORS}
    mean_iters = runner.iters_used_mean()
    row = {
        "exit_threshold_px": threshold,
        "min_iters": min_iters,
        "mean_iters_used": round(mean_iters, 3),
        "iters_fraction_of_fixed": round(mean_iters / iters, 3),
        "epe": {v: round(metrics[f"{v}-epe"], 4) for v in VALIDATORS},
        "depe_vs_fixed": depe,
        "max_depe_px": max(depe.values()),
    }
    print(json.dumps({"early_exit_sweep": row}), flush=True)
    return row


def latency_bench(cfg, variables, iters, pairs, repeats: int,
                  settings) -> list:
    """Per-image latency per (tier name, threshold, min_iters) setting vs
    the fixed baseline (settings[0]), over the same pairs the validators
    scored."""
    from raft_stereo_tpu.eval.runner import InferenceRunner

    rows = []
    for name, threshold, min_iters in settings:
        runner = InferenceRunner(cfg, variables, iters=iters,
                                 exit_threshold_px=threshold,
                                 exit_min_iters=min_iters)
        runner(*pairs[0])                      # absorb the compile
        runner.reset_iters_used()
        secs = []
        for _ in range(repeats):
            for left, right in pairs:
                secs.append(runner(left, right)[1])
        secs = np.asarray(secs)
        rows.append({
            "tier": name,
            "exit_threshold_px": threshold,
            "min_iters": min_iters,
            "images": len(secs),
            "latency_ms": {
                "p50": round(float(np.percentile(secs, 50)) * 1e3, 2),
                "p95": round(float(np.percentile(secs, 95)) * 1e3, 2),
                "mean": round(float(secs.mean()) * 1e3, 2)},
            "mean_iters_used": (round(runner.iters_used_mean(), 3)
                                if runner.iters_used_mean() is not None
                                else float(iters)),
        })
        print(json.dumps({"tier_latency": rows[-1]}), flush=True)
    fixed_p50 = rows[0]["latency_ms"]["p50"]
    for row in rows[1:]:
        # A tier may tie fixed depth (quality IS fixed depth) but must
        # not regress past the noise band.
        if row["latency_ms"]["p50"] > 1.25 * fixed_p50:
            print(f"WARNING: tier {row['tier']} p50 "
                  f"{row['latency_ms']['p50']} ms regressed vs fixed "
                  f"{fixed_p50} ms", flush=True)
            row["regression_vs_fixed"] = True
    return rows


def eval_pairs(data_root: str) -> list:
    """The validator images as (left, right) pairs for the latency
    bench (one shape per benchmark — the runner buckets them)."""
    from raft_stereo_tpu.data import datasets as ds

    pairs = []
    for dataset in (ds.ETH3D(root=os.path.join(data_root, "ETH3D")),
                    ds.KITTI(root=os.path.join(data_root, "KITTI"))):
        for i in range(len(dataset)):
            s = dataset[i]
            pairs.append((s["image1"], s["image2"]))
    return pairs


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    hw = tuple(int(x) for x in args.hw.split("x"))
    train_hw = tuple(int(x) for x in args.train_hw.split("x"))
    thresholds = [float(t) for t in args.thresholds.split(",")]

    import jax

    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    from raft_stereo_tpu.config import REQUEST_TIERS
    from raft_stereo_tpu.eval.runner import InferenceRunner
    from raft_stereo_tpu.telemetry.events import bench_record, write_record

    cfg = model_config()
    t0 = time.perf_counter()
    if args.steps > 0:
        variables = trained_variables(cfg, args.steps, train_hw,
                                      args.train_iters)
    else:
        variables = init_variables(cfg)
    train_s = time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as work:
        data_root = os.path.join(work, "datasets")
        build_benchmarks(data_root, n=args.images, hw=hw)

        # --- fixed-depth baseline --------------------------------------
        fixed = InferenceRunner(cfg, variables, iters=args.iters)
        base_metrics = run_validators(fixed, data_root)
        baseline_epe = {v: base_metrics[f"{v}-epe"] for v in VALIDATORS}
        print(json.dumps({"fixed_baseline": {
            "iters": args.iters,
            "epe": {v: round(baseline_epe[v], 4) for v in VALIDATORS},
        }}), flush=True)

        # --- threshold sweep -------------------------------------------
        rows = [sweep_row(cfg, variables, args.iters, data_root, t,
                          args.min_iters, baseline_epe)
                for t in thresholds]

        # Operating point: loosest threshold within the EPE budget (rows
        # are loosest-first, so the first admissible row saves the most
        # iterations).
        admissible = [r for r in rows
                      if r["max_depe_px"] <= args.max_depe]
        chosen = admissible[0] if admissible else None
        meets_bar = bool(chosen
                         and chosen["iters_fraction_of_fixed"] <= 0.60)

        # --- per-tier latency vs fixed ---------------------------------
        # The production presets (config.REQUEST_TIERS thresholds target
        # fully-converged models) plus the interactive tier CALIBRATED to
        # this sweep's operating point — the row that demonstrates the
        # latency win on these weights.
        settings = [("fixed", None, None),
                    ("interactive", REQUEST_TIERS["interactive"]
                     .exit_threshold_px,
                     REQUEST_TIERS["interactive"].min_iters)]
        if chosen is not None:
            settings.append(
                ("interactive@calibrated",
                 chosen["exit_threshold_px"], args.min_iters))
        pairs = eval_pairs(data_root)
        latency = latency_bench(cfg, variables, args.iters, pairs,
                                args.lat_repeats, settings)

    # The headline latency statement: the calibrated interactive tier's
    # p50 win over fixed depth on the same pairs.
    lat_win = None
    calib = [r for r in latency if r["tier"] == "interactive@calibrated"]
    if calib:
        lat_win = round(latency[0]["latency_ms"]["p50"]
                        / calib[0]["latency_ms"]["p50"], 3)

    rec = bench_record({
        "metric": "early_exit_threshold_sweep",
        "value": (chosen["iters_fraction_of_fixed"] if chosen else None),
        "unit": f"mean iters_used / fixed depth ({args.iters}) at worst "
                f"validator dEPE <= {args.max_depe} px",
        "platform": jax.devices()[0].platform,
        "model_config": cfg.to_dict(),
        "fixed_iters": args.iters,
        "min_iters": args.min_iters,
        "train_steps": args.steps,
        "train_seconds": round(train_s, 1),
        "validators": list(VALIDATORS),
        "images_per_validator": args.images,
        "fixed_baseline_epe": {v: round(baseline_epe[v], 4)
                               for v in VALIDATORS},
        "sweep": rows,
        "chosen": chosen,
        "meets_60pct_bar": meets_bar,
        "tier_presets": {name: {"exit_threshold_px": t.exit_threshold_px,
                                "min_iters": t.min_iters}
                         for name, t in REQUEST_TIERS.items()},
        "tier_latency": latency,
        "interactive_calibrated_p50_speedup_vs_fixed": lat_win,
        "notes": "synthetic four-benchmark trees (tests/golden_data.py) "
                 "scored by the real validators on briefly-trained "
                 "weights; CPU numbers acceptable per ROADMAP (TPU "
                 "pending)",
    })
    out = args.out or os.path.join(_REPO, f"EARLY_EXIT_{args.tag}.json")
    write_record(out, rec, indent=1)
    print(json.dumps({"metric": "early_exit_threshold_sweep", "out": out,
                      "chosen": chosen, "meets_60pct_bar": meets_bar}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
