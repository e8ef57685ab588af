"""Benchmark evaluation CLI (reference: evaluate_stereo.py:192-242).

    python -m raft_stereo_tpu.cli.evaluate --restore_ckpt models/raftstereo-eth3d.pth \\
        --dataset eth3d

Datasets: eth3d | kitti | things | middlebury_F | middlebury_H | middlebury_Q.
KITTI additionally reports the FPS protocol (warmup-discarded).
"""

from __future__ import annotations

import argparse
import json
import logging

from raft_stereo_tpu.cli import common

log = logging.getLogger(__name__)


def run_eval(args) -> dict:
    from raft_stereo_tpu.eval import (InferenceRunner, sequence_drift,
                                      validate_eth3d, validate_kitti,
                                      validate_middlebury, validate_things)

    overrides = common.arch_overrides(args)
    # mirror the reference: bf16 lookup is safe only for the fused corr
    # backend (evaluate_stereo.py:227-230)
    cfg, variables = common.load_any_checkpoint(args.restore_ckpt, **overrides)
    log.info("model config: %s", cfg.to_dict())
    runner = InferenceRunner(cfg, variables, iters=args.valid_iters,
                             fetch_dtype=args.fetch_dtype,
                             exit_threshold_px=args.exit_threshold_px,
                             exit_min_iters=args.min_iters)

    root = args.data_root
    if args.sequence:
        # Sequence mode (round 14 streaming sessions): the dataset's
        # frames run in order twice — cold per-frame vs warm-start
        # chained — and the row reports the EPE drift + iters/FPS split
        # (eval/validate.sequence_drift).  --stream_out records the row
        # as a versioned bench JSON (telemetry/events.bench_record), a
        # product of the run.
        from raft_stereo_tpu.data import datasets as ds

        if args.dataset == "eth3d":
            dataset = ds.ETH3D(root=f"{root}/ETH3D")
        elif args.dataset == "kitti":
            dataset = ds.KITTI(root=f"{root}/KITTI")
        elif args.dataset == "things":
            dataset = ds.SceneFlow(root=root, dstype="frames_finalpass",
                                   things_test=True)
        elif args.dataset.startswith("middlebury_"):
            dataset = ds.Middlebury(
                root=f"{root}/Middlebury",
                split=args.dataset.removeprefix("middlebury_"))
        else:
            raise SystemExit(f"unknown dataset {args.dataset!r}")
        results = sequence_drift(runner, dataset, args.dataset,
                                 max_images=args.max_images)
        if args.stream_out:
            from raft_stereo_tpu.telemetry.events import (bench_record,
                                                          write_record)
            write_record(args.stream_out, bench_record({
                "metric": "warm_start_sequence_drift",
                "value": results[f"{args.dataset}-warm-drift-epe"],
                "unit": "EPE(warm chained) - EPE(cold per-frame), px",
                "dataset": args.dataset,
                "valid_iters": args.valid_iters,
                "exit_threshold_px": args.exit_threshold_px,
                "min_iters": args.min_iters,
                "results": {k: round(v, 5) for k, v in results.items()},
            }), indent=1)
            log.info("sequence-drift record -> %s", args.stream_out)
        return results
    if args.dataset == "eth3d":
        results = validate_eth3d(runner, root=f"{root}/ETH3D",
                                 max_images=args.max_images)
    elif args.dataset == "kitti":
        results = validate_kitti(runner, root=f"{root}/KITTI",
                                 max_images=args.max_images)
    elif args.dataset == "things":
        results = validate_things(runner, root=root,
                                  max_images=args.max_images)
    elif args.dataset.startswith("middlebury_"):
        results = validate_middlebury(runner, root=f"{root}/Middlebury",
                                      split=args.dataset.removeprefix(
                                          "middlebury_"),
                                      max_images=args.max_images)
    else:
        raise SystemExit(f"unknown dataset {args.dataset!r}")
    if runner.iters_used_mean() is not None:
        # The accuracy/latency knob, visible outside the server: the mean
        # GRU trip count the convergence gate actually ran.
        results[f"{args.dataset}-iters-used-mean"] = round(
            runner.iters_used_mean(), 3)
        print(f"Adaptive early exit: mean iters_used "
              f"{runner.iters_used_mean():.2f} of {args.valid_iters} "
              f"(threshold {args.exit_threshold_px} px)")
    return results


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--restore_ckpt", required=True)
    p.add_argument("--dataset", required=True,
                   choices=["eth3d", "kitti", "things", "middlebury_F",
                            "middlebury_H", "middlebury_Q"])
    p.add_argument("--data_root", default="datasets")
    p.add_argument("--valid_iters", type=int, default=32,
                   help="GRU iterations (reference: --valid_iters); the "
                        "depth CAP when --exit_threshold_px is set")
    p.add_argument("--exit_threshold_px", type=float, default=None,
                   help="adaptive GRU early exit: stop refining once the "
                        "mean |Δdisparity| per iteration falls below this "
                        "(px at feature resolution); the result row gains "
                        "the mean iters_used.  <= 0 or unset keeps the "
                        "reference's fixed-depth loop")
    p.add_argument("--min_iters", type=int, default=None,
                   help="iterations that always run before the early-exit "
                        "threshold may fire (default 1)")
    p.add_argument("--fetch_dtype", default=None,
                   choices=["fp16", "bf16"],
                   help="half-precision device->host disparity fetch "
                        "(halves the down-leg bytes; results stay f32 — "
                        "eval/runner.py; fp16 ulp <= 0.125 px at |d|<256)")
    p.add_argument("--max_images", type=int, default=None,
                   help="evaluate only the first N images (smoke runs)")
    p.add_argument("--sequence", action="store_true",
                   help="sequence mode: run the dataset's frames in "
                        "order twice — cold per-frame vs warm-start "
                        "chained (each frame's GRU seeded from the "
                        "previous frame's disparity) — and report the "
                        "warm-start EPE drift plus per-pass iters/FPS")
    p.add_argument("--stream_out", default=None,
                   help="with --sequence: write the drift row as a "
                        "versioned bench JSON")
    p.add_argument("--json", action="store_true",
                   help="print results as one JSON line")
    common.add_arch_overrides(p)
    return p


def main(argv=None):
    common.setup_logging()
    args = build_parser().parse_args(argv)
    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()
    results = run_eval(args)
    if args.json:
        print(json.dumps(results))
    return results


if __name__ == "__main__":
    main()
