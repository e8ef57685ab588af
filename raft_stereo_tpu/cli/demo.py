"""Inference demo: stereo pairs → disparity images (reference: demo.py).

    python -m raft_stereo_tpu.cli.demo --restore_ckpt models/raftstereo-eth3d.pth \\
        -l 'datasets/ETH3D/two_view_training/*/im0.png' \\
        -r 'datasets/ETH3D/two_view_training/*/im1.png'

Saves ``<name>.png`` jet-colormapped disparity (and ``.npy`` with
``--save_numpy``) into ``--output_directory``, like the reference
(demo.py:46-50).
"""

from __future__ import annotations

import argparse
import glob
import logging
import os
import time

import numpy as np

from raft_stereo_tpu.cli import common

log = logging.getLogger(__name__)


def jet_colormap(x: np.ndarray) -> np.ndarray:
    """Normalized [0,1] → uint8 RGB using matplotlib's jet (with a NumPy
    fallback so the demo runs without matplotlib)."""
    try:
        from matplotlib import cm
        return (cm.jet(np.clip(x, 0, 1))[..., :3] * 255).astype(np.uint8)
    except ImportError:  # piecewise-linear jet approximation
        x = np.clip(x, 0, 1)
        r = np.clip(1.5 - np.abs(4 * x - 3), 0, 1)
        g = np.clip(1.5 - np.abs(4 * x - 2), 0, 1)
        b = np.clip(1.5 - np.abs(4 * x - 1), 0, 1)
        return (np.stack([r, g, b], -1) * 255).astype(np.uint8)


def run_demo(args) -> int:
    from PIL import Image

    from raft_stereo_tpu.data.frame_utils import read_image
    from raft_stereo_tpu.eval.runner import InferenceRunner

    cfg, variables = common.load_any_checkpoint(
        args.restore_ckpt, **common.arch_overrides(args))
    runner = InferenceRunner(cfg, variables, iters=args.valid_iters,
                             fetch_dtype=args.fetch_dtype,
                             exit_threshold_px=args.exit_threshold_px,
                             exit_min_iters=args.min_iters)

    out_dir = args.output_directory
    os.makedirs(out_dir, exist_ok=True)
    sequence = args.sequence is not None
    left_glob = (args.sequence if isinstance(args.sequence, str)
                 else args.left_imgs)
    left_images = sorted(glob.glob(left_glob, recursive=True))
    right_images = sorted(glob.glob(args.right_imgs, recursive=True))
    if len(left_images) != len(right_images) or not left_images:
        raise SystemExit(
            f"found {len(left_images)} left / {len(right_images)} right "
            "images — globs must match pairwise")
    log.info("found %d image pairs; writing to %s%s", len(left_images),
             out_dir, " (sequence mode: warm-start chaining)"
             if sequence else "")

    state = None                # previous frame's padded low-res flow
    t_seq = time.perf_counter()
    for idx, (left_path, right_path) in enumerate(zip(left_images,
                                                      right_images)):
        left, right = read_image(left_path), read_image(right_path)
        if sequence:
            # Frames are a temporally ordered sequence: warm-start the
            # GRU from the previous frame's disparity (RAFT's warm
            # start) and chain the state forward.  A resolution change
            # restarts cold, like a scene cut would on the server.
            try:
                frame = runner.run_stream(left, right,
                                          prev_flow_low=state)
            except ValueError:          # resolution changed mid-glob
                frame = runner.run_stream(left, right)
            # Keyframe guard (the serving engine's session_reseed_on_cap
            # policy): a warm frame that ran to the cap never satisfied
            # the convergence gate — drop the state so the next frame
            # cold-starts instead of chaining a drifting field.
            state = (None if (frame.warm and frame.iters_used is not None
                              and frame.iters_used >= args.valid_iters)
                     else frame.flow_low)
            disp = frame.disparity
        else:
            disp = runner.disparity(left, right)
            frame = None
        stem = os.path.splitext(os.path.basename(left_path))[0]
        if args.save_numpy:
            np.save(os.path.join(out_dir, f"{stem}.npy"), disp)
        vis = jet_colormap(disp / max(float(disp.max()), 1e-6))
        Image.fromarray(vis).save(os.path.join(out_dir,
                                               f"{stem}-disparity.png"))
        if sequence:
            fps = (idx + 1) / (time.perf_counter() - t_seq)
            log.info(
                "%s: frame %d %s iters_used %s/%d, cumulative %.2f FPS, "
                "disparity range [%.2f, %.2f]", stem, idx,
                "warm" if frame.warm else "cold",
                frame.iters_used if frame.iters_used is not None else "-",
                args.valid_iters, fps, disp.min(), disp.max())
        elif runner.last_iters_used is not None:
            log.info("%s: disparity range [%.2f, %.2f] (iters_used %d/%d)",
                     stem, disp.min(), disp.max(), runner.last_iters_used,
                     args.valid_iters)
        else:
            log.info("%s: disparity range [%.2f, %.2f]", stem, disp.min(),
                     disp.max())
    if sequence:
        wall = time.perf_counter() - t_seq
        log.info("sequence done: %d frames in %.2fs (%.2f FPS)%s",
                 len(left_images), wall, len(left_images) / wall,
                 (f", mean iters_used {runner.iters_used_mean():.2f} "
                  f"of {args.valid_iters}"
                  if runner.iters_used_mean() is not None else ""))
    elif runner.iters_used_mean() is not None:
        log.info("adaptive early exit: mean iters_used %.2f of %d "
                 "(threshold %.4g px, min %d)", runner.iters_used_mean(),
                 args.valid_iters, args.exit_threshold_px or 0.0,
                 args.min_iters or 1)
    return len(left_images)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--restore_ckpt", required=True,
                   help=".pth or orbax checkpoint directory")
    p.add_argument("-l", "--left_imgs", required=True,
                   help="glob for left (im0) images")
    p.add_argument("-r", "--right_imgs", required=True,
                   help="glob for right (im1) images")
    p.add_argument("--output_directory", default="demo_output")
    p.add_argument("--sequence", nargs="?", const=True, default=None,
                   metavar="GLOB",
                   help="treat the frames as a temporally ORDERED video "
                        "sequence: each frame warm-starts the GRU from "
                        "the previous frame's disparity (RAFT's warm "
                        "start) and logs per-frame iters_used + "
                        "cumulative FPS.  The optional GLOB overrides "
                        "--left_imgs.  Combine with --exit_threshold_px "
                        "so warm frames actually exit earlier")
    p.add_argument("--save_numpy", action="store_true")
    p.add_argument("--valid_iters", type=int, default=32)
    p.add_argument("--exit_threshold_px", type=float, default=None,
                   help="adaptive GRU early exit: stop refining once the "
                        "mean |Δdisparity| per iteration falls below this "
                        "(px at feature resolution; --valid_iters becomes "
                        "the cap and each image logs its iters_used). "
                        "<= 0 or unset keeps the fixed-depth loop")
    p.add_argument("--min_iters", type=int, default=None,
                   help="iterations that always run before the early-exit "
                        "threshold may fire (default 1)")
    p.add_argument("--fetch_dtype", default=None,
                   choices=["fp16", "bf16"],
                   help="half-precision device->host disparity fetch "
                        "(halves the down-leg bytes; results stay f32 — "
                        "eval/runner.py; fp16 ulp <= 0.125 px at |d|<256)")
    common.add_arch_overrides(p)
    return p


def main(argv=None):
    common.setup_logging()
    args = build_parser().parse_args(argv)
    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()
    run_demo(args)


if __name__ == "__main__":
    main()
