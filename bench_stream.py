#!/usr/bin/env python
"""Streaming stereo bench: steady-state warm-session FPS vs cold
per-frame FPS, plus the warm-start EPE drift that bounds the win.

The round-14 streaming sessions exist for exactly one claim: on a
temporally coherent sequence, seeding the GRU from the previous frame's
disparity (RAFT's warm start, arXiv 2109.07547 §3) lets the round-12
convergence gate stall after a FRACTION of the cold iterations — so
steady-state video FPS beats cold per-frame FPS via reduced
``iters_used``, not via a different program.  This bench measures that
claim end to end and writes the record the acceptance bar reads
(``STREAM_<tag>.json``):

1. brief-train the hermetic tiny architecture (tools/early_exit_report's
   exact recipe — an untrained GRU's update magnitudes are meaningless,
   so its convergence gate is too);
2. synthesize a VIDEO: a textured scene with known disparity, panned a
   few pixels per frame (``np.roll`` keeps the ground truth exact), with
   an optional hard scene cut in the middle;
3. runner-level measurement (``InferenceRunner.run_stream``): the same
   early-exit runner does a cold pass (every frame zero-init — the
   stateless baseline any per-frame client gets) and a warm pass (state
   chained frame to frame).  Reported: per-pass FPS, mean ``iters_used``,
   EPE vs ground truth, and the warm−cold EPE drift per frame;
4. engine-level measurement: the same frames through
   ``ServingEngine.submit_session`` (the full session/queue/dispatch
   path) vs stateless ``submit`` — the number a video client actually
   sees at the HTTP door;
5. the four synthetic validators run through
   ``eval.validate.sequence_drift`` (the evaluate.py --sequence mode) —
   warm-start drift on NON-sequence frames, i.e. the adversarial bound
   the scene-cut fallback protects.

Acceptance (ISSUE 9): steady-state warm FPS >= 1.5x cold per-frame FPS
on CPU, drift bounded and reported.  The bench prints the bar verdict
and records ``meets_1_5x_bar``.

Streaming v2 (round 19 / ISSUE 14) adds two measurement axes:

* **warm-h rows + gate sweep** — the ``warm_h`` mode chains the GRU
  hidden-state tree alongside the disparity (``run_stream
  prev_hidden``), and ``--gate_sweep`` re-runs warm-flow-only vs warm-h
  chains at tightening exit thresholds, answering STREAM_r14's open
  question: cold-h was hypothesized to be why gates below the 2.0 px
  floor diverged — the sweep records per-gate mean iters, EPE drift,
  and cap-hit (keyframe-guard) rates for both state policies.
* **--slo_ms** — the serving-capacity mode: N concurrent sessions drive
  the engine (sessions + session_hidden + the EDF bounded-slack
  scheduler) at one frame per SLO period each, and the bench reports
  **streams-per-device at the deadline** (the largest N whose p99
  per-frame latency meets the SLO at <= 5% misses), the
  dispatches-vs-frames coalescing ratio, and per-frame p50/p99 —
  the capacity number that actually describes serving video.

Run from the repo root (CPU fine; ~2-4 min at the defaults):

    JAX_PLATFORMS=cpu python bench_stream.py
    JAX_PLATFORMS=cpu python bench_stream.py --slo_ms 400 --streams 1,2,4
    JAX_PLATFORMS=cpu python bench_stream.py --steps 40 --frames 10 \\
        --out /tmp/STREAM_smoke.json                       # smoke
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tests"))
sys.path.insert(0, os.path.join(_REPO, "tools"))

DEFAULT_TAG = "r19"
# Warm-path regression guard: warn when this run's warm/fixed speedup
# falls below r14's published number by more than the CPU noise band
# (the bench.py REGRESSION_FACTOR rationale).
R14_BASELINE = "STREAM_r14.json"
REGRESSION_FACTOR = 0.90


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--frames", type=int, default=12,
                   help="video frames per measured pass")
    p.add_argument("--hw", default="96x128", help="frame size HxW")
    p.add_argument("--pan_px", type=int, default=2,
                   help="horizontal camera pan per frame (px)")
    p.add_argument("--scene_cut_at", type=int, default=-1,
                   help="inject a hard scene cut at this frame index "
                        "(< 0 disables — the default measures a clean "
                        "coherent stream)")
    p.add_argument("--iters", type=int, default=16,
                   help="GRU depth cap; also the FIXED depth of the "
                        "cold per-frame baseline row (the stateless "
                        "quality protocol; the repo CLIs default to 32)")
    p.add_argument("--threshold", type=float, default=2.0,
                   help="exit_threshold_px for the gated rows.  2.0 px "
                        "is the stable operating point for warm-start "
                        "CHAINING on these briefly-trained weights: "
                        "tighter gates (0.3-1.0) make the weakly-"
                        "trained GRU run LONGER from a warm init, not "
                        "shorter (measured; see notes in the record) — "
                        "production thresholds on converged checkpoints "
                        "sit far tighter")
    p.add_argument("--min_iters", type=int, default=1,
                   help="early-exit floor — warm frames bottom out here")
    p.add_argument("--steps", type=int, default=200,
                   help="brief-training steps before measuring")
    p.add_argument("--train_hw", default="32x48")
    p.add_argument("--train_iters", type=int, default=4)
    p.add_argument("--repeats", type=int, default=3,
                   help="timed passes per mode (FPS = best pass, the "
                        "bench.py convention for CPU noise)")
    p.add_argument("--validator_images", type=int, default=3,
                   help="images per synthetic validator tree for the "
                        "sequence_drift rows")
    p.add_argument("--skip_engine", action="store_true",
                   help="skip the engine-level session measurement")
    p.add_argument("--skip_validators", action="store_true",
                   help="skip the synthetic-validator drift rows")
    p.add_argument("--gate_sweep", default="0.75,1.25,2.0",
                   help="comma list of exit thresholds (px) for the "
                        "warm-flow-only vs warm-h chaining-stability "
                        "sweep — includes gates BELOW the 2.0 px floor "
                        "STREAM_r14 recorded as divergent for cold-h "
                        "chains; empty string skips the sweep")
    p.add_argument("--slo_ms", type=float, default=None,
                   help="per-frame deadline (ms) for the streams-per-"
                        "device capacity mode: N concurrent sessions "
                        "each send one frame per SLO period through the "
                        "EDF engine; None skips the mode")
    p.add_argument("--streams", default="1,2,4",
                   help="stream counts swept by --slo_ms")
    p.add_argument("--slo_frames", type=int, default=10,
                   help="frames per stream per --slo_ms sweep point")
    p.add_argument("--slo_batch_sizes", default="1,2,4,8",
                   help="engine batch ladder for the --slo_ms mode")
    p.add_argument("--tag", default=DEFAULT_TAG)
    p.add_argument("--out", default=None,
                   help="output path; default STREAM_<tag>.json")
    return p


def make_video(rng, n_frames: int, hw, pan_px: int, cut_at):
    """A synthetic stereo video with exact ground truth: one textured
    scene + disparity field panned ``pan_px`` px/frame (np.roll keeps
    the warp geometry exact), with an optional hard scene cut (a fresh
    scene) at ``cut_at``.  Returns [(left, right, gt_flow)]."""
    from golden_data import disparity_field, textured_image, warp_right

    h, w = hw
    frames = []
    scenes = [(textured_image(rng, h, w), disparity_field(rng, h, w))]
    if cut_at is not None and 0 < cut_at < n_frames:
        scenes.append((textured_image(rng, h, w),
                       disparity_field(rng, h, w)))
    for t in range(n_frames):
        scene = scenes[-1] if (cut_at is not None and 0 < cut_at <= t) \
            else scenes[0]
        base_t = t - cut_at if (cut_at is not None and 0 < cut_at <= t) \
            else t
        left = np.roll(scene[0], -pan_px * base_t, axis=1)
        disp = np.roll(scene[1], -pan_px * base_t, axis=1)
        right = warp_right(left, disp)
        frames.append((left.astype(np.uint8), right.astype(np.uint8),
                       -disp.astype(np.float32)))
    return frames


def _epe(flow_pr, flow_gt) -> float:
    return float(np.mean(np.abs(flow_pr - flow_gt)))


def runner_pass(runner, frames, warm: bool, cap: int,
                hidden: bool = False):
    """One pass over the video: returns (seconds list, iters list,
    per-frame EPE list, cap-hit count).  Warm chains the state with the
    keyframe guard (a warm frame that ran to the cap drops its state —
    the serving engine's ``session_reseed_on_cap`` policy); cold
    zero-inits every frame.  ``hidden`` additionally chains the GRU
    hidden-state tree (the round-19 warm-h path).  Frame timings use
    the runner's own fetch-stop clock."""
    runner.reset_iters_used()
    state, htree = None, None
    secs, iters, epes = [], [], []
    cap_hits = 0
    for left, right, gt in frames:
        frame = runner.run_stream(
            left, right,
            prev_flow_low=state if warm else None,
            prev_hidden=htree if (warm and hidden) else None,
            carry_hidden=hidden)
        if warm:
            if (frame.warm and frame.iters_used is not None
                    and frame.iters_used >= cap):
                cap_hits += 1
                state, htree = None, None
            else:
                state, htree = frame.flow_low, frame.hidden
        secs.append(frame.seconds)
        iters.append(frame.iters_used if frame.iters_used is not None
                     else cap)
        epes.append(_epe(frame.flow, gt))
    return secs, iters, epes, cap_hits


def measure_runner(cfg, variables, frames, args) -> dict:
    """The headline table, three rows over the same video:

    * ``fixed`` — the stateless per-frame protocol: fixed GRU depth
      ``--iters``, zero init every frame (what every repo CLI and the
      serving quality tier run today) — the COLD PER-FRAME baseline;
    * ``cold_gated`` — the round-12 convergence gate, still zero init
      every frame (stateless early exit — the intermediate point);
    * ``warm`` — streaming sessions: gate + disparity chained frame to
      frame with the keyframe guard (the r14 flow-only warm start);
    * ``warm_h`` — round 19: disparity AND the GRU hidden-state tree
      chained (the warm-h program) — the row that answers whether
      carrying the trajectory beats re-deriving it every frame.

    FPS is the best of ``--repeats`` steady-state passes (programs
    precompiled before the clock starts)."""
    from raft_stereo_tpu.eval.runner import InferenceRunner

    fixed = InferenceRunner(cfg, variables, iters=args.iters)
    gated = InferenceRunner(cfg, variables, iters=args.iters,
                            exit_threshold_px=args.threshold,
                            exit_min_iters=args.min_iters)
    # Absorb every program compile (fixed, gated-cold, gated-warm,
    # gated-warm-h).
    for r in (fixed, gated):
        c0 = r.run_stream(frames[0][0], frames[0][1])
        r.run_stream(frames[0][0], frames[0][1],
                     prev_flow_low=np.zeros_like(c0.flow_low))
    ch = gated.run_stream(frames[0][0], frames[0][1], carry_hidden=True)
    gated.run_stream(frames[0][0], frames[0][1],
                     prev_flow_low=np.zeros_like(ch.flow_low),
                     prev_hidden=ch.hidden)

    modes = {"fixed": (fixed, False, False),
             "cold_gated": (gated, False, False),
             "warm": (gated, True, False),
             "warm_h": (gated, True, True)}
    rows, per_frame = {}, {}
    for mode, (runner, warm, hidden) in modes.items():
        best = None
        for _ in range(max(1, args.repeats)):
            secs, iters, epes, cap_hits = runner_pass(
                runner, frames, warm, args.iters, hidden=hidden)
            fps = len(secs) / sum(secs)
            if best is None or fps > best[0]:
                best = (fps, secs, iters, epes, cap_hits)
        fps, secs, iters, epes, cap_hits = best
        per_frame[mode] = {"iters": iters, "epe": epes}
        rows[mode] = {
            "fps": round(fps, 3),
            "mean_ms_per_frame": round(1e3 * float(np.mean(secs)), 2),
            "mean_iters_used": round(float(np.mean(iters)), 3),
            "per_frame_iters": iters,
            "epe_mean": round(float(np.mean(epes)), 4),
            "epe_max": round(float(np.max(epes)), 4),
            "cap_hits": cap_hits,
        }
        print(json.dumps({f"runner_{mode}": rows[mode]}), flush=True)
    for warm_mode in ("warm", "warm_h"):
        for base in ("fixed", "cold_gated"):
            drift = [w - c for w, c in zip(per_frame[warm_mode]["epe"],
                                           per_frame[base]["epe"])]
            tag = ("warm_drift_epe_vs_" + base if warm_mode == "warm"
                   else f"{warm_mode}_drift_epe_vs_{base}")
            rows[tag] = {
                "mean": round(float(np.mean(drift)), 4),
                "max": round(float(np.max(drift)), 4),
                "per_frame": [round(d, 4) for d in drift],
            }
    # The acceptance ratio: warm sessions vs the cold per-frame
    # fixed-depth protocol (the win is reduced iters_used through the
    # same gate — cold_gated is reported so the two mechanisms' shares
    # are separable).
    rows["speedup"] = round(rows["warm"]["fps"] / rows["fixed"]["fps"], 3)
    rows["speedup_vs_cold_gated"] = round(
        rows["warm"]["fps"] / rows["cold_gated"]["fps"], 3)
    rows["iters_fraction"] = round(
        rows["warm"]["mean_iters_used"]
        / rows["fixed"]["mean_iters_used"], 3)
    rows["speedup_warm_h"] = round(
        rows["warm_h"]["fps"] / rows["fixed"]["fps"], 3)
    rows["warm_h_vs_warm_iters"] = round(
        rows["warm_h"]["mean_iters_used"]
        / max(rows["warm"]["mean_iters_used"], 1e-9), 3)
    return rows


def gate_sweep(cfg, variables, frames, args) -> list:
    """The STREAM_r14 open question, measured: at each exit threshold
    (including gates BELOW the 2.0 px floor r14 recorded as divergent),
    chain the same video warm-flow-only vs warm-h and record mean
    iters, EPE drift vs the fixed-depth baseline, and how often the
    keyframe guard tripped (cap hits = the chain was NOT trusted).  A
    gate is called STABLE for a policy when its chain never trips the
    guard and its mean EPE stays within 0.5 px of the fixed-depth
    protocol's."""
    from raft_stereo_tpu.eval.runner import InferenceRunner

    gates = [float(g) for g in args.gate_sweep.split(",") if g.strip()]
    if not gates:
        return []
    fixed = InferenceRunner(cfg, variables, iters=args.iters)
    _, _, fixed_epes, _ = runner_pass(fixed, frames, warm=False,
                                      cap=args.iters)
    fixed_epe = float(np.mean(fixed_epes))
    rows = []
    for gate in gates:
        runner = InferenceRunner(cfg, variables, iters=args.iters,
                                 exit_threshold_px=gate,
                                 exit_min_iters=args.min_iters)
        row = {"gate_px": gate}
        for mode, hidden in (("warm_flow_only", False),
                             ("warm_h", True)):
            _, iters, epes, cap_hits = runner_pass(
                runner, frames, warm=True, cap=args.iters,
                hidden=hidden)
            drift = float(np.mean(epes)) - fixed_epe
            row[mode] = {
                "mean_iters_used": round(float(np.mean(iters)), 3),
                "epe_mean": round(float(np.mean(epes)), 4),
                "epe_drift_vs_fixed": round(drift, 4),
                "cap_hits": cap_hits,
                "stable": bool(cap_hits == 0 and drift <= 0.5),
            }
        print(json.dumps({"gate_sweep": row}), flush=True)
        rows.append(row)
    return rows


def measure_slo(cfg, variables, args) -> dict:
    """Streams-per-device at a real-time deadline: N concurrent
    sessions drive the EDF engine (sessions + session_hidden + the
    bounded-slack scheduler), each sending one frame per SLO period
    with ``deadline_ms`` = the SLO.  Per stream count: per-frame
    p50/p99 (scheduled-send to answer, so a stream falling behind its
    period shows up as latency, the open-loop convention), deadline
    miss rate, and the dispatches-vs-frames coalescing ratio.  The
    headline ``streams_per_device`` is the largest swept N whose p99
    meets the SLO at <= 5% misses, divided by the device count (1 on
    this bench).  A policy-off comparison row at the largest N
    isolates what the EDF coalescing itself buys."""
    import threading

    from raft_stereo_tpu.serving import ServeConfig, StereoService

    slo_s = args.slo_ms / 1e3
    stream_counts = [int(n) for n in args.streams.split(",")]
    sizes = tuple(int(s) for s in args.slo_batch_sizes.split(","))
    tier = f"stream:{args.threshold}:{args.min_iters}"
    rng = np.random.default_rng(23)

    def run_point(n_streams: int, edf: bool) -> dict:
        frames = make_video(rng, args.slo_frames + 1, hw_tuple,
                            args.pan_px, None)
        svc_cfg = ServeConfig(
            max_batch=max(sizes), batch_sizes=sizes, iters=args.iters,
            max_queue=max(64, 4 * n_streams),
            sessions=True, session_hidden=True, session_ttl_s=600.0,
            edf_scheduler=edf, edf_max_slack_ms=min(
                50.0, args.slo_ms / 4),
            tiers=(tier, "quality"), default_tier="quality",
            warmup_shapes=(hw_tuple,))
        latencies, misses = [], [0]
        lock = threading.Lock()
        with StereoService(cfg, variables, svc_cfg) as svc:
            # absorb session-family compiles outside the clock
            svc.infer_session("warmup", *frames[0][:2], tier="stream",
                              timeout=600)
            svc.infer_session("warmup", *frames[1][:2], tier="stream",
                              timeout=600)
            barrier = threading.Barrier(n_streams)

            def stream(sid: str):
                barrier.wait()
                t0 = time.perf_counter()
                for i, (left, right, _gt) in enumerate(frames):
                    target = t0 + i * slo_s
                    delay = target - time.perf_counter()
                    if delay > 0:
                        time.sleep(delay)
                    try:
                        svc.infer_session(
                            sid, left, right, tier="stream",
                            deadline_ms=args.slo_ms, timeout=600)
                        lat = time.perf_counter() - target
                        with lock:
                            latencies.append(lat)
                            if lat > slo_s:
                                misses[0] += 1
                    except Exception:
                        with lock:
                            misses[0] += 1

            threads = [threading.Thread(target=stream,
                                        args=(f"cam{j}",), daemon=True)
                       for j in range(n_streams)]
            d0 = svc.metrics.batches.value
            f0 = svc.metrics.session_frames("warm") \
                + svc.metrics.session_frames("cold")
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            dispatches = svc.metrics.batches.value - d0
            frames_done = (svc.metrics.session_frames("warm")
                           + svc.metrics.session_frames("cold")) - f0
            slack_waits = svc.metrics.edf_slack_waits.value
        lat = np.array(sorted(latencies)) if latencies else np.array([0.0])
        total = n_streams * len(frames)
        row = {
            "streams": n_streams, "edf": edf,
            "frames_total": total,
            "frames_completed": len(latencies),
            "dispatches": int(dispatches),
            "coalescing_ratio": round(
                frames_done / max(1, dispatches), 3),
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 1),
            "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 1),
            "miss_rate": round(misses[0] / max(1, total), 3),
            "meets_slo": bool(
                float(np.percentile(lat, 99)) <= slo_s
                and misses[0] / max(1, total) <= 0.05),
            "edf_slack_waits": int(slack_waits),
        }
        print(json.dumps({"slo_point": row}), flush=True)
        return row

    hw_tuple = tuple(int(x) for x in args.hw.split("x"))
    rows = [run_point(n, edf=True) for n in stream_counts]
    off_row = run_point(stream_counts[-1], edf=False)
    passing = [r["streams"] for r in rows if r["meets_slo"]]
    import jax
    n_dev = len(jax.devices())
    return {
        "slo_ms": args.slo_ms,
        "frames_per_stream": args.slo_frames + 1,
        "batch_sizes": list(sizes),
        "points": rows,
        "edf_off_comparison": off_row,
        "streams_meeting_slo": max(passing) if passing else 0,
        "streams_per_device": round(
            (max(passing) if passing else 0) / n_dev, 2),
        "devices": n_dev,
    }


def measure_engine(cfg, variables, frames, args) -> dict:
    """The same video through the full serving stack: stateless
    ``submit`` at the quality tier (the fixed-depth cold per-frame
    protocol — what a sessionless video client gets today) vs
    ``submit_session`` at the gated stream tier — queue, dispatch,
    session bookkeeping and all."""
    from raft_stereo_tpu.serving import ServeConfig, StereoService

    tier = f"stream:{args.threshold}:{args.min_iters}"
    hw = frames[0][0].shape[:2]
    out = {}
    with StereoService(cfg, variables, ServeConfig(
            max_batch=1, batch_sizes=(1,), iters=args.iters,
            sessions=True, session_ttl_s=600.0,
            tiers=(tier, "quality"), default_tier="quality",
            warmup_shapes=(hw,))) as svc:
        # steady state: warm-up frame 0 of each mode outside the clock
        svc.infer(frames[0][0], frames[0][1], timeout=600)
        t0 = time.perf_counter()
        for left, right, _ in frames:
            svc.infer(left, right, timeout=600)      # quality tier, cold
        cold_s = time.perf_counter() - t0
        svc.infer_session("bench", frames[0][0], frames[0][1],
                          tier="stream", timeout=600)
        t0 = time.perf_counter()
        results = [svc.infer_session("bench", left, right, tier="stream",
                                     timeout=600)
                   for left, right, _ in frames]
        warm_s = time.perf_counter() - t0
        out = {
            "cold_fps": round(len(frames) / cold_s, 3),
            "warm_fps": round(len(frames) / warm_s, 3),
            "speedup": round(cold_s / warm_s, 3),
            "warm_frames": sum(1 for r in results if r.warm),
            "scene_cut_frames": sum(1 for r in results if r.scene_cut),
            "reseeds": svc.metrics.session_reseeds.value,
            "mean_iters_warm": round(float(np.mean(
                [r.iters_used for r in results])), 3),
            "session_stats": svc.close_session("bench"),
        }
    print(json.dumps({"engine_sessions": out}), flush=True)
    return out


def validator_drift(cfg, variables, args) -> dict:
    """evaluate.py --sequence over the four synthetic validator trees:
    warm-start drift on UNRELATED consecutive frames — the adversarial
    bound (tools/early_exit_report builds the same trees)."""
    import tempfile

    from early_exit_report import VALIDATORS, build_benchmarks
    from raft_stereo_tpu.data import datasets as ds
    from raft_stereo_tpu.eval.runner import InferenceRunner
    from raft_stereo_tpu.eval.validate import sequence_drift

    hw = tuple(int(x) for x in args.hw.split("x"))
    runner = InferenceRunner(cfg, variables, iters=args.iters,
                             exit_threshold_px=args.threshold,
                             exit_min_iters=args.min_iters)
    rows = {}
    with tempfile.TemporaryDirectory() as work:
        root = os.path.join(work, "datasets")
        build_benchmarks(root, n=args.validator_images, hw=hw)
        datasets = {
            "eth3d": ds.ETH3D(root=os.path.join(root, "ETH3D")),
            "kitti": ds.KITTI(root=os.path.join(root, "KITTI")),
            "things": ds.SceneFlow(root=root, dstype="frames_finalpass",
                                   things_test=True),
            "middleburyH": ds.Middlebury(
                root=os.path.join(root, "Middlebury"), split="H"),
        }
        for name in VALIDATORS:
            rows[name] = {
                k: round(v, 4) for k, v in
                sequence_drift(runner, datasets[name], name).items()}
    return rows


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    hw = tuple(int(x) for x in args.hw.split("x"))
    train_hw = tuple(int(x) for x in args.train_hw.split("x"))
    cut_at = (args.frames // 2 if args.scene_cut_at is None
              else (None if args.scene_cut_at < 0 else args.scene_cut_at))

    import jax

    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    from early_exit_report import (init_variables, model_config,
                                   trained_variables)
    from raft_stereo_tpu.telemetry.events import bench_record, write_record

    cfg = model_config()
    t0 = time.perf_counter()
    if args.steps > 0:
        variables = trained_variables(cfg, args.steps, train_hw,
                                      args.train_iters)
    else:
        variables = init_variables(cfg)
    train_s = time.perf_counter() - t0

    rng = np.random.default_rng(17)
    frames = make_video(rng, args.frames, hw, args.pan_px, cut_at)

    runner_rows = measure_runner(cfg, variables, frames, args)
    gate_rows = gate_sweep(cfg, variables, frames, args)
    engine_rows = (None if args.skip_engine
                   else measure_engine(cfg, variables, frames, args))
    validator_rows = (None if args.skip_validators
                      else validator_drift(cfg, variables, args))
    slo_rows = (None if args.slo_ms is None
                else measure_slo(cfg, variables, args))

    meets_bar = runner_rows["speedup"] >= 1.5
    if not meets_bar:
        print(f"WARNING: warm/cold FPS ratio {runner_rows['speedup']} "
              f"< 1.5x acceptance bar", flush=True)

    # Warn-on-regression vs the r14 warm-path record (same protocol:
    # warm flow-only FPS / fixed-depth cold FPS).
    r14_path = os.path.join(_REPO, R14_BASELINE)
    r14_speedup = None
    if os.path.exists(r14_path):
        with open(r14_path) as f:
            r14_speedup = json.load(f).get("value")
        if (r14_speedup
                and runner_rows["speedup"]
                < REGRESSION_FACTOR * r14_speedup):
            print(f"WARNING: warm-path regression vs {R14_BASELINE}: "
                  f"speedup {runner_rows['speedup']} < "
                  f"{REGRESSION_FACTOR} x r14's {r14_speedup}",
                  flush=True)

    rec = bench_record({
        "metric": "stream_warm_vs_cold_fps",
        "value": runner_rows["speedup"],
        "unit": f"steady-state warm-session FPS / cold per-frame "
                f"fixed-depth FPS ({hw[0]}x{hw[1]}, depth {args.iters}, "
                f"gate {args.threshold} px, CPU)",
        "platform": jax.devices()[0].platform,
        "model_config": cfg.to_dict(),
        "frames": args.frames,
        "pan_px": args.pan_px,
        "scene_cut_at": cut_at,
        "iters_cap": args.iters,
        "exit_threshold_px": args.threshold,
        "min_iters": args.min_iters,
        "train_steps": args.steps,
        "train_seconds": round(train_s, 1),
        "runner": runner_rows,
        "gate_sweep": gate_rows,
        "engine_sessions": engine_rows,
        "validator_sequence_drift": validator_rows,
        "slo": slo_rows,
        "r14_baseline_speedup": r14_speedup,
        "meets_1_5x_bar": meets_bar,
        "notes": "synthetic panned-scene video with exact ground truth "
                 "(tests/golden_data.py geometry) on briefly-trained "
                 "weights; CPU numbers acceptable per ROADMAP (TPU "
                 "pending).  The warm win is reduced iters_used through "
                 "the round-12 convergence gate, not a different "
                 "program — cold-frame outputs are bitwise-pinned to "
                 "the sessionless path by tests/test_sessions.py; "
                 "hidden-off and EDF-off paths are pinned to the r14 "
                 "programs/scheduler by tests/test_sessions.py and "
                 "tests/test_edf.py.  Round 19: warm_h rows chain the "
                 "GRU hidden state alongside the disparity (the half "
                 "of the temporal state r14 left cold) and the "
                 "gate_sweep section answers whether chaining holds "
                 "below the 2.0 px floor r14 recorded as divergent for "
                 "cold-h chains; the slo section drives N concurrent "
                 "sessions through the EDF bounded-slack scheduler and "
                 "reports streams-per-device at the per-frame "
                 "deadline, with the coalescing ratio (frames per "
                 "device dispatch) > 1 the proof that concurrent "
                 "streams batch deliberately rather than by accident.",
    })
    out = args.out or os.path.join(_REPO, f"STREAM_{args.tag}.json")
    write_record(out, rec, indent=1)
    print(json.dumps({
        "metric": "stream_warm_vs_cold_fps",
        "speedup": runner_rows["speedup"],
        "speedup_warm_h": runner_rows["speedup_warm_h"],
        "speedup_vs_cold_gated": runner_rows["speedup_vs_cold_gated"],
        "iters_fraction": runner_rows["iters_fraction"],
        "warm_h_vs_warm_iters": runner_rows["warm_h_vs_warm_iters"],
        "drift_mean_vs_fixed":
            runner_rows["warm_drift_epe_vs_fixed"]["mean"],
        "gates_stable_warm_h": [r["gate_px"] for r in gate_rows
                                if r["warm_h"]["stable"]],
        "streams_per_device": (slo_rows or {}).get("streams_per_device"),
        "meets_1_5x_bar": meets_bar, "out": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
