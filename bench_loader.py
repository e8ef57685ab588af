"""Benchmark: host input-pipeline throughput (the training-story gap VERDICT
round 2 flagged — bench_train feeds a synthetic in-memory batch, so nothing
showed the REAL loader can keep the chip busy).

Builds a synthetic SceneFlow-layout TRAIN tree (540x960 PNG pairs + PFM
disparity — the real on-disk formats, reference: core/stereo_datasets.py:
123-184) and measures:

* images/s of the full pipeline (decode -> DenseAugmentor -> batch stack)
  by worker-thread count, against the demand of the measured chip step rate
  (steps/s x batch 8 at the SceneFlow config, BENCH_TRAIN_r03.json);
* with --device: a combined run — the real ``StereoLoader`` feeding the
  jitted train step on the TPU — reporting seconds/step next to the
  synthetic-batch step time, so host-boundedness (or not) is a measurement,
  not a guess.

Prints one JSON line per measurement (bench.py contract).
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

H, W = 540, 960          # SceneFlow native frame size
CROP = (320, 720)        # the reference's SceneFlow training crop
BATCH = 8


def build_tree(root: str, n_pairs: int, seed: int = 0, hw=(H, W)) -> None:
    """FlyingThings3D/frames_cleanpass/TRAIN layout with realistic content:
    smooth low-frequency images (PNG deflate cost sits between noise and
    natural images) and a smooth positive disparity field."""
    from PIL import Image

    from raft_stereo_tpu.data.frame_utils import write_pfm

    h, w = hw
    rng = np.random.default_rng(seed)
    base = np.kron(rng.uniform(0, 255, (-(-h // 20), -(-w // 20), 3)),
                   np.ones((20, 20, 1)))[:h, :w]

    for i in range(n_pairs):
        seq = os.path.join(root, "FlyingThings3D", "frames_cleanpass",
                           "TRAIN", "A", f"{i:04d}")
        dseq = os.path.join(root, "FlyingThings3D", "disparity", "TRAIN",
                            "A", f"{i:04d}", "left")
        os.makedirs(os.path.join(seq, "left"), exist_ok=True)
        os.makedirs(os.path.join(seq, "right"), exist_ok=True)
        os.makedirs(dseq, exist_ok=True)
        noise = rng.integers(0, 30, (h, w, 3))
        left = np.clip(base + noise, 0, 255).astype(np.uint8)
        right = np.clip(np.roll(base, -12, axis=1) + noise, 0,
                        255).astype(np.uint8)
        disp = (8.0 + 40.0 * rng.random((h, w))).astype(np.float32)
        Image.fromarray(left).save(os.path.join(seq, "left", "0006.png"))
        Image.fromarray(right).save(os.path.join(seq, "right", "0006.png"))
        write_pfm(os.path.join(dseq, "0006.pfm"), disp)


def make_loader(root: str, workers: int, photometric: bool = True,
                worker_type: str = "thread"):
    from raft_stereo_tpu.data.datasets import SceneFlow
    from raft_stereo_tpu.data.loader import StereoLoader

    aug = {"crop_size": CROP, "min_scale": -0.2, "max_scale": 0.4,
           "do_flip": None, "yjitter": True, "photometric": photometric}
    ds = SceneFlow(aug, root=root, dstype="frames_cleanpass")
    return StereoLoader(ds, batch_size=BATCH, num_workers=workers,
                        prefetch=2, seed=0, worker_type=worker_type)


def measure_host(root: str, workers: int, n_batches: int,
                 photometric: bool = True,
                 worker_type: str = "thread") -> float:
    loader = make_loader(root, workers, photometric, worker_type)
    it = iter(loader)
    next(it)  # warm: thread spin-up, file-cache population
    t0 = time.perf_counter()
    for _ in range(n_batches):
        next(it)
    dt = time.perf_counter() - t0
    del it
    return n_batches * BATCH / dt


def stage_breakdown(root: str) -> dict:
    """Per-stage host ms for one sample (decode, photometric, spatial) —
    the evidence for what device_photometric moves off the host."""
    import glob as _glob

    from raft_stereo_tpu.data import frame_utils
    from raft_stereo_tpu.data.augment import DenseAugmentor, _eraser

    candidates = []
    for dstype in ("frames_cleanpass", "frames_finalpass"):
        candidates += sorted(_glob.glob(os.path.join(
            root, "FlyingThings3D", dstype, "TRAIN/*/*/left/*.png")))[:1]
    if not candidates:  # e.g. a Monkaa/Driving-only root: skip, don't crash
        return {"skipped": "no FlyingThings TRAIN pair under this root"}
    left_p = candidates[0]
    right_p = left_p.replace("left", "right")
    dstype = left_p.split(os.sep + "FlyingThings3D" + os.sep)[1].split(
        os.sep)[0]
    disp_p = left_p.replace(dstype, "disparity").replace(".png", ".pfm")
    aug = DenseAugmentor(CROP, -0.2, 0.4, None, True)
    rngf = lambda: np.random.default_rng(0)  # noqa: E731

    def t(f, n=15):
        f()
        t0 = time.perf_counter()
        for _ in range(n):
            f()
        return (time.perf_counter() - t0) / n * 1e3

    img1 = frame_utils.read_image(left_p)
    img2 = frame_utils.read_image(right_p)
    disp = frame_utils.read_gen(disp_p)
    flow = np.stack([-disp, np.zeros_like(disp)], axis=-1)
    decode_ms = t(lambda: (frame_utils.read_image(left_p),
                           frame_utils.read_image(right_p),
                           frame_utils.read_gen(disp_p)))
    color_ms = t(lambda: aug._color(img1, img2, rngf()))
    c1, c2 = aug._color(img1, img2, rngf())
    e2 = _eraser(c2, rngf())
    spatial_ms = t(lambda: aug._spatial(c1, e2, flow, rngf()))
    return {"decode_ms": round(decode_ms, 1),
            "photometric_ms": round(color_ms, 1),
            "spatial_ms": round(spatial_ms, 1)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", type=int, default=64)
    ap.add_argument("--batches", type=int, default=12)
    ap.add_argument("--workers", type=int, nargs="*", default=[0, 2, 4, 8])
    ap.add_argument("--device", action="store_true",
                    help="combined run: real loader -> jitted train step on "
                         "the accelerator (compiles the full step)")
    ap.add_argument("--root", default=None,
                    help="reuse an existing tree instead of building one")
    args = ap.parse_args()

    from raft_stereo_tpu import native
    from raft_stereo_tpu.telemetry.events import bench_record

    root = args.root or tempfile.mkdtemp(prefix="loaderbench_")
    if not args.root:
        build_tree(root, args.pairs)

    # Shared versioned run header (telemetry/events.py); the per-config
    # lines below are rows under it.
    print(json.dumps(bench_record(
        {"metric": "loader_bench_run", "pairs": args.pairs,
         "batches": args.batches, "workers": args.workers,
         "device": args.device})))
    print(json.dumps({"metric": "loader_stage_breakdown_ms",
                      **stage_breakdown(root), "unit": "ms/sample"}))

    for w in args.workers:
        for wt in (("thread",) if w == 0 else ("thread", "process")):
            for photometric in (True, False):
                ips = measure_host(root, w, args.batches,
                                   photometric=photometric, worker_type=wt)
                print(json.dumps({
                    "metric": "loader_images_per_s", "workers": w,
                    "worker_type": wt, "host_photometric": photometric,
                    "native_decoders": native.available(),
                    "value": round(ips, 2),
                    "unit": f"images/s (540x960 -> {CROP})"}))

    if args.device:
        import functools

        import jax
        import jax.numpy as jnp

        from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
        from raft_stereo_tpu.training.state import create_train_state
        from raft_stereo_tpu.training.step import train_step

        from raft_stereo_tpu.profiling import setup_compilation_cache
        setup_compilation_cache()

        from raft_stereo_tpu.data.device_jitter import params_for_datasets

        model_cfg = RaftStereoConfig(mixed_precision=True)
        train_cfg = TrainConfig(batch_size=BATCH, train_iters=22,
                                image_size=CROP)
        state = create_train_state(model_cfg, train_cfg,
                                   jax.random.PRNGKey(0),
                                   image_shape=(1,) + CROP + (3,))
        step = jax.jit(functools.partial(
            train_step, iters=22, loss_gamma=train_cfg.loss_gamma,
            max_flow=train_cfg.max_flow), donate_argnums=(0,))
        step_devjit = jax.jit(functools.partial(
            train_step, iters=22, loss_gamma=train_cfg.loss_gamma,
            max_flow=train_cfg.max_flow,
            jitter=params_for_datasets(("sceneflow",))), donate_argnums=(0,))

        from raft_stereo_tpu.training.train_loop import _DevicePrefetcher

        def run(batch_iter, n, prefetch: bool, step_fn=None):
            """``prefetch`` runs the host->device upload on the train
            loop's own _DevicePrefetcher thread (the product path);
            without it the upload is serial with dispatch."""
            nonlocal state
            step_fn = step_fn or step
            metrics = None
            it = (_DevicePrefetcher(batch_iter, jax.device_put)
                  if prefetch else
                  ({k: jnp.asarray(v) for k, v in b.items()}
                   for b in batch_iter))
            t0 = time.perf_counter()
            for _ in range(n):
                state, metrics = step_fn(state, next(it))
            # device_get of the last loss: the stop clock includes every
            # dispatched step.
            jax.device_get(metrics["loss"])
            dt = (time.perf_counter() - t0) / n
            if prefetch:
                it.close()
            return dt

        loader = make_loader(root, workers=max(args.workers))
        real_it = iter(loader)
        first = next(real_it)  # compile against a real batch

        def synth_iter():
            while True:
                yield dict(first)

        run(synth_iter(), 1, prefetch=False)  # compile + warm
        synth_s = run(synth_iter(), args.batches, prefetch=False)
        synth_pf_s = run(synth_iter(), args.batches, prefetch=True)
        real_s = run(real_it, args.batches, prefetch=True)
        print(json.dumps({
            "metric": "combined_loader_train_step",
            "value": round(real_s, 4),
            "unit": "s/step (real loader + device prefetch)",
            "synthetic_batch_s": round(synth_s, 4),
            "synthetic_batch_prefetch_s": round(synth_pf_s, 4),
            "host_overhead_pct": round(100 * (real_s / synth_pf_s - 1), 1)}))

        # Same combined run with photometric moved on-device: host loader
        # skips ColorJitter (78% of its per-sample CPU), the train step
        # applies the jitter inside the compiled program.
        dj_loader = make_loader(root, workers=max(args.workers),
                                photometric=False)
        dj_it = iter(dj_loader)
        first_dj = next(dj_it)
        run(iter([first_dj]), 1, prefetch=False,
            step_fn=step_devjit)  # compile the devjit variant
        devjit_s = run(dj_it, args.batches, prefetch=True,
                       step_fn=step_devjit)
        print(json.dumps({
            "metric": "combined_loader_train_step_device_photometric",
            "value": round(devjit_s, 4),
            "unit": "s/step (real loader, jitter on device)",
            "vs_host_jitter": round(devjit_s / real_s, 3),
            "synthetic_batch_prefetch_s": round(synth_pf_s, 4),
            "host_overhead_pct":
                round(100 * (devjit_s / synth_pf_s - 1), 1)}))


if __name__ == "__main__":
    main()
