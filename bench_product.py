"""Product-path FPS: the REAL KITTI evaluation harness on the chip.

bench.py times a bare on-device forward chain; the reference's protocol
(reference: evaluate_stereo.py:60-109) runs a Python loop with a per-image
host->device copy, /32 pad, forward, unpad, and device->host fetch.  This
script runs OUR product harness — ``eval.validate.validate_kitti`` over a
synthetic KITTI-layout tree at the real 375x1242 resolution (the honest
per-image stop clock is the result fetch; see eval/runner.py) — next to the
bare-forward chained measurement, so the flagship FPS number and the
product path finally meet and their gap is a measurement.

Prints one JSON line (bench.py contract): value = product-path FPS;
``bare_forward_fps`` and ``gap`` fields explain the difference (per-image
Python/dispatch/copy overhead on this host).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_REPO, "tests"))

N_IMAGES = 70          # warmup discards the first 50 (evaluate_stereo.py:105)
KITTI_HW = (375, 1242)
ITERS = 7              # realtime protocol depth (bench.py)
K_LO, K_HI = 3, 23
REPEATS = 3


def main():
    from golden_data import make_kitti

    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.eval.runner import InferenceRunner
    from raft_stereo_tpu.eval.validate import validate_kitti
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo
    from raft_stereo_tpu.profiling import (chained_seconds_per_call,
                                           make_forward_chain)

    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    cfg = RaftStereoConfig.realtime()
    model = RAFTStereo(cfg)
    img_s = jnp.zeros((1, 64, 96, 3), jnp.float32)
    variables = jax.jit(lambda r: model.init(r, img_s, img_s, iters=1,
                                             test_mode=True)
                        )(jax.random.PRNGKey(0))

    # --- product path: the real KITTI validator over a synthetic tree
    with tempfile.TemporaryDirectory(prefix="kittibench_") as td:
        root = os.path.join(td, "KITTI")
        make_kitti(root, np.random.default_rng(0), n=N_IMAGES, hw=KITTI_HW,
                   hard=True)
        runner = InferenceRunner(cfg, variables, iters=ITERS)
        res = validate_kitti(runner, root=root)

        # --- batched product mode: upload BATCH pairs per dispatch,
        # amortizing the per-image dispatch and transfer setup the
        # per-image protocol pays once per frame.
        from raft_stereo_tpu.data.frame_utils import read_image
        BATCHED_N = 8
        lefts = [read_image(os.path.join(root, "training", "image_2",
                                         f"{i:06d}_10.png"))
                 for i in range(BATCHED_N)]
        rights = [read_image(os.path.join(root, "training", "image_3",
                                          f"{i:06d}_10.png"))
                  for i in range(BATCHED_N)]
        runner.run_batch(lefts, rights)  # compile + warm
        batched = [runner.run_batch(lefts, rights)[1] for _ in range(5)]
        batched_s = float(np.median(batched)) / BATCHED_N
        flows_fp32, _ = runner.run_batch(lefts, rights)

        # --- half-precision fetch (round 5): the flow is cast fp16 ON
        # DEVICE before the fetch, halving the down-leg bytes of the
        # batched path.
        runner16 = InferenceRunner(cfg, variables, iters=ITERS,
                                   fetch_dtype="fp16")
        runner16.run_batch(lefts, rights)  # compile + warm
        batched16 = [runner16.run_batch(lefts, rights)[1] for _ in range(5)]
        batched16_s = float(np.median(batched16)) / BATCHED_N
        flows_fp16, _ = runner16.run_batch(lefts, rights)
        # pure fetch-rounding error — bounds any EPE delta from above
        fetch_roundoff_px = float(np.abs(flows_fp16 - flows_fp32).mean())

    # --- bare forward at the same padded shape (bench.py's method)
    h = -(-KITTI_HW[0] // 32) * 32
    w = -(-KITTI_HW[1] // 32) * 32
    rng = np.random.default_rng(0)
    img1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)), jnp.float32)

    bare_s = chained_seconds_per_call(
        make_forward_chain(
            lambda v, a, b: model.apply(v, a, b, iters=ITERS,
                                        test_mode=True)[1],
            variables, img1, img2),
        k_lo=K_LO, k_hi=K_HI, repeats=REPEATS)

    fps_product = res["kitti-fps"]
    fps_bare = 1.0 / bare_s
    from raft_stereo_tpu.telemetry.events import bench_record, write_record

    rec = bench_record({
        "metric": "product_path_fps_kitti",
        "value": round(fps_product, 2),
        "unit": "frames/s (validate_kitti end-to-end, 375x1242)",
        "batched_fps": round(1.0 / batched_s, 2),
        "batched_n_per_roundtrip": BATCHED_N,
        "batched_fp16_fetch_fps": round(1.0 / batched16_s, 2),
        "fp16_fetch_roundoff_px": round(fetch_roundoff_px, 5),
        "bare_forward_fps": round(fps_bare, 2),
        "gap": round(fps_product / fps_bare, 3),
        "per_image_overhead_ms": round(1e3 * (1 / fps_product - bare_s), 2),
        "kitti_epe_random_weights": round(res["kitti-epe"], 2),
        "n_timed": N_IMAGES - 50,  # FpsProtocol times images 51..N
    })
    print(json.dumps(rec))
    os.makedirs(os.path.join(_REPO, "chiprun_out"), exist_ok=True)
    write_record(os.path.join(_REPO, "chiprun_out", "PRODUCT.json"), rec)


if __name__ == "__main__":
    main()
