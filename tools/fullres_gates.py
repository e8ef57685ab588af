"""Measure the full-resolution path gates (VERDICT round 2 weak #5 / next #7).

Produces the numbers behind the memory-derived gates:

1. ``_STEM_EXTRA_BYTES_PER_PIXEL`` (models/raft_stereo.py) — XLA-compiled
   peak-HBM delta between the batch-2 fnet concat and the sequential-fnet
   path, per image pixel, across Middlebury-class shapes.
2. The sequential path's FPS cost at KITTI / SceneFlow / full-res shapes —
   the round-2 README claimed "no FPS cost" without a measurement.
3. ``_BAND_BYTES_PER_ROW_PIXEL`` (models/banded.py) — slope of the banded
   encoder's peak HBM in the band height, per row x width-pixel.

Peak HBM comes from ``compiled.memory_analysis()`` (static XLA analysis —
this environment's runtime exposes no live device memory stats), so sizes
that would OOM at runtime still measure.  FPS uses the chained-differencing
protocol (see bench.py).  Run on the TPU chip:

    python tools/fullres_gates.py [--fps]

Prints one JSON line per measurement plus a calibration summary.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MEM_SHAPES = ((544, 960), (1088, 1984), (1984, 2880))
FPS_SHAPES = ((384, 1248), (544, 960), (1088, 1984))  # KITTI, SceneFlow, full-res
BANDS = (128, 256, 512)
BAND_SHAPE = (1984, 2880)
ITERS = 32
HUGE = 1 << 40


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fps", action="store_true",
                    help="also time batched vs sequential (slow: compiles "
                         "2 programs per shape)")
    args = ap.parse_args()

    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo
    from raft_stereo_tpu.profiling import chained_seconds_per_call

    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    rng = np.random.default_rng(0)
    base = RaftStereoConfig(corr_backend="alt")  # volume-free: stem dominates

    img_s = jnp.zeros((1, 64, 96, 3), jnp.float32)
    model0 = RAFTStereo(base)
    variables = jax.jit(lambda r: model0.init(r, img_s, img_s, iters=1,
                                              test_mode=True)
                        )(jax.random.PRNGKey(0))

    def peak_bytes(cfg, h, w, k=1):
        model = RAFTStereo(cfg)
        img1 = jnp.zeros((1, h, w, 3), jnp.float32)
        img2 = jnp.zeros((1, h, w, 3), jnp.float32)

        @functools.partial(jax.jit, static_argnums=(3,))
        def chain(variables, image1, image2, k):
            def body(i, acc):
                _, up = model.apply(variables, image1 + i * 1e-6, image2,
                                    iters=ITERS, test_mode=True)
                return acc + jnp.mean(up)
            return jax.lax.fori_loop(0, k, body, jnp.float32(0))

        compiled = chain.lower(variables, img1, img2, k).compile()
        return compiled.memory_analysis().peak_memory_in_bytes, chain

    # 1. batched-vs-sequential stem peak delta -------------------------------
    extra_bpps = []
    for h, w in MEM_SHAPES:
        p_seq, _ = peak_bytes(
            dataclasses.replace(base, sequential_fnet_pixels=0), h, w)
        p_bat, _ = peak_bytes(
            dataclasses.replace(base, sequential_fnet_pixels=HUGE), h, w)
        bpp = (p_bat - p_seq) / (h * w)
        extra_bpps.append(bpp)
        print(json.dumps({
            "metric": "stem_extra_bytes_per_pixel", "size": f"{h}x{w}",
            "peak_seq_gib": round(p_seq / 2 ** 30, 3),
            "peak_batched_gib": round(p_bat / 2 ** 30, 3),
            "value": round(bpp, 1), "unit": "bytes/pixel"}))

    # 2. sequential-fnet FPS cost -------------------------------------------
    if args.fps:
        for h, w in FPS_SHAPES:
            img1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)), jnp.float32)
            img2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)), jnp.float32)
            fps = {}
            for name, pix in (("sequential", 0), ("batched", HUGE)):
                _, chain = peak_bytes(dataclasses.replace(
                    base, sequential_fnet_pixels=pix), h, w)
                per = chained_seconds_per_call(
                    lambda k: (lambda: float(chain(variables, img1, img2, k))),
                    k_lo=1, k_hi=3, repeats=3)
                fps[name] = 1.0 / per
            print(json.dumps({
                "metric": "sequential_fnet_fps_cost", "size": f"{h}x{w}",
                "fps_batched": round(fps["batched"], 2),
                "fps_sequential": round(fps["sequential"], 2),
                "sequential_cost_pct": round(
                    100 * (1 - fps["sequential"] / fps["batched"]), 1)}))

    # 3. banded band-height memory slope ------------------------------------
    h, w = BAND_SHAPE
    peaks = {}
    for band in BANDS:
        cfg = dataclasses.replace(base, banded_encoder=True, band_rows=band)
        peaks[band], _ = peak_bytes(cfg, h, w)
        print(json.dumps({
            "metric": "banded_peak_hbm", "size": f"{h}x{w}", "band": band,
            "value": round(peaks[band] / 2 ** 30, 3), "unit": "GiB"}))
    slope = (peaks[BANDS[-1]] - peaks[BANDS[0]]) / (BANDS[-1] - BANDS[0]) / w
    print(json.dumps({
        "metric": "band_bytes_per_row_pixel", "size": f"{h}x{w}",
        "value": round(slope, 1), "unit": "bytes/(row*width-pixel)"}))

    print(json.dumps({
        "metric": "fullres_gates_calibration",
        "stem_extra_bytes_per_pixel": [round(b, 1) for b in extra_bpps],
        "band_bytes_per_row_pixel": round(slope, 1)}))


if __name__ == "__main__":
    main()
