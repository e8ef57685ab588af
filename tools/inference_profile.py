"""Where does realtime-model inference time go, and does batching scale?

Two measurements the per-image FPS protocol can't show (run on the chip):

1. Phase split: encoder-only vs full forward (chained protocol), telling
   whether further GRU/lookup work can move the headline at all.
2. Batched throughput: images/s at batch 1/2/4/8 — the reference's
   protocol is strictly per-image (evaluate_stereo.py:68-82), but a TPU
   serves batches; this is the deployment-relevant ceiling.

Prints one JSON line per measurement.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

H, W = 384, 1248
ITERS = 7
BATCHES = (1, 2, 4, 8)
K_LO, K_HI = 3, 13
REPEATS = 3


def main():
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo
    from raft_stereo_tpu.profiling import chained_seconds_per_call

    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    cfg = RaftStereoConfig.realtime()
    model = RAFTStereo(cfg)
    img_s = jnp.zeros((1, 64, 96, 3), jnp.float32)
    variables = jax.jit(lambda r: model.init(r, img_s, img_s, iters=1,
                                             test_mode=True)
                        )(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    from raft_stereo_tpu.profiling import make_forward_chain

    def timed(apply_fn, img1, img2):
        return chained_seconds_per_call(
            make_forward_chain(apply_fn, variables, img1, img2),
            k_lo=K_LO, k_hi=K_HI, repeats=REPEATS)

    img1 = jnp.asarray(rng.uniform(0, 255, (1, H, W, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (1, H, W, 3)), jnp.float32)

    # Phase 1: full forward vs GRU-depth extrapolated encoder share.
    # iters=0 is invalid (scan needs length>=1), so measure iters=1 and
    # iters=7: per-iteration cost = (t7 - t1) / 6; encoder+overhead = t1 -
    # per_iter.
    def apply_at(iters):
        return lambda v, a, b: model.apply(v, a, b, iters=iters,
                                           test_mode=True)[1]

    t7 = timed(apply_at(7), img1, img2)
    t1 = timed(apply_at(1), img1, img2)
    per_iter = (t7 - t1) / 6
    stem = t1 - per_iter
    print(json.dumps({
        "metric": "realtime_phase_split", "t_iters7_ms": round(t7 * 1e3, 2),
        "t_iters1_ms": round(t1 * 1e3, 2),
        "per_gru_iter_ms": round(per_iter * 1e3, 3),
        "encoder_and_fixed_ms": round(stem * 1e3, 2),
        "gru_share_at_7_iters": round(7 * per_iter / t7, 3)}))

    # Phase 2: batched throughput.  batch=1 reuses Phase 1's t7 — same
    # shape, same iters; re-measuring it would double minutes of chip time.
    for b in BATCHES:
        if b == 1:
            t = t7
        else:
            i1 = jnp.asarray(rng.uniform(0, 255, (b, H, W, 3)), jnp.float32)
            i2 = jnp.asarray(rng.uniform(0, 255, (b, H, W, 3)), jnp.float32)
            t = timed(apply_at(ITERS), i1, i2)
        print(json.dumps({
            "metric": "realtime_batched_throughput", "batch": b,
            "value": round(b / t, 2), "unit": "images/s (on-device chained)",
            "s_per_batch": round(t, 4)}))


if __name__ == "__main__":
    main()
