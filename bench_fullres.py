"""Benchmark: full-resolution (Middlebury-F class) inference — the
long-context path.

BASELINE config 3: the reference runs Middlebury-F full resolution ONLY via
its no-volume "alt" backend (reference: README.md:121, core/corr.py:64-107)
because the reg corr volume is O(H·W·W) memory.  This measures, on one chip,
for the accuracy architecture (n_downsample=2, fp32, 32 iters):

* XLA-compiled peak HBM (``compiled.memory_analysis()`` — this runtime does
  not expose live device memory stats) for the fused no-volume ``alt``
  backend vs the volume-based ``reg_fused`` backend;
* FPS via the chained-differencing protocol (see bench.py), when the
  program fits at all.

Sizes: 1088x1984 (mid-size MiddEval3-F frames, /32-aligned) and 1984x2880
(Jadeplant-class, the largest trainingF frames).  Prints one JSON line per
(backend, size) with peak HBM and FPS; RESOURCE_EXHAUSTED is reported as
``"oom": true`` — that outcome IS the measurement for the volume path.
"""

from __future__ import annotations

import functools
import json

import argparse

import jax
import jax.numpy as jnp
import numpy as np

SIZES = ((1088, 1984), (1984, 2880))
BACKENDS = ("alt", "reg_fused")
ITERS = 32
K_LO, K_HI = 1, 3
REPEATS = 3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--banded", action="store_true",
                    help="banded encoder (models/banded.py): several-fold "
                         "lower peak HBM, ~20%% slower at full res")
    ap.add_argument("--xl_mesh", default=None,
                    help="also measure the mesh-SHARDED forward (e.g. "
                         "'rows=4'): peak HBM becomes per-device and the "
                         "ROWSGRU memory wall drops ~1/N — the raw-"
                         "forward twin of the serving xl tier "
                         "(bench_serve.py --xl measures the engine "
                         "path).  Needs rows*corr local devices")
    args = ap.parse_args()

    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo
    from raft_stereo_tpu.profiling import chained_seconds_per_call
    from raft_stereo_tpu.telemetry.events import bench_record

    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    # Shared versioned run header (telemetry/events.py); the per-(backend,
    # size) lines below are rows under it.
    print(json.dumps(bench_record(
        {"metric": "fullres_inference_run", "banded": args.banded,
         "iters": ITERS, "sizes": [f"{h}x{w}" for h, w in SIZES]})))

    import contextlib

    # Mesh-sharded variant (--xl_mesh): trace the same chained forward
    # with rows/corr sharding active — every compile below then reports
    # PER-DEVICE memory_analysis, directly comparable to the solo rows.
    mesh_ctx = contextlib.nullcontext
    mesh_kw = {}
    if args.xl_mesh:
        from raft_stereo_tpu.parallel.mesh import (ROWS_AXIS, make_mesh,
                                                   parse_mesh_spec)
        from raft_stereo_tpu.parallel.rows_sharded import rows_sharding
        spec = parse_mesh_spec(args.xl_mesh)
        mesh = make_mesh(n_data=1, n_corr=spec["corr"],
                         n_rows=spec["rows"],
                         devices=jax.devices()[:spec["rows"]
                                               * spec["corr"]])
        mesh_kw = {"rows_shards": spec["rows"],
                   "corr_w2_shards": spec["corr"],
                   "rows_gru": spec["rows"] > 1 and spec["corr"] == 1}
        if spec["rows"] > 1:
            mesh_ctx = lambda: rows_sharding(mesh, ROWS_AXIS)  # noqa: E731
        if spec["corr"] > 1:
            from raft_stereo_tpu.parallel.corr_sharded import corr_sharding
            prev_ctx = mesh_ctx

            def mesh_ctx():
                stack = contextlib.ExitStack()
                stack.enter_context(prev_ctx())
                stack.enter_context(corr_sharding(mesh))
                return stack

    rng = np.random.default_rng(0)
    results = []
    variables = None
    for backend in BACKENDS:
        try:
            cfg = RaftStereoConfig(corr_backend=backend,
                                   banded_encoder=args.banded, **mesh_kw)
        except ValueError as e:   # e.g. corr sharding x volume-free 'alt'
            print(json.dumps({"metric": "fullres_inference",
                              "backend": backend,
                              "xl_mesh": args.xl_mesh,
                              "skipped": str(e)[:160]}))
            continue
        model = RAFTStereo(cfg)
        if variables is None:
            img_s = jnp.zeros((1, 64, 96, 3), jnp.float32)
            variables = jax.jit(
                lambda r: model.init(r, img_s, img_s, iters=1, test_mode=True)
            )(jax.random.PRNGKey(0))
        for h, w in SIZES:
            img1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)), jnp.float32)
            img2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)), jnp.float32)

            @functools.partial(jax.jit, static_argnums=(3,))
            def chain(variables, image1, image2, k):
                def body(i, acc):
                    _, up = model.apply(variables, image1 + i * 1e-6, image2,
                                        iters=ITERS, test_mode=True)
                    return acc + jnp.mean(up)
                return jax.lax.fori_loop(0, k, body, jnp.float32(0))

            rec = {"metric": "fullres_inference", "backend": backend,
                   "size": f"{h}x{w}", "iters": ITERS,
                   "banded_encoder": args.banded}
            if args.xl_mesh:
                rec["xl_mesh"] = args.xl_mesh
                rec["hbm_is_per_device"] = True
            try:
                with mesh_ctx():
                    compiled = chain.lower(variables, img1, img2,
                                           1).compile()
                ma = compiled.memory_analysis()
                rec["peak_hbm_gib"] = round(
                    ma.peak_memory_in_bytes / 2 ** 30, 3)
                rec["temp_gib"] = round(ma.temp_size_in_bytes / 2 ** 30, 3)

                def make_chain(k):
                    if k == 1:  # reuse the executable compiled above
                        return lambda: float(compiled(variables, img1, img2))

                    def run_k():
                        with mesh_ctx():   # k>1 traces a fresh program
                            return float(chain(variables, img1, img2, k))
                    return run_k

                per_image = chained_seconds_per_call(
                    make_chain, k_lo=K_LO, k_hi=K_HI, repeats=REPEATS)
                rec["value"] = round(1.0 / per_image, 3)
                rec["unit"] = "frames/s"
                rec["oom"] = False
            except Exception as e:  # noqa: BLE001 - OOM is a result here
                msg = str(e)
                rec["oom"] = ("RESOURCE_EXHAUSTED" in msg
                              or "Out of memory" in msg
                              or "exceeds the limit" in msg)
                rec["error"] = msg.splitlines()[0][:200]
            print(json.dumps(rec))
            results.append(rec)
    return results


if __name__ == "__main__":
    main()
