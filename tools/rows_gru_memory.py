"""Memory scaling of full-loop context parallelism (``rows_gru``).

Two measurements, selected by the active JAX platform:

* ``--mesh-scaling`` (run under ``JAX_PLATFORMS=cpu`` with
  ``--xla_force_host_platform_device_count=8``): XLA's buffer assignment for
  the SAME global training step at ``n_rows`` in {1, 2, 4, 8}.  The
  per-device temp bytes are the structural evidence that the train-mode
  scan's O(H) per-iteration carries — the tensors that wall off
  full-resolution training on one chip — shard ~1/N across the rows axis,
  with the halo overlap as the measured deviation from ideal.
* ``--chip-wall`` (run on the TPU): single-device full-resolution TRAINING
  step peak HBM vs image height via ``compiled.memory_analysis()`` (the
  same static analysis the remat-knob experiments used,
  docs/TRAIN_PROFILE.md round 4) — the wall ``rows_gru`` exists to break.
  Compile-only: nothing is executed, so heights far past the OOM point are
  measurable.

Prints one JSON line per configuration.  Reference anchor: the reference has
no answer at all to full-resolution training — it trains on 2x24 GB GPUs at
crops (train_stereo.py:221-227) and handles full-res only at eval via the
no-volume alt backend (core/corr.py:64-107).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _train_step_compiled(model_cfg, train_cfg, mesh, image_hw):
    import jax

    from raft_stereo_tpu.parallel.mesh import replicate, shard_batch
    from raft_stereo_tpu.training.state import create_train_state
    from raft_stereo_tpu.training.step import make_train_step

    h, w = image_hw
    rng = np.random.default_rng(0)
    host_batch = {
        "image1": rng.uniform(0, 255, (train_cfg.batch_size, h, w, 3)
                              ).astype(np.float32),
        "image2": rng.uniform(0, 255, (train_cfg.batch_size, h, w, 3)
                              ).astype(np.float32),
        "flow": rng.uniform(-8, 0, (train_cfg.batch_size, h, w)
                            ).astype(np.float32),
        "valid": np.ones((train_cfg.batch_size, h, w), np.float32),
    }
    state = create_train_state(model_cfg, train_cfg, jax.random.PRNGKey(0),
                               image_shape=(1, h, w, 3))
    if mesh is not None:
        state = replicate(state, mesh)
        batch = shard_batch(host_batch, mesh)
    else:
        batch = host_batch
    step = make_train_step(train_cfg, mesh=mesh, donate=False)
    return step.lower(state, batch).compile()


def mesh_scaling(args):
    import contextlib

    import jax

    from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
    from raft_stereo_tpu.parallel.mesh import ROWS_AXIS, make_mesh
    from raft_stereo_tpu.parallel.rows_sharded import rows_sharding

    h, w = args.height, args.width
    for n_rows in args.rows:
        # fp32 on the CPU mesh: XLA's CPU backend aborts ("Invalid binary
        # instruction opcode copy", hlo_instruction.cc) compiling the bf16
        # BACKWARD of the row-sharded loop — a backend compiler bug
        # (fp32 grads and bf16 forward both compile clean; single-device
        # bf16 training on the TPU backend is measured working).  The 1/N
        # scaling ratio this measurement exists for is dtype-independent.
        model_cfg = RaftStereoConfig(
            corr_backend="alt", mixed_precision=False,
            rows_shards=n_rows, rows_gru=n_rows > 1,
            rows_gru_halo=args.halo)
        train_cfg = TrainConfig(batch_size=1, train_iters=args.iters,
                                image_size=(h, w), data_parallel=1)
        mesh = (make_mesh(n_data=1, n_corr=1, n_rows=n_rows,
                          devices=jax.devices()[:n_rows])
                if n_rows > 1 else None)
        ctx = (rows_sharding(mesh, axis=ROWS_AXIS) if n_rows > 1
               else contextlib.nullcontext())
        with ctx:
            compiled = _train_step_compiled(model_cfg, train_cfg, mesh,
                                            (h, w))
        ma = compiled.memory_analysis()
        total_gib = (ma.temp_size_in_bytes
                     + ma.argument_size_in_bytes) / 2**30
        print(json.dumps({
            "metric": "rows_gru_mesh_memory",
            "n_rows": n_rows, "halo": args.halo,
            "image": f"{h}x{w}", "iters": args.iters,
            "per_device_temp_mib": round(ma.temp_size_in_bytes / 2**20, 1),
            "per_device_args_mib": round(
                ma.argument_size_in_bytes / 2**20, 1),
            "per_device_total_gib": round(total_gib, 3),
            "fits_16gib_chip": bool(total_gib < 15.75),
            "unit": "MiB/device (XLA buffer assignment, CPU backend, fp32)",
        }), flush=True)


def chip_wall(args):
    import re

    from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
    from raft_stereo_tpu.profiling import device_hbm_bytes

    budget = device_hbm_bytes()
    for h, w in [tuple(map(int, s.split("x"))) for s in args.shapes]:
        model_cfg = RaftStereoConfig(corr_backend="alt",
                                     mixed_precision=True,
                                     banded_encoder=args.banded)
        train_cfg = TrainConfig(batch_size=1, train_iters=args.iters,
                                image_size=(h, w), data_parallel=1)
        row = {"metric": "fullres_train_single_chip_hbm",
               "image": f"{h}x{w}", "iters": args.iters,
               "banded_encoder": args.banded,
               "device_hbm_gib": round(budget / 2**30, 2)}
        try:
            compiled = _train_step_compiled(model_cfg, train_cfg, None,
                                            (h, w))
            ma = compiled.memory_analysis()
            peak = getattr(ma, "peak_memory_in_bytes", 0) or (
                ma.temp_size_in_bytes + ma.argument_size_in_bytes)
            row.update(peak_hbm_gib=round(peak / 2**30, 3),
                       fits=bool(peak < budget),
                       unit="GiB (compiled.memory_analysis, compile-only)")
        except Exception as e:
            # The remote TPU compiler refuses outright past the wall; its
            # message carries the honest number ("Used X of Y hbm").  Any
            # OTHER failure is a tool/environment error, not a measurement —
            # re-raise so it can't masquerade as a fits=false datapoint.
            m = re.search(r"Used ([0-9.]+)G of ([0-9.]+)G hbm", str(e))
            if m is None:
                raise
            # both numbers from the same message so the row is
            # self-consistent (the local HBM query may differ from the
            # compiler's budget, e.g. 16.0 vs 15.75)
            row.update(fits=False, peak_hbm_gib=float(m.group(1)),
                       device_hbm_gib=float(m.group(2)),
                       unit="GiB (XLA:TPU compile OOM message)")
        print(json.dumps(row), flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--mesh-scaling", action="store_true")
    p.add_argument("--chip-wall", action="store_true")
    p.add_argument("--height", type=int, default=768)
    p.add_argument("--width", type=int, default=256)
    p.add_argument("--iters", type=int, default=6)
    p.add_argument("--rows", type=int, nargs="+", default=[1, 2, 4, 8],
                   help="rows-shard counts for --mesh-scaling (full "
                        "Middlebury-F geometry: --height 1984 works for "
                        "rows<=4; rows=8 needs H%%128==0, e.g. 2048)")
    p.add_argument("--halo", type=int, default=12,
                   help="rows_gru fine-level halo rows")
    p.add_argument("--banded", action="store_true",
                   help="chip-wall with the banded (streaming) encoder — "
                        "the single-chip alternative to row sharding")
    p.add_argument("--shapes", nargs="+",
                   default=["512x736", "992x1440", "1984x2880"])
    args = p.parse_args()
    if args.mesh_scaling:
        # CPU virtual mesh (tests/_hermetic.py sets the two variables)
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tests"))
        from _hermetic import force_cpu
        force_cpu(max(8, max(args.rows)))
        mesh_scaling(args)
    if args.chip_wall:
        chip_wall(args)


if __name__ == "__main__":
    main()
