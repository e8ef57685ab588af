"""``compile_for_v5e.py`` for one cell named on the command line: the cell's
forward program compiled at full size for a DESCRIBED v5e (no chip attached,
nothing runs), with ``memory_analysis()``, the kernel launches in the text
and the program's own "kernel path" log lines (each shape-driven choice is
logged the first time it is made in a process).  Compile facts, never
chip runs.

    JAX_PLATFORMS=cpu python3 benchmark/tests/compile_cell_for_v5e.py \
        fullres.bulk.middlebury-f [--batches 1,2]

``--batches`` defaults to the cell's own ``pairs_per_call``.  The kernel
gates ask ``jax.default_backend()``, which is ``cpu`` here, so this script
opens them itself, as ``tests/test_v5e_compile.py`` does.
"""

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--batches", default=None)
    args = ap.parse_args(argv)
    import logging

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    logging.basicConfig(level=logging.WARNING, format="  %(message)s")
    logging.getLogger("raft_stereo_tpu.kernels.corr_lookup").setLevel(
        logging.INFO)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.eval.runner import (effective_inference_config,
                                             make_forward)
    from raft_stereo_tpu.kernels import corr_alt, corr_lookup, gru_fused
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo
    from raft_stereo_tpu.training.state import init_model_variables

    for mod in (corr_lookup, corr_alt, gru_fused):
        mod.fused_lookup_available = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    wl = cell["workload"]
    precision = cell["config"].get("env", {}).get(
        "JAX_DEFAULT_MATMUL_PRECISION", "default")
    cfg = effective_inference_config(
        RaftStereoConfig.from_dict(cell["config"]["model"]), wl["iters"])
    h, w = (-(-d // 32) * 32 for d in wl["traffic"]["image_hw"])
    batches = ([int(b) for b in args.batches.split(",")] if args.batches
               else [wl["traffic"]["pairs_per_call"]])
    shapes = jax.eval_shape(
        lambda: init_model_variables(cfg, jax.random.PRNGKey(0)))
    variables = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        shapes)
    for b in batches:
        img = jax.ShapeDtypeStruct((b, h, w, 3), jnp.uint8, sharding=chip)
        t0 = time.monotonic()
        with jax.default_matmul_precision(precision):
            compiled = make_forward(RAFTStereo(cfg), wl["iters"]).lower(
                variables, img, img).compile()
        m = compiled.memory_analysis()
        total = (m.temp_size_in_bytes + m.argument_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes)
        print(f"{cell['name']}: {h}x{w} batch {b} iters {wl['iters']} "
              f"precision {precision}: temp "
              f"{m.temp_size_in_bytes / 1e9:.3f}e9 B, arguments "
              f"{m.argument_size_in_bytes / 1e9:.3f}e9, outputs "
              f"{m.output_size_in_bytes / 1e9:.3f}e9, together "
              f"{total / 1e9:.3f}e9 B = {100 * total / 2 ** 34:.1f} % of "
              f"16 GiB; {compiled.as_text().count('tpu_custom_call')} "
              f"mentions of tpu_custom_call in the text; compiled in "
              f"{time.monotonic() - t0:.0f}s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
