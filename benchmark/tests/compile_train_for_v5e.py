"""A training cell's step compiled at full size for a DESCRIBED v5e (no
chip attached, nothing runs): ``make_train_step`` as ``train()`` builds it,
over the cell's ``data`` mesh, with ``memory_analysis()`` a device, the
kernel launches in the text (name and result of each), the collectives,
and the program's own "kernel path" log lines.  Compile facts, never chip
runs; the source of PERF.md section 4's bytes.

    JAX_PLATFORMS=cpu python3 benchmark/tests/compile_train_for_v5e.py \
        [sceneflow.train.b4] [sceneflow.train.b8-dp4]

With no cell named, both training cells: batch 4 on one chip, and the
published batch 8 over ``data=4`` (2 pairs a chip).

The kernel gates ask ``jax.default_backend()``, which is ``cpu`` here, so
this script opens them itself, as ``tests/test_v5e_compile.py`` does.
"""

import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

CELLS = ("sceneflow.train.b4", "sceneflow.train.b8-dp4")


def compile_step(cell: dict):
    """The cell's compiled step for a described ``v5e:2x2`` and the mesh it
    was compiled over."""
    import warnings

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.entries import train_job
    from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
    from raft_stereo_tpu.kernels import corr_alt, corr_lookup, gru_fused
    from raft_stereo_tpu.parallel.mesh import make_mesh
    from raft_stereo_tpu.training.state import create_train_state
    from raft_stereo_tpu.training.step import make_train_step

    for mod in (corr_lookup, corr_alt, gru_fused):
        mod.fused_lookup_available = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    model_cfg = RaftStereoConfig.from_dict(cell["config"]["model"])
    train_cfg = TrainConfig.from_dict(train_job.recipe_of(cell, 0))
    n = train_cfg.data_parallel
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # "uses n of 4 devices"
        mesh = make_mesh(n_data=n, devices=topo.devices[:n])
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    h, w = train_cfg.image_size
    b = train_cfg.batch_size
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl,
                                       weak_type=x.weak_type),
        jax.eval_shape(lambda: create_train_state(
            model_cfg, train_cfg, jax.random.PRNGKey(0),
            image_shape=(1, h, w, 3))))
    # the batch as the loop's prefetcher uploads it (compact_upload)
    gt = ((jnp.float16, np.uint8) if train_cfg.compact_upload
          else (jnp.float32, jnp.float32))
    batch = {"image1": jax.ShapeDtypeStruct((b, h, w, 3), np.uint8,
                                            sharding=split),
             "image2": jax.ShapeDtypeStruct((b, h, w, 3), np.uint8,
                                            sharding=split),
             "flow": jax.ShapeDtypeStruct((b, h, w), gt[0], sharding=split),
             "valid": jax.ShapeDtypeStruct((b, h, w), gt[1], sharding=split)}
    return make_train_step(train_cfg, mesh=mesh).lower(state,
                                                       batch).compile(), n


def kernel_launches(text: str) -> dict:
    """``{name and result of a Mosaic call: times it stands in the text}``."""
    out = {}
    for line in text.splitlines():
        if "tpu_custom_call" not in line or " custom-call(" not in line:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[^=]*?\)?)\s+"
                     r"custom-call\(", line)
        if m:
            name = re.sub(r"\.\d+$", "", m.group(1))
            result = re.sub(r"\{[^}]*\}", "", m.group(2))
            key = f"{name} = {result}"
            out[key] = out.get(key, 0) + 1
    return out


def main(argv) -> int:
    import logging

    from benchmark import harness

    logging.basicConfig(level=logging.WARNING, format="  %(message)s")
    logging.getLogger("raft_stereo_tpu.kernels.corr_lookup").setLevel(
        logging.INFO)
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    for name in argv or CELLS:
        cell = harness.load_cell(name)
        t0 = time.monotonic()
        compiled, n = compile_step(cell)
        m = compiled.memory_analysis()
        total = (m.temp_size_in_bytes + m.argument_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes)
        text = compiled.as_text()
        tr = cell["workload"]["traffic"]
        print(f"{name}: batch {tr['batch_size']} over data={n}, "
              f"{tr['image_hw'][0]}x{tr['image_hw'][1]}, "
              f"{cell['workload']['iters']} iterations, a device: temp "
              f"{m.temp_size_in_bytes / 1e9:.3f}e9 B, arguments "
              f"{m.argument_size_in_bytes / 1e9:.3f}e9, outputs "
              f"{m.output_size_in_bytes / 1e9:.3f}e9, aliased "
              f"{m.alias_size_in_bytes / 1e9:.3f}e9, together "
              f"{total / 1e9:.3f}e9 B = {100 * total / 17.18e9:.1f} % of "
              f"17.18e9; all-reduce {text.count(' all-reduce(')}"
              f" + {text.count(' all-reduce-start(')} started, all-gather "
              f"{text.count(' all-gather(')}; compiled in "
              f"{time.monotonic() - t0:.0f}s", flush=True)
        for key, count in sorted(kernel_launches(text).items()):
            print(f"  kernel x{count}: {key}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
