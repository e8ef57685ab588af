"""The programs' layer scopes (``profiling.LAYERS``) and the benchmark's
join of a trace to them (``benchmark/layer_scopes.py``), on the CPU at tiny
widths: every instruction the realtime, accuracy and IGEV-Stereo forwards
and the batch-2 training step stage with device cost is under a listed
layer; the training step shows all three passes; each Mosaic call keeps the
innermost scope the TPU names it after; and a trace of these programs
reads back as shares that add up, or as nothing where the trace lacks the
HLO.
"""

import dataclasses
import json
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness, layer_scopes, trace_reduce
from raft_stereo_tpu import profiling
from raft_stereo_tpu.config import IgevStereoConfig, RaftStereoConfig, \
    TrainConfig
from raft_stereo_tpu.eval.runner import make_forward
from raft_stereo_tpu.models.raft_stereo import create_model
from raft_stereo_tpu.training.state import create_train_state
from raft_stereo_tpu.training.step import make_train_step

HW = (64, 128)
# what costs nothing on a device: no operation is launched for it
FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
        "after-all", "partition-id", "replica-id"}


def _small(cfg):
    return dataclasses.replace(cfg, hidden_dims=(32, 32, 32), fnet_dim=64)


FORWARDS = {
    "realtime": _small(RaftStereoConfig.realtime()),
    "accuracy": _small(RaftStereoConfig()),
    "igev": IgevStereoConfig(hidden_dims=(32, 32, 32), max_disp=32),
}


_SHAPES = {}        # configuration -> its variables' shapes


def _forward(cfg, batch=1):
    model = create_model(cfg)
    if cfg not in _SHAPES:
        img = jnp.zeros((batch, *HW, 3), jnp.float32)
        _SHAPES[cfg] = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), img, img, iters=1, test_mode=True))
    images = jax.ShapeDtypeStruct((batch, *HW, 3), jnp.uint8)
    return make_forward(model, 2, donate_images=False), (
        _SHAPES[cfg], images, images)


def _train_step():
    """The training cells' step (bf16, ``remat_gru``) with one GRU level,
    which halves its compile here."""
    cfg = dataclasses.replace(_small(RaftStereoConfig()),
                              mixed_precision=True, remat_gru=True,
                              n_gru_layers=1, hidden_dims=(32,))
    tcfg = TrainConfig(train_iters=2, num_steps=100)
    state = jax.eval_shape(lambda: create_train_state(
        cfg, tcfg, jax.random.PRNGKey(0), image_shape=(1, *HW, 3)))
    batch = {"image1": jax.ShapeDtypeStruct((2, *HW, 3), jnp.uint8),
             "image2": jax.ShapeDtypeStruct((2, *HW, 3), jnp.uint8),
             "flow": jax.ShapeDtypeStruct((2, *HW), jnp.float32),
             "valid": jax.ShapeDtypeStruct((2, *HW), jnp.float32)}
    return make_train_step(tcfg, donate=False), (state, batch)


def _programs():
    out = {name: _forward(cfg) for name, cfg in FORWARDS.items()}
    out["train"] = _train_step()
    return out


@pytest.fixture(scope="module")
def compiled():
    """Each program compiled for this CPU once, with its arguments'
    shapes.  The training step, which no test runs, skips LLVM's costly
    passes: they change the machine code, not the HLO read here."""
    quick = {"xla_llvm_disable_expensive_passes": True,
             "xla_backend_optimization_level": 0}
    return {name: (fn.trace(*args).lower().compile(
        compiler_options=quick if name == "train" else None), args)
        for name, (fn, args) in _programs().items()}


def _module(hlo):
    module = layer_scopes._classes()["HloModuleProto"]()
    module.ParseFromString(hlo.as_serialized_hlo_module_proto())
    return module


def _launched(module):
    """(computation, instruction) of every instruction a device launches:
    those of the entry and of the computations a loop, call or branch
    runs; not the inside of a fusion or a reduction's region."""
    inner = {c for comp in module.computations for ins in comp.instructions
             if ins.opcode not in ("while", "call", "conditional")
             for c in ins.called_computation_ids}
    return [(comp, ins) for comp in module.computations
            if comp.id not in inner for ins in comp.instructions
            if ins.opcode not in FREE]


def test_the_program_and_the_benchmark_list_the_same_layers():
    """The benchmark keeps its own copy, so that a program change cannot
    move the yardstick; a layer added to one is added to both."""
    assert layer_scopes.LAYERS == profiling.LAYERS


@pytest.mark.parametrize("name", ["realtime", "accuracy", "igev", "train"])
def test_every_operation_with_device_cost_has_a_layer(compiled, name):
    """Every instruction the compiled program launches is under a listed
    layer, but those the compiler made itself at the top level: they carry
    no path at all (this CPU's float conversions around bfloat16 products,
    layout copies), and the program staged nothing there."""
    executable, _ = compiled[name]
    module = _module(executable.runtime_executable().hlo_modules()[0])
    places = layer_scopes.placements(module)
    launched = _launched(module)
    unscoped = [(ins.name, ins.opcode, ins.metadata.op_name)
                for comp, ins in launched
                if places[ins.name][0] is None
                and not (comp.id == module.entry_computation_id
                         and not ins.metadata.op_name)]
    assert not unscoped, unscoped[:10]
    passes = {places[ins.name][1] for _, ins in launched}
    assert passes == ({"forward", "backward", "recompute"}
                      if name == "train" else {"forward"})


@pytest.mark.parametrize("name,innermost", [
    ("realtime", {"gru_iter", "gru08", "gru16"}),
    ("accuracy", {"gru_iter", "gru08", "gru16", "gru32"}),
    ("igev", {"gru_iter", "gru04", "gru08", "gru16"}),
])
def test_each_mosaic_call_keeps_its_innermost_scope(monkeypatch, name,
                                                    innermost):
    """The TPU names a Mosaic call after its innermost scope, and the
    cells' kernel patterns match those names (``%gru_iter.N`` for the
    lookup kernels, ``%gru16.N`` for the fused gate kernel): lowered for
    the TPU with the kernels on, each call's innermost scope is one of
    them, and no layer scope has taken their place."""
    from raft_stereo_tpu.kernels import corr_alt, corr_lookup, gru_fused

    for module in (corr_alt, corr_lookup, gru_fused):
        monkeypatch.setattr(module, "fused_lookup_available", lambda: True)
    fn, args = _forward(FORWARDS[name])
    lowered = fn.trace(*args).lower(lowering_platforms=("tpu",))
    module = _module(lowered.compiler_ir("hlo"))
    scopes = {ins.metadata.op_name.split("/")[-2]
              for comp in module.computations for ins in comp.instructions
              if ins.opcode == "custom-call"
              and ins.metadata.op_name.endswith("/pallas_call")}
    assert scopes == innermost


# ------------------------------------------------ the join of a trace
def _zeros(tree):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  tree)


@pytest.fixture(scope="module")
def trace_file(compiled, tmp_path_factory):
    """One CPU trace of the three forwards, the host tracer on (the XLA
    client's lines stand in for a device plane, as in a ``TestRig``'s
    rehearsal), and its reduction as a cell's post-pass makes it.  The
    training step's readers are read in the training cell's own traced
    rehearsal (``tests/test_train_cell.py``)."""
    runs = [(compiled[name][0], _zeros(compiled[name][1]))
            for name in FORWARDS]
    trace_dir = tmp_path_factory.mktemp("trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        for executable, args in runs:
            jax.block_until_ready(executable(*args))
    finally:
        jax.profiler.stop_trace()
    (path,) = trace_dir.glob("**/*.xplane.pb")
    return str(path), trace_reduce.reduce_file(str(path), {}, {},
                                               host_stand_in=True)


def _observed(monkeypatch, work_root, cell_name, path, reduced):
    """A traced run of ``cell_name`` that left ``path`` where its entry
    leaves its trace."""
    monkeypatch.setattr(harness, "WORK_ROOT", str(work_root))
    trace_dir = work_root / cell_name / "trace"
    trace_dir.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, trace_dir / os.path.basename(path))
    cell = harness.load_cell(cell_name)
    return cell, {"cell": cell, "seconds": 1.0, "pairs_completed": 1,
                  "counters": {}, "trace": reduced,
                  "device_kind": "TPU v5 lite"}


NEW_METRICS = {
    "fullres.bulk.middlebury-f": {"fnet_cnet_share_pct.full"},
    "igev.bulk.kitti": {"gev_stage_share_pct.igev"},
}


def test_a_trace_reads_back_as_shares_that_add_up(trace_file, monkeypatch,
                                                  tmp_path):
    path, reduced = trace_file
    got = layer_scopes.reduce_file(path, host_stand_in=True)
    assert got["programs"] >= 3 and got["joined_s"] > 0
    shares = {k: 100.0 * s / got["busy_s"] for k, s in got["seconds"].items()}
    assert sum(shares.values()) == pytest.approx(100.0, abs=0.5)
    assert {layer for layer, _ in shares} >= {
        "prep", "fnet", "cnet", "features", "gev", "gru_iter", "upsample"}
    for cell_name, metrics in NEW_METRICS.items():
        cell, observed = _observed(monkeypatch, tmp_path, cell_name, path,
                                   reduced)
        read = harness.read_per_layer(cell, observed)
        for metric in metrics:
            assert 0 < read[metric]["value"] < 100, (metric, read)


def test_a_trace_without_the_hlo_reads_nothing(trace_file, monkeypatch,
                                               tmp_path):
    """The metadata plane stripped (a profiler that keeps no HLO): the
    readers leave their metrics out and do not raise."""
    path, reduced = trace_file
    space = layer_scopes._classes()["XSpace"]()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    keep = [p for p in space.planes if p.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(keep)
    stripped = tmp_path / "stripped" / "host.xplane.pb"
    stripped.parent.mkdir()
    stripped.write_bytes(space.SerializeToString())
    assert layer_scopes.reduce_file(str(stripped),
                                    host_stand_in=True)["programs"] == 0
    for cell_name, metrics in NEW_METRICS.items():
        cell, observed = _observed(monkeypatch, tmp_path / "work",
                                   cell_name, str(stripped), reduced)
        assert not metrics & set(harness.read_per_layer(cell, observed))


def test_a_chip_trace_joins_whole_without_tensorflow():
    """The v5e trace the benchmark's own tests keep (the accuracy forward
    at 64x128, host and python tracers off) carries its program's HLO:
    every operation joins, nine tenths of its time to a listed layer; and
    the reader imports no tensorflow (a second's import, and more for a
    four-chip trace)."""
    import subprocess
    import sys

    path = os.path.join(harness.BENCH_DIR, "tests", "data",
                        "tiny_accuracy_64x128_2iters.xplane.pb")
    code = (
        "import json, sys\n"
        "from benchmark import layer_scopes\n"
        f"got = layer_scopes.reduce_file({path!r})\n"
        "layers = {}\n"
        "for (layer, _), s in got['seconds'].items():\n"
        "    layers[layer] = layers.get(layer, 0.0) + s\n"
        "print(json.dumps([got['programs'], got['joined_s'], got['busy_s'],\n"
        "                  {str(k): v for k, v in layers.items()},\n"
        "                  'tensorflow' in sys.modules]))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=harness.ROOT,
                         capture_output=True, text=True, check=True)
    programs, joined, busy, layers, tensorflow = json.loads(
        out.stdout.strip().splitlines()[-1])
    assert programs == 1 and joined == pytest.approx(busy)
    assert busy == pytest.approx(1.543e-3, rel=1e-3)   # as trace_reduce's
    assert layers["None"] < 0.1 * busy
    assert {"fnet", "cnet", "gru_iter", "corr_pyramid", "upsample"} <= set(
        layers)
    assert not tensorflow
