"""Compile the main path's kernels at published widths for a DESCRIBED
TPU v5e — no chip attached, nothing runs.

Interpret mode (every other kernel test in this suite) cannot see what the
chip's compiler refuses: a scoped-VMEM overflow, a misaligned slice, a
Mosaic kernel under a mesh that XLA will not partition.  These compiles
can, at no chip time.  Rules this file keeps (on-chip-measurement guide,
section 2): the topology is described only inside the module-scoped
fixture below — never at import, never in a ``skipif``/``parametrize``
argument — the compiles run in the test's own process, jax's persistent
cache is off around them, and all of them live in this one file (libtpu
belongs to the one xdist worker that is handed it).

The ``*_available()`` gates ask ``jax.default_backend()``, which is
``cpu`` here: kernel tests call the launch functions directly, and the
whole-program tests steer the gates from the test (``kernels_on``), not
through an option of the program.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

RADIUS = 4
K = 2 * RADIUS + 1
# 1/4-res correlation shapes: (batch*H rows, W1, per-level W2)
ACCURACY_KITTI = (96, 312, (312, 156, 78, 39))     # 384x1248, 4 levels
REALTIME_KITTI = (48, 156, (156, 78))              # 1/8-res, 2 levels
SCENEFLOW_TRAIN = (8 * 80, 180, (180, 90, 45, 22))  # batch 8, 320x720
MIDDLEBURY_F = (496, 720, 256)                     # 1/4-res H, W, fnet D
# the realtime bulk cell: 1/8-res rows of 384x1248 pairs, all four levels
REALTIME_BULK = (48, 156, (156, 78, 39, 19), 256)
# its call: 128 padded 384x1248 uint8 pairs, which the runner launches as
# this many pipelined sub-batches (PERF.md section 4)
BULK_CALL = (128, (384, 1248))
BULK_SUB_BATCHES = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described device is written to the persistent cache
    # but cannot be read back without a chip; keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def data_mesh(topo):
    from raft_stereo_tpu.parallel.mesh import make_mesh

    return make_mesh(n_data=4, devices=topo.devices[:4])


@pytest.fixture
def kernels_on(monkeypatch):
    """Steer the capability gates as the chip would answer them.
    ``kernels_on("lookup")`` opens the correlation kernels only;
    ``kernels_on("lookup", "gru")`` the fused ConvGRU gates too."""
    from raft_stereo_tpu.kernels import corr_alt, corr_lookup, gru_fused

    def steer(*families):
        if "lookup" in families:
            monkeypatch.setattr(corr_lookup, "fused_lookup_available",
                                lambda: True)
            monkeypatch.setattr(corr_alt, "fused_lookup_available",
                                lambda: True)
        if "gru" in families:
            monkeypatch.setattr(gru_fused, "fused_lookup_available",
                                lambda: True)
    return steer


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_calls(compiled):
    from chip_smoke import kernel_launches    # the smoke's own reading

    return kernel_launches(compiled.as_text())


def _pyramid(shape, dtype, sharding):
    rows, w1, w2s = shape
    # (B=1, H=rows, W2, W1), the TRANSPOSED levels the kernel reads: the
    # entries flatten (B, H) to rows themselves
    return ([_sds((1, rows, w2, w1), dtype, sharding) for w2 in w2s],
            _sds((1, rows, w1), jnp.float32, sharding))


# ------------------------------------------------------------- the lookups
@pytest.mark.parametrize("shape,dtype", [
    # fp32 at KITTI width: ONE launch an iteration in the served program
    # since PR 33 (four before: the old body's hat field and product did
    # not fit one program's VMEM budget together with the four tiles)
    (ACCURACY_KITTI, jnp.float32),
    (REALTIME_KITTI, jnp.bfloat16),
    (SCENEFLOW_TRAIN, jnp.bfloat16),
], ids=["accuracy-kitti-fp32", "realtime-kitti-bf16", "sceneflow-train-bf16"])
def test_lookup_compiles(one_chip, shape, dtype):
    from benchmark.trace_reduce import result_elements
    from raft_stereo_tpu.kernels.corr_lookup import lookup_pyramid_fused

    pyramid, coords = _pyramid(shape, dtype, one_chip)
    compiled = jax.jit(
        lambda pyr, c: lookup_pyramid_fused(pyr, c, RADIUS)
    ).lower(pyramid, coords).compile()
    (call,) = _kernel_calls(compiled)
    # the cells' ``corr_lookup_roofline`` counts lookups from this shape
    rows, w1, w2s = shape
    assert result_elements(call) == rows * w1 * len(w2s) * K, call


@pytest.mark.parametrize("shape,dtype,backward", [
    (ACCURACY_KITTI, jnp.float32, False),
    (SCENEFLOW_TRAIN, jnp.bfloat16, False),
    (SCENEFLOW_TRAIN, jnp.bfloat16, True),
], ids=["accuracy-kitti-fp32", "sceneflow-train-bf16",
        "sceneflow-train-bf16-backward"])
def test_lookup_plan_tracks_mosaic_scoped_vmem(one_chip, monkeypatch, shape,
                                               dtype, backward):
    """The launch plan's estimate (``corr_lookup._program_bytes``, half of
    the scoped VMEM a program of ``ROW_BLK`` rows needs) against the
    compiler itself: with ``vmem_limit_bytes`` at twice the estimate the
    all-levels program compiles, at three quarters of that it is refused.
    So the gate neither admits what Mosaic would refuse nor splits what
    would fit with a quarter to spare."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from raft_stereo_tpu.kernels import corr_lookup

    rows, w1, w2s = shape
    itemsize = jnp.dtype(dtype).itemsize
    per_row, fixed = corr_lookup._program_bytes(w2s, RADIUS, itemsize,
                                                itemsize, backward)
    scoped = 2 * (corr_lookup.ROW_BLK * per_row + fixed)
    pyramid, coords = _pyramid(shape, dtype, one_chip)
    g = _sds((1, rows, w1, len(w2s) * K), dtype, one_chip)

    def run(pyr, c, g):
        out, vjp = jax.vjp(
            lambda p: corr_lookup.lookup_pyramid_fused(p, c, RADIUS), pyr)
        return vjp(g) if backward else out

    real = pl.pallas_call

    def compiles(limit):
        monkeypatch.setattr(pl, "pallas_call", lambda *a, **k: real(
            *a, compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=int(limit)), **k))
        jax.clear_caches()
        try:
            compiled = jax.jit(run).lower(pyramid, coords, g).compile()
        except Exception as e:  # the compiler's refusal names the limit
            assert "vmem" in str(e).lower(), e
            return False
        return len(_kernel_calls(compiled)) == 1

    assert compiles(scoped)
    assert not compiles(0.75 * scoped)


@pytest.mark.parametrize("shape,q_dtype", [
    (ACCURACY_KITTI, jnp.int8),
    (REALTIME_KITTI, jnp.float8_e4m3fn),
], ids=["accuracy-kitti-int8", "realtime-kitti-fp8"])
def test_quantized_lookup_compiles(one_chip, monkeypatch, shape, q_dtype):
    from raft_stereo_tpu.kernels import corr_lookup

    # check_q_dtype asks the fp8 capability gate, cpu here
    monkeypatch.setattr(corr_lookup, "fused_lookup_available", lambda: True)
    pyramid, coords = _pyramid(shape, q_dtype, one_chip)
    compiled = jax.jit(
        lambda pyr, c: corr_lookup.lookup_pyramid_fused_q(
            pyr, c, RADIUS, out_dtype=jnp.float32, q_dtype=q_dtype)
    ).lower(pyramid, coords).compile()
    assert len(_kernel_calls(compiled)) == 1


# bf16 is what a runner below 16 iterations leaves the features in; float32
# is the published full-resolution command's (``corr_fp32``, the
# ``fullres.bulk.middlebury-f`` cell).  At W2 = 720/360/180/90 the all-levels
# launch's working set is over Mosaic's scoped VMEM in either, so the lookup
# is one launch a level, each with its row block shrunk to fit.
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
def test_alt_lookup_compiles_at_middlebury_f(one_chip, dtype):
    from raft_stereo_tpu.kernels.corr_alt import alt_lookup_fused

    h, w, d = MIDDLEBURY_F
    f1 = _sds((1, h, w, d), dtype, one_chip)
    f2s = [_sds((1, h, w // 2 ** i, d), dtype, one_chip) for i in range(4)]
    coords = _sds((1, h, w), jnp.float32, one_chip)
    compiled = jax.jit(
        lambda a, bs, c: alt_lookup_fused(a, bs, c, RADIUS)
    ).lower(f1, f2s, coords).compile()
    assert len(_kernel_calls(compiled)) == 4


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_alt_lookup_compiles_at_realtime_bulk(one_chip, dtype):
    """The no-volume lookup as ``realtime.bulk.kitti`` runs it (two pairs'
    rows here, 128 pairs' there), and the quantized entry that shares its
    forward body: ONE kernel call for all four levels, whose printed result
    holds rows x W1 x levels*K elements.  The cell's ``trace.kernels``
    finds the call by ``tpu_custom_call`` in its name and
    ``trace_reduce.result_elements`` counts its lookups from that shape, so
    a change to either fails here, without a chip."""
    from benchmark.trace_reduce import result_elements
    from raft_stereo_tpu.kernels import corr_alt

    h, w, w2s, d = REALTIME_BULK
    rows = 2 * h
    f1 = _sds((1, rows, w, d), dtype, one_chip)
    f2s = [_sds((1, rows, w2, d), dtype, one_chip) for w2 in w2s]
    coords = _sds((1, rows, w), jnp.float32, one_chip)
    if dtype == jnp.int8:
        def lookup(a, bs, c):
            return corr_alt.alt_lookup_fused_q(a, bs, c, RADIUS,
                                               out_dtype=jnp.float32)
    else:
        def lookup(a, bs, c):
            return corr_alt.alt_lookup_fused(a, bs, c, RADIUS)
    compiled = jax.jit(lookup).lower(f1, f2s, coords).compile()
    (call,) = _kernel_calls(compiled)
    assert result_elements(call) == rows * w * len(w2s) * K, call


# ONE all-levels launch in either dtype since PR 33 (the old body's fp32
# backward asked Mosaic for 16.32 MiB against 16 and ran a launch a level).
# Its first result is the finest level's cotangent, (rows, W2, W1): what
# ``sceneflow.train.b4``'s trace pattern ``= (bf16[320,180,180]`` finds at
# batch 4 and ``corr_lookup_bwd_roofline.train`` counts lookups from.
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
def test_lookup_backward_compiles_at_sceneflow_crop(one_chip, dtype):
    from raft_stereo_tpu.kernels.corr_lookup import lookup_pyramid_fused

    pyramid, coords = _pyramid(SCENEFLOW_TRAIN, dtype, one_chip)
    rows, w1, w2s = SCENEFLOW_TRAIN
    g = _sds((1, rows, w1, len(w2s) * K), dtype, one_chip)

    def pullback(pyr, c, g):
        _, vjp = jax.vjp(lambda p: lookup_pyramid_fused(p, c, RADIUS), pyr)
        return vjp(g)

    compiled = jax.jit(pullback).lower(pyramid, coords, g).compile()
    (call,) = _kernel_calls(compiled)
    hlo_type = "bf16" if dtype == jnp.bfloat16 else "f32"
    assert f" = ({hlo_type}[{rows},{w2s[0]},{w1}]" in call, call


# ---------------------------------------------------------- the ConvGRU gates
def _gru_operands(b, h, w, sharding_b, sharding_w, ch=128, cx=128):
    act = lambda c: _sds((b, h, w, c), jnp.bfloat16, sharding_b)  # noqa: E731
    cin = ch + cx
    return (act(ch), act(cx), act(ch)), (
        _sds((3, 3, cin, 2 * ch), jnp.float32, sharding_w),
        _sds((2 * ch,), jnp.float32, sharding_w),
        _sds((3, 3, cin, ch), jnp.float32, sharding_w),
        _sds((ch,), jnp.float32, sharding_w))


def test_gru_gates_compile(one_chip):
    from raft_stereo_tpu.kernels.gru_fused import gru_gates_fused

    acts, weights = _gru_operands(1, 24, 78, one_chip, one_chip)
    compiled = jax.jit(gru_gates_fused).lower(*acts, *weights).compile()
    assert len(_kernel_calls(compiled)) == 1


def test_gru_gates_split_over_data_mesh(data_mesh):
    """The gate kernel under a 4-device data mesh: each device launches
    it on its own quarter of the batch."""
    from raft_stereo_tpu.kernels.gru_fused import gru_gates_fused
    from raft_stereo_tpu.parallel.data_sharded import (data_sharding,
                                                       over_data_axis)

    acts, weights = _gru_operands(8, 24, 78,
                                  NamedSharding(data_mesh, P("data")),
                                  NamedSharding(data_mesh, P()))

    def gates(*ops):
        with data_sharding(data_mesh):
            return over_data_axis(gru_gates_fused, ops[:3], ops[3:])

    compiled = jax.jit(gates).lower(*acts, *weights).compile()
    calls = _kernel_calls(compiled)
    assert len(calls) == 1
    assert "bf16[2,24,78,256]" in calls[0]    # zr of 2 of the 8 images


# ------------------------------------------------------------ whole programs
def test_accuracy_forward_compiles(one_chip, kernels_on):
    """The served program (eval/runner.make_forward) at the KITTI bucket,
    32 iterations, published widths."""
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.eval.runner import make_forward
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo
    from raft_stereo_tpu.training.state import init_model_variables

    kernels_on("lookup", "gru")
    cfg = RaftStereoConfig()
    variables = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: init_model_variables(
            cfg, jax.random.PRNGKey(0))))
    image = _sds((1, 384, 1248, 3), np.uint8, one_chip)
    compiled = make_forward(RAFTStereo(cfg), 32).lower(
        variables, image, image).compile()
    assert _kernel_calls(compiled)
    # one 16 GB chip holds it with room for the batch ladder
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


def _bulk_call_sub_batches():
    """The rule of eval/runner._run_padded on the realtime cell's call."""
    from raft_stereo_tpu.eval.runner import _sub_batches

    pairs, (hp, wp) = BULK_CALL
    return _sub_batches(pairs, 2 * pairs * hp * wp * 3)     # uint8 pairs


def test_bulk_rule_splits_the_cells_call():
    """No compile: given the cell's call the rule picks what ``PERF.md`` §4
    says it picks, so a CPU run sees the shape the cell launches."""
    assert _bulk_call_sub_batches() == BULK_SUB_BATCHES
    assert BULK_CALL[0] % BULK_SUB_BATCHES == 0


@pytest.mark.parametrize("pairs", ["call", "sub_batch"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float16])
def test_bulk_crop_compiles_to_one_flat_result(one_chip, dtype, pairs):
    """The bulk runner's crop (eval/runner._crop_flat) at the realtime
    cell's call of 128 KITTI pairs and at the sub-batch the runner's rule
    launches it as: its 1-D result is what makes the fetch arrive
    row-major, and it takes the forward's result in the layout the
    compiler gives it (no 3-D result whose layout the compiler may pick)."""
    from raft_stereo_tpu.eval.runner import _crop_flat

    n, (hp, wp) = BULK_CALL
    if pairs == "sub_batch":
        n //= _bulk_call_sub_batches()
    compiled = _crop_flat.lower(_sds((n, hp, wp), dtype, one_chip),
                                pads=(3, 3, 4, 5)).compile()
    (result,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert result.shape == (n * 375 * 1242,) and result.dtype == dtype
    memory = compiled.memory_analysis()
    assert (memory.output_size_in_bytes
            < 1.01 * result.size * result.dtype.itemsize)
    assert memory.temp_size_in_bytes < 0.3e9 * n / BULK_CALL[0]


# ------------------------------------------------- the trunk's 64-channel stage
def _trunk_text(one_chip, monkeypatch, n, lanes):
    """The realtime program's trunk (cnet: batch norm, the stem at stride
    2) on ``n`` KITTI-bucket images in bf16, compiled with packing allowed
    below ``lanes`` images."""
    from raft_stereo_tpu.models import extractor

    monkeypatch.setattr(extractor, "LANE_BATCH_IMAGES", lanes)
    trunk = extractor._Trunk("batch", 3, jnp.bfloat16)
    hp, wp = BULK_CALL[1]
    variables = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: trunk.init(
            jax.random.PRNGKey(0), jnp.zeros((2, 64, 64, 3), jnp.bfloat16))))
    x = _sds((n, hp, wp, 3), jnp.bfloat16, one_chip)
    return jax.jit(trunk.apply).lower(variables, x).compile().as_text()


def _estimated_cycles(text):
    import re

    return sum(int(c) for c in re.findall(r'"estimated_cycles":"(\d+)"',
                                          text))


def test_trunk_stage_packs_at_realtime_bulk(one_chip, monkeypatch):
    """At the realtime cell's sub-batch (16 pairs on the shared backbone:
    32 images) the plain stage holds 64 channels on the 128 lanes
    (``{3,...}``: the channels minor); the packed stage fills them, and the
    compiler's own estimate for the whole trunk is the smaller."""
    from raft_stereo_tpu.models.extractor import (LANE_BATCH_IMAGES,
                                                  packs_two_images)

    n = 2 * (BULK_CALL[0] // _bulk_call_sub_batches())
    assert n == 32 and packs_two_images(n)
    plain = _trunk_text(one_chip, monkeypatch, n, 0)
    packed = _trunk_text(one_chip, monkeypatch, n, LANE_BATCH_IMAGES)
    assert "bf16[32,192,624,64]{3," in plain
    assert "bf16[16,192,624,128]{3," in packed
    assert "bf16[32,192,624,64]" not in packed
    assert _estimated_cycles(packed) < _estimated_cycles(plain)


def test_trunk_stage_batch_takes_the_lanes_from_the_threshold(one_chip,
                                                              monkeypatch):
    """``LANE_BATCH_IMAGES`` from the compiler: at that many images the
    plain stage already lays the batch on the lanes (``{0,...}``: the batch
    minor), so there is nothing for packing to fill."""
    from raft_stereo_tpu.models.extractor import LANE_BATCH_IMAGES

    plain = _trunk_text(one_chip, monkeypatch, LANE_BATCH_IMAGES, 0)
    assert f"bf16[{LANE_BATCH_IMAGES},192,624,64]{{0," in plain


# The trunk calls of each cell's programs: images a call, whether the stage
# packs.  Serving cells: the ladder's rungs of b pairs run cnet on b images
# and fnet on 2b; realtime's shared backbone takes 2 x 16 pairs; the full-
# resolution cell runs one image a call (fnet scans the two, cnet image1);
# IGEV's cnet takes image1 of 16 pairs; the training steps run cnet on
# image1 and fnet on both images of the global batch (4 pairs on one chip,
# 8 over four).
CELL_TRUNKS = [
    ("accuracy.serve.kitti-steady", 1, False), ("accuracy.serve.kitti-steady",
                                                2, True),
    ("accuracy.serve.kitti-saturated", 8, True),
    ("accuracy.serve.kitti-saturated", 16, True),
    ("realtime.bulk.kitti", 32, True),
    ("fullres.bulk.middlebury-f", 1, False),
    ("igev.bulk.kitti", 16, True),
    ("sceneflow.train.b4", 4, True), ("sceneflow.train.b4", 8, True),
    ("sceneflow.train.b8-dp4", 8, True), ("sceneflow.train.b8-dp4", 16, True),
    ("a realtime program of 128 pairs", 256, False),
]


@pytest.mark.parametrize("cell,images,packs", CELL_TRUNKS,
                         ids=[f"{c}-{n}" for c, n, _ in CELL_TRUNKS])
def test_trunk_packing_rule_at_each_cells_shapes(cell, images, packs):
    from raft_stereo_tpu.models.extractor import packs_two_images

    assert packs_two_images(images) is packs


def test_data_parallel_train_step_compiles(data_mesh, kernels_on):
    """``make_train_step(train_cfg, mesh=<data=4>)`` with the default
    ``corr_backend="reg_fused"`` at the published SceneFlow crop (2 of the
    22 iterations; the scan body compiles once whatever the count): before
    parallel/data_sharded.py this failed to lower — "Mosaic kernels cannot
    be automatically partitioned".  The ConvGRU kernel stays gated off
    here (each of its three level shapes costs ~25 s of Mosaic compile);
    its split is pinned above."""
    from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
    from raft_stereo_tpu.training.state import create_train_state
    from raft_stereo_tpu.training.step import make_train_step

    kernels_on("lookup")
    model_cfg = RaftStereoConfig(mixed_precision=True)
    train_cfg = TrainConfig(batch_size=8, image_size=(320, 720),
                            train_iters=2)
    h, w = train_cfg.image_size
    b = train_cfg.batch_size
    repl = NamedSharding(data_mesh, P())
    split = NamedSharding(data_mesh, P("data"))
    state = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, repl),
        jax.eval_shape(lambda: create_train_state(
            model_cfg, train_cfg, jax.random.PRNGKey(0),
            image_shape=(1, h, w, 3))))
    batch = {"image1": _sds((b, h, w, 3), np.uint8, split),
             "image2": _sds((b, h, w, 3), np.uint8, split),
             "flow": _sds((b, h, w), jnp.float32, split),
             "valid": _sds((b, h, w), jnp.float32, split)}
    # Least optimisation effort: partitioning and the Mosaic compile are
    # what this test is about, and XLA's full effort on the step's convs
    # costs 225 s against 39 s.
    compiled = make_train_step(train_cfg, mesh=data_mesh).lower(
        state, batch).compile(compiler_options={
            "exec_time_optimization_effort": -1.0,
            "memory_fitting_effort": -1.0})
    calls = _kernel_calls(compiled)
    assert calls
    rows = b * (h // 4) // 4    # a quarter of the batch's 1/4-res rows
    assert all(f"bf16[{rows},180,180]" in c for c in calls), calls
    assert "all-gather" not in compiled.as_text()
