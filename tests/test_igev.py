"""IGEV-Stereo (``igevstereo``, PR 35) on the normal path, on the CPU at the
tests' size (64x128 pairs, ``max_disp`` 32, 2 iterations; the published
widths otherwise): the program against the benchmark's plain reference on
seeded weights, the combined lookup kernel (interpret mode) against plain
sampling, a planted fault the cell's comparison catches, the new
cell's files, the metrics' readers, the configuration's kind through a
checkpoint, the options only RAFT-Stereo has refused by name, and the
serving engine's refusal.  What is
computed and compared, never how long it takes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (compare, control, flops_igev, harness, reference,
                       reference_igev, scenes, weights, weights_igev)
from raft_stereo_tpu.config import (IgevStereoConfig, RaftStereoConfig,
                                    config_from_dict)
from raft_stereo_tpu.eval.runner import InferenceRunner
from raft_stereo_tpu.kernels import corr_lookup
from raft_stereo_tpu.models import igev

CELL = "igev.bulk.kitti"
SEED = 2147483659
HW = (64, 128)
ITERS = 2


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


@pytest.fixture(scope="module")
def model(cell):
    """The cell's model as run, with 8 bins (a 1/4 map 32 wide)."""
    return dict(cell["config"]["model"], max_disp=32)


@pytest.fixture(scope="module")
def table(model):
    return weights_igev.make_weights(model, SEED)


@pytest.fixture(scope="module")
def pair():
    return scenes.make_pairs(7, 1, HW)[0]


_REFS = {}          # (precision, pair id) -> the reference's answer
_FWD = {}


def _reference(model, table, pair, lower=None):
    """The reference as the cell's post-pass runs it: one program for
    float32 (``which`` 0) and the bfloat16 unit (1)."""
    key = (lower, id(pair))
    if key not in _REFS:
        if "fwd" not in _FWD:
            _FWD["fwd"] = reference_igev.program(
                model, ITERS, (control.LOWER["bf16"],))
        with jax.default_matmul_precision("highest"):
            _REFS[key] = np.asarray(_FWD["fwd"](
                table, *pair, {None: 0, "bf16": 1}[lower]))
    return _REFS[key]


def _program(model, table, pairs, **overrides):
    runner = InferenceRunner(config_from_dict({**model, **overrides}),
                             weights.nest(table), iters=ITERS)
    flows, _ = runner.run_batch([p[0] for p in pairs], [p[1] for p in pairs])
    return flows


# --------------------------------------- the program against the reference
@pytest.mark.parametrize("kernel,batch", [(True, 2), (False, 1)],
                         ids=["lookup-kernel-b2", "xla-lookup-b1"])
def test_program_agrees_with_the_reference_in_float32(
        model, table, pair, kernel, batch):
    """Every dtype float32: the whole model through the runner's staging,
    the combined lookup by its kernel (in the interpreter) and by XLA
    gathers, batch 1 and 2.  Tolerance 1e-4 of the largest disparity, as
    ``benchmark/tests/test_reference.py`` has it for RAFT-Stereo: both
    sides compute in float32 and differ in the order of their sums alone
    (read 1.1-1.4e-6 here).  The first execute says what it built."""
    corr_lookup._interpret_override = kernel or None
    said = corr_lookup.path_choices()
    try:
        pairs = [pair] + scenes.make_pairs(8, batch - 1, HW)
        flows = _program(model, table, pairs, mixed_precision=False)
    finally:
        corr_lookup._interpret_override = None
    new = [m for m, n in corr_lookup.path_choices().items()
           if n != said.get(m, 0)]
    assert any(m.startswith("IGEV 3-D stage float32: 8 bins x 8 groups")
               for m in new)
    assert any(m.startswith("IGEV combined lookup G=8 D=8/4 W2=32/16")
               and ("one launch" in m) == kernel for m in new)
    want = _reference(model, table, pair)
    assert flows.shape == (batch,) + HW
    assert np.abs(want).mean() > 0.5              # something was matched
    assert np.abs(flows[0] - want).max() < 1e-4 * np.abs(want).max()


def _tail_share(model, table, pair, flow, cell):
    tail = cell["workload"]["compare"]["unit"]
    return compare.answer_numbers(
        flow, _reference(model, table, pair),
        _reference(model, table, pair, lower=tail["precision"]), tail)


def test_program_as_run_stays_inside_the_cells_unit(cell, model, table,
                                                    pair):
    """The model as the cell runs it (bfloat16 outside the two volumes),
    counted as the cell counts: its own limit is the tolerance."""
    nums = _tail_share(model, table, pair,
                       _program(model, table, [pair])[0], cell)
    assert nums["unit_p99_gap_px"] > 0
    assert nums["share_over_unit_tail"] <= \
        cell["workload"]["compare"]["limits"]["share_over_unit_tail"]


def test_gev_sampled_at_x_minus_d_is_not_correct(cell, model, table, pair,
                                                 monkeypatch):
    """A planted fault: the GEV sampled at the all-pairs volume's centre
    ``x - d`` and not at the disparity.  The cell's comparison fails it."""
    real = igev.lookup_geo_xla
    k = 2 * model["corr_radius"] + 1
    g = weights_igev.GROUPS

    def faulty(gev_pyr, vol_pyr, disp, radius):
        x = jnp.arange(disp.shape[-1], dtype=jnp.float32)
        wrong = real(gev_pyr, vol_pyr, x - disp, radius)
        right = real(gev_pyr, vol_pyr, disp, radius)
        per_level = (g + 1) * k
        is_gev = (jnp.arange(wrong.shape[-1]) % per_level) < g * k
        return jnp.where(is_gev, wrong, right)

    monkeypatch.setattr(igev, "lookup_geo_xla", faulty)
    nums = _tail_share(model, table, pair,
                       _program(model, table, [pair])[0], cell)
    assert nums["share_over_unit_tail"] > \
        cell["workload"]["compare"]["limits"]["share_over_unit_tail"]


@pytest.mark.parametrize("shape", [(1, 5, 7, 6), (1, 3, 4, 5, 6)],
                         ids=["2d", "3d"])
def test_the_references_transposed_conv_is_torchs(shape):
    """``reference_igev.deconv`` (jax's transposed convolution of the stored
    kernel) against torch's definition of ``ConvTranspose(k=4, s=2, p=1)``
    written out: input pixel i adds ``x[i] W[t]`` at output ``2 i - 1 + t``,
    W the stored kernel flipped, the outputs at -1 and 2n dropped."""
    import itertools

    rng = np.random.default_rng(5)
    nd = len(shape) - 2
    x = rng.standard_normal(shape).astype(np.float32)
    k = rng.standard_normal((4,) * nd + (shape[-1], 3)).astype(np.float32)
    n = shape[1:1 + nd]
    out = np.zeros((shape[0],) + tuple(2 * s + 2 for s in n) + (3,))
    wt = k[(slice(None, None, -1),) * nd]
    for t in itertools.product(range(4), repeat=nd):
        out[(slice(None),) + tuple(slice(ti, ti + 2 * s, 2)
                                   for ti, s in zip(t, n))] += x @ wt[t]
    want = out[(slice(None),) + tuple(slice(1, 1 + 2 * s) for s in n)]
    got = reference_igev.deconv({"params/d/kernel": jnp.asarray(k)}, "d",
                                jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------- the combined lookup
@pytest.mark.parametrize("b,h,w1,bins,w2", [
    (1, 2, 32, 8, 32),        # the tests' model: D 8/4, one partial block
    (2, 3, 160, 48, 160),     # the published bins; two blocks along W1
], ids=["d8-w32", "d48-w160-two-blocks"])
def test_combined_lookup_kernel_against_plain_sampling(b, h, w1, bins, w2):
    """The kernel in the interpreter against ``reference.sample_linear``
    (the benchmark's own linear window sampler) on random volumes, with
    disparities that reach past both ends of both volumes; and the
    program's XLA fallback against the same."""
    rng = np.random.default_rng(3)
    g, r = 8, 4
    gev = [jnp.asarray(rng.standard_normal((b, h, g, bins, w1)), jnp.float32)]
    vol = [jnp.asarray(rng.standard_normal((b, h, w2, w1)), jnp.float32)]
    for _ in range(1):
        gev.append(igev._pool_last2(gev[-1]))
        vol.append(igev._pool_last2(vol[-1]))
    disp = jnp.asarray(rng.uniform(-6, bins + 6, (b, h, w1)), jnp.float32)
    dx = jnp.arange(-r, r + 1, dtype=jnp.float32)
    x = jnp.arange(w1, dtype=jnp.float32)
    want = []
    for i, (gv, v) in enumerate(zip(gev, vol)):
        at = (disp / 2 ** i)[:, :, None, :, None] + dx
        want.append(jnp.moveaxis(reference.sample_linear(
            jnp.swapaxes(gv, -1, -2), jnp.broadcast_to(
                at, gv.shape[:3] + (w1, 2 * r + 1))), 2, 3).reshape(
                    b, h, w1, -1))
        want.append(reference.sample_linear(
            jnp.swapaxes(v, -1, -2), ((x - disp) / 2 ** i)[..., None] + dx))
    want = np.asarray(jnp.concatenate(want, -1))
    rb = corr_lookup.geo_row_block(tuple(v.shape[3] for v in gev),
                                   tuple(v.shape[2] for v in vol), g, r)
    assert rb == 8
    corr_lookup._interpret_override = True
    try:
        got = np.asarray(corr_lookup.lookup_geo_fused(gev, vol, disp, r, rb))
    finally:
        corr_lookup._interpret_override = None
    assert got.shape == (b, h, w1, 2 * (g + 1) * (2 * r + 1))
    # the kernel takes a window's fraction once, the plain sampler a tap at
    # a time from ``c + k``: float32 positions near 50 differ by ~4e-6,
    # times a difference of two normal entries
    np.testing.assert_allclose(got, want, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(igev.lookup_geo_xla(gev, vol, disp, r)), want, atol=1e-4)


def test_the_references_tent_is_linear_sampling():
    """``reference_igev.tent`` as the reference uses it (a product with the
    weights) against ``reference.sample_linear``'s two gathers, at
    positions inside, on and past both ends of a row."""
    rng = np.random.default_rng(4)
    vol = jnp.asarray(rng.standard_normal((2, 3, 40)), jnp.float32)
    x = jnp.asarray(rng.uniform(-3, 43, (2, 3, 9)), jnp.float32)
    x = x.at[0, 0, :3].set(jnp.asarray([0.0, 39.0, 17.0]))
    with jax.default_matmul_precision("highest"):
        got = jnp.einsum("...w,...kw->...k", vol,
                         reference_igev.tent(x, 40))
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        reference.sample_linear(vol, x)), rtol=1e-6, atol=1e-6)


# ------------------------------------------------------ the cells' files
def test_the_cells_files_load_and_state_what_runs(cell):
    wl, config = cell["workload"], cell["config"]
    assert cell["chips"] == 1 and config["reduced"] == [] and \
        config["env"] == {}
    assert {m["name"] for m in cell["end_to_end"]} == {"pairs_per_s",
                                                       "setup_s"}
    assert wl["traffic"]["image_hw"] == [375, 1242]
    assert wl["compare"]["unit"]["precision"] == "bf16"
    assert wl["compare"]["answers"] == 6
    assert config_from_dict(config["model"]) == IgevStereoConfig(
        mixed_precision=True)
    assert wl["entry"] == "bulk_runner_igev" and wl["iters"] == 32
    assert wl["traffic"]["pool_pairs"] == 2 * wl["traffic"]["pairs_per_call"]
    assert {m["name"] for m in cell["per_layer"]} == {
        "geo_lookup_roofline.igev", "gev_share_pct.igev",
        "gru_loop_share_pct.igev", "step_mfu_pct.igev",
        "device_idle_pct.igev", "gev_stage_share_pct.igev"}


def test_the_works_arithmetic_at_kitti_size(cell):
    model = cell["config"]["model"]
    per_pair = flops_igev.forward_flops(model, 384, 1248, 32)
    assert 4.5e12 < per_pair < 5.0e12           # 4.757 TFLOP a KITTI pair
    loop = per_pair - flops_igev.forward_flops(model, 384, 1248, 0)
    assert 0.9 < loop / per_pair < 0.92          # the loop is 90 % of it
    work = flops_igev.geo_lookup_work(model, 384, 1248)
    assert flops_igev.lookup_taps(model, 384, 1248) == 96 * 312 * 162
    assert work["bytes"] == pytest.approx(41.09e6, rel=1e-3)


# ------------------------------------------------- the metrics' readers
def _observed(cell, trace):
    return {"cell": cell, "seconds": 50.0, "pairs_completed": 500,
            "counters": {}, "trace": trace, "device_kind": "TPU v5 lite"}


def test_readers_on_a_reduced_trace(cell):
    """A trace as ``trace_reduce`` and ``post_igev`` hand it over, with two
    lookups' worth of one launch each."""
    trace = {"busy_s": 4.0, "window_s": 5.0,
             "scopes": {"gru_iter": 3.0, "gev": 0.4},
             "kernels": {"geo_lookup": {
                 "seconds": 0.002, "launches": 2,
                 "out_elements": 2 * 96 * 312 * 162}}}
    got = {k: v["value"] for k, v in harness.read_per_layer(
        cell, _observed(cell, trace)).items()}
    assert got["gru_loop_share_pct.igev"] == pytest.approx(75.0)
    assert got["gev_share_pct.igev"] == pytest.approx(10.0)
    assert got["device_idle_pct.igev"] == pytest.approx(20.0)
    # two lookups of 41.09e6 B at 819e9 B/s over 2 ms
    assert got["geo_lookup_roofline.igev"] == pytest.approx(
        100 * 2 * 41.094e6 / 819e9 / 0.002, rel=1e-3)
    # 500 pairs of 4.757e12 in 50 s against 197e12
    assert got["step_mfu_pct.igev"] == pytest.approx(24.15, rel=2e-3)


def test_readers_find_nothing_where_there_is_nothing(cell):
    """A parent's trace, or a run without the 3-D stage's marker: the
    readers leave their metrics out and do not raise."""
    empty = {"busy_s": 0.0, "window_s": 1.0, "scopes": {"gru_iter": 0.0},
             "kernels": {}}
    for trace in (empty, None):
        assert set(harness.read_per_layer(cell, _observed(cell, trace))) \
            == {"step_mfu_pct.igev"}


def test_the_3d_stage_is_found_by_the_rank_of_its_results():
    """``post_igev.rank5_seconds`` on a device plane as the profiler gives
    it: operations whose result has five axes or more (the 3-D stage: the
    v5e compiler splits W in some, giving six) count, their overlaps once;
    a four-axis one does not; a trace with no device plane gives None."""
    from types import SimpleNamespace as NS

    from benchmark import post_igev

    def op(start, end, name):
        return NS(start_ns=start, duration_ns=end - start, name=name,
                  stats=[])

    plane = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        op(0, 10, "%fusion.1 = bf16[48,96,8,40,8]{4,2,3,1,0} fusion(%p)"),
        op(5, 20, "%fusion.2 = bf16[16,96,312,128]{3,0,2,1} fusion(%q)"),
        op(8, 14, "%convolution.3 = bf16[24,48,1,8,20,16]{5,3} conv(%r)"),
        op(30, 40, "%fusion.4 = (f32[1,96,8,48,312]{4}, f32[]) fusion(%s)"),
    ])])
    assert post_igev.rank5_seconds(NS(planes=[plane])) == \
        pytest.approx(24e-9)
    host = NS(name="/host:CPU", lines=[])
    assert post_igev.rank5_seconds(NS(planes=[host])) is None


# ---------------------------- the kind through a checkpoint and the CLIs
def _igev_checkpoint(path, table):
    from raft_stereo_tpu.training import checkpoint

    cfg = IgevStereoConfig(max_disp=32)
    tree = weights.nest(table)
    checkpoint.save_weights(path, cfg, tree["params"],
                            batch_stats=tree["batch_stats"])
    return cfg


def test_a_checkpoint_carries_its_kind(tmp_path, table):
    from raft_stereo_tpu.cli import common
    from raft_stereo_tpu.training import checkpoint

    path = str(tmp_path / "igev")
    cfg = _igev_checkpoint(path, table)
    assert checkpoint.is_valid_checkpoint(path)
    assert checkpoint.load_config(path) == cfg
    got, variables = common.load_any_checkpoint(path)
    assert got == cfg and set(variables) == {"params", "batch_stats"}
    with pytest.raises(ValueError, match="config_from_dict"):
        RaftStereoConfig.from_dict(cfg.to_dict())
    assert config_from_dict(RaftStereoConfig().to_dict()) == \
        RaftStereoConfig()


@pytest.mark.parametrize("option", [
    "exit_threshold_px", "exit_min_iters", "quant", "run_stream",
    "evaluate --sequence", "demo --sequence"])
def test_raft_only_options_are_refused_by_name(tmp_path, table, option):
    """What only RAFT-Stereo's program has (the convergence-gated loop,
    int8 weights, warm-start streaming), asked of IGEV-Stereo through the
    runner or the CLIs: ``ArchitectureLacksOption``, before anything
    compiles or any image is read."""
    from raft_stereo_tpu.cli import demo, evaluate
    from raft_stereo_tpu.eval.runner import ArchitectureLacksOption

    cfg = IgevStereoConfig(max_disp=32)
    variables = weights.nest(table)
    given = {"exit_threshold_px": 0.5, "exit_min_iters": 2, "quant": "int8"}
    with pytest.raises(ArchitectureLacksOption, match="'igevstereo'"):
        if option in given:
            InferenceRunner(cfg, variables, iters=ITERS,
                            **{option: given[option]})
        elif option == "run_stream":
            img = np.zeros(HW + (3,), np.uint8)
            InferenceRunner(cfg, variables, iters=ITERS).run_stream(img, img)
        else:
            path = str(tmp_path / "igev")
            _igev_checkpoint(path, table)
            if option.startswith("evaluate"):
                evaluate.run_eval(evaluate.build_parser().parse_args(
                    ["--restore_ckpt", path, "--dataset", "kitti",
                     "--sequence"]))
            else:
                demo.run_demo(demo.build_parser().parse_args(
                    ["--restore_ckpt", path, "-l", "l.png", "-r", "r.png",
                     "--sequence"]))


def test_the_serving_engine_refuses_it_by_name():
    from raft_stereo_tpu.serving.engine import (ArchitectureNotServed,
                                                ServingEngine)

    with pytest.raises(ArchitectureNotServed, match="'igevstereo'"):
        ServingEngine(IgevStereoConfig(), {})
