"""The published SceneFlow training recipe (``raftstereo-sceneflow-train``)
and its cell ``sceneflow.train.b4``, on the CPU at a tiny size (64x96
crops, 3 iterations, published widths): the cell's files, the program's
train step against the benchmark's plain training reference (the
rehearsed runs of the cell and their planted faults are in
``tests/test_train_cell.py``), ``train()``'s stop callable and its
``train.*`` phases, and the readers of the new per-layer metrics.  What is counted and compared, never
how long it takes.
"""

import dataclasses
import inspect
import json

import jax
import numpy as np
import pytest

from benchmark import (control, flops, flops_train, harness, post_train,
                       reference_train, scenes_tree, weights)
from benchmark.entries import train_job
from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
from raft_stereo_tpu.data.loader import StereoLoader
from raft_stereo_tpu.telemetry import TrainTelemetry
from raft_stereo_tpu.telemetry.train_metrics import TRAIN_PHASES
from raft_stereo_tpu.training import train_loop
from raft_stereo_tpu.training.state import create_train_state
from raft_stereo_tpu.training.step import make_train_step

B4 = "sceneflow.train.b4"
SEED = 2147483659
HW, ITERS = (64, 96), 3


# The cells' limits are the chip's (PERF.md section 2: the program reads
# 0.97-1.30 units there, the int8 control 2.8 and more).  This CPU's
# bfloat16 convolutions round otherwise than the chip's matrix unit: at the
# tests' size the program reads 2.08 units over all parameters (1.31 fnet,
# 1.35 cnet, 1.06 the context convolutions, 2.66 the update block) and the
# control 4.17 (2.39, 4.59, 4.46, 4.79), so the rehearsals carry limits
# between THOSE readings; the runs are seeded and repeat to the digit.
CPU_LIMITS = {"loss_gap_rel": 0.05,
              "grad_gap_units": 3.0, "grad_gap_units_fnet": 1.8,
              "grad_gap_units_cnet": 2.5, "grad_gap_units_context_zqr": 2.2,
              "grad_gap_units_update_block": 3.6, "update_gap_rel": 0.4}


def _tiny(data_parallel: int = 1) -> harness.TestRig:
    """The tests' size of the cell: the published widths on 64x96 crops of
    80x120 frames, 3 iterations, 2 pairs on one device (or 1 a device over
    four: the job the published batch needs on a v5e host, which has no
    cell yet)."""
    return harness.TestRig(
        sizes={"iters": ITERS, "warmup_steps": 1, "sync_steps": 1,
               "traffic": {"batch_size": 2 if data_parallel == 1 else 4,
                           "data_parallel": data_parallel,
                           "image_hw": list(HW), "pool_pairs": 8,
                           "frame_hw": [80, 120]},
               "compare": {"limits": CPU_LIMITS},
               "trace": {"steps": 2}},
        require_accelerator=False, device_kind="TPU v5 lite")


@pytest.fixture(scope="module", autouse=True)
def _compiled_once(tmp_path_factory):
    """A rehearsed run compiles its step in call A and again in call B (a
    new ``jit`` of the same program), and a second run does both again:
    half a minute each on this CPU.  For this module alone jax's persistent
    cache is on (``tests/conftest.py`` keeps it off), in a directory of the
    module's own, set through the one helper that may name one."""
    from jax.experimental.compilation_cache import compilation_cache

    from raft_stereo_tpu.serving.persist import (
        enable_persistent_compilation_cache)

    keys = ("jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    enable_persistent_compilation_cache(
        str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(B4)


@pytest.fixture(scope="module")
def model(cell):
    return cell["config"]["model"]


_BUILT = {}        # (model as JSON, seed) -> the weight table


def _weights_once(cfg, seed, build=weights.make_weights):
    """``make_weights`` compiles anew on every call; a rehearsed run makes
    two and the tests share one seed."""
    key = (json.dumps(cfg, sort_keys=True), seed)
    if key not in _BUILT:
        _BUILT[key] = build(cfg, seed)
    return _BUILT[key]


@pytest.fixture
def one_weight_build(monkeypatch):
    monkeypatch.setattr(weights, "make_weights", _weights_once)


@pytest.fixture
def in_tmp_work(monkeypatch, tmp_path):
    """A run's scratch directory under the test's own."""
    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path / "bench_work"))


# ------------------------------------------------------------ the files
def test_the_cells_files_state_model_and_recipe_as_run(cell):
    config = cell["config"]
    assert config["reduced"] == [] and config["env"] == {}
    as_run = RaftStereoConfig(mixed_precision=True).to_dict()
    assert config["model"] == json.loads(json.dumps(as_run))
    recipe = TrainConfig(saturation_range=(0.0, 1.4)).to_dict()
    stated_by_cell = {"batch_size", "data_parallel", "image_size",
                      "train_iters", "seed"}
    assert config["train"] == json.loads(json.dumps(
        {k: v for k, v in recipe.items() if k not in stated_by_cell}))
    wl, tr = cell["workload"], cell["workload"]["traffic"]
    assert cell["chips"] == 1 and wl["entry"] == "train_job"
    # never 0 = "all devices": a one-chip cell on a four-chip host must
    # not spread
    assert (tr["batch_size"], tr["data_parallel"]) == (4, 1)
    as_set = TrainConfig.from_dict(train_job.recipe_of(cell, SEED))
    assert as_set == dataclasses.replace(
        TrainConfig(saturation_range=(0.0, 1.4)), batch_size=4,
        data_parallel=1, seed=SEED % (2 ** 31 - 1))
    assert as_set.image_size == (320, 720) and as_set.train_iters == 22
    assert tr["augmentation"] == {
        "spatial_scale": list(as_set.spatial_scale),
        "saturation_range": list(as_set.saturation_range)}
    # the loader is the one train() builds: the file states its defaults,
    # it sets none
    defaults = {k: p.default for k, p in inspect.signature(
        StereoLoader.__init__).parameters.items()}
    assert tr["loader"] == {
        "workers": defaults["num_workers"],
        "worker_type": defaults["worker_type"],
        "prefetch": defaults["prefetch"],
        "device_prefetch": train_loop._DEVICE_PREFETCH_DEPTH}
    assert (wl["steps_compared"], wl["warmup_steps"], wl["sync_steps"],
            wl["trace"]["steps"]) == (2, 3, 4, 4)
    assert {m["name"] for m in cell["end_to_end"]} == {"pairs_per_s",
                                                       "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "step_mfu_pct.train", "device_idle_pct.train",
        "data_wait_ms.train", "corr_lookup_bwd_roofline.train",
        "backward_share_pct.train", "recompute_share_pct.train",
        "optimizer_share_pct.train"}


def test_the_works_arithmetic_at_full_size(model):
    """The numbers ISSUE 32 reckons with: 1.860 TFLOP a 320x720 pair's
    forward at 22 iterations, 5.58 a trained pair."""
    assert flops.forward_flops(model, 320, 720, 22) == pytest.approx(
        1.860e12, rel=1e-3)
    assert flops_train.trained_pair_flops(model, 320, 720, 22) == (
        pytest.approx(5.58e12, rel=1e-3))
    assert flops_train.lookup_bwd_level0_elements(model, 320, 720) == (
        80 * 180 * 180)
    work = flops_train.lookup_bwd_work(model, 320, 720, 2)
    assert work["bytes"] == 80 * 180 * (4 * 10 * 2 + 4 + 36 * 2)


# --------------------------------- the program's step against the reference
def _batches(seed: int, n: int, batch: int):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        triples = [scenes_tree.make_pair(rng, HW) for _ in range(batch)]
        out.append({"image1": np.stack([t[0] for t in triples]),
                    "image2": np.stack([t[1] for t in triples]),
                    "flow": -np.stack([t[2] for t in triples]) * 0.3,
                    "valid": np.ones((batch,) + HW, np.float32)})
    return out


def _program_steps(model, table, batches, **overrides):
    """``train_step`` as ``make_train_step`` compiles it, from the seeded
    table: the state after the batches as ``post_train`` takes it, and each
    step's metrics."""
    cfg = RaftStereoConfig.from_dict({**model, **overrides})
    recipe = TrainConfig(batch_size=len(batches[0]["flow"]),
                         train_iters=ITERS, image_size=HW, data_parallel=1)
    tree = weights.nest(table)
    state = create_train_state(cfg, recipe, jax.random.PRNGKey(0),
                               (1,) + HW + (3,))
    state = state.replace(params=tree["params"],
                          batch_stats=tree["batch_stats"])
    step = make_train_step(recipe, mesh=None, donate=False)
    rows = []
    for batch in batches:
        state, metrics = step(state, batch)
        rows.append({k: float(v) for k, v in metrics.items()})
    moments = train_job._adam_moments(
        _as_containers(jax.device_get(state.opt_state)))
    flat = {g: {"params/" + p: a for p, a in train_job.flat_arrays(
        jax.device_get(t)).items()}
        for g, t in (("params", state.params), ("mu", moments["mu"]))}
    return flat, rows


def _as_containers(opt_state):
    """optax's named tuples as the dicts and lists a restored checkpoint
    holds in their place."""
    if hasattr(opt_state, "_asdict"):
        return {k: _as_containers(v) for k, v in opt_state._asdict().items()}
    if isinstance(opt_state, (tuple, list)):
        return [_as_containers(v) for v in opt_state]
    return opt_state


@pytest.fixture(scope="module")
def table(model):
    return _weights_once(model, SEED)


@pytest.fixture(scope="module")
def replayed(model, table):
    """The plain reference's two steps on two seeded batches of two."""
    batches = _batches(3, 2, 2)
    recipe = TrainConfig(batch_size=2, train_iters=ITERS, image_size=HW,
                         data_parallel=1).to_dict()
    arrays, mu, _nu, steps = reference_train.replay(model, recipe, table,
                                                    batches)
    return batches, recipe, {"params": arrays, "mu": mu}, steps


def test_program_step_agrees_with_the_reference_in_float32(
        model, table, replayed):
    """The recipe's step with every dtype float32 and every product at
    ``highest``, through the lookup kernel's own forward and backward (in
    the interpreter): loss, clipped gradients (Adam's first moment) module
    by module, and the parameters' change after two steps.  Both sides
    compute in float32 and differ by the order of their sums: 1e-4 of the
    loss and of the gradient's norm; 1e-3 of a module's gradient, 2e-2 of
    the feature encoder's (its instance norms divide by a spread that the
    untrained stem makes small: 8e-3 read); the parameters' change 1e-2
    (Adam's first steps are lr x sign(g): a gradient entry within rounding
    of nought flips a whole step: 2.5e-3 read)."""
    from raft_stereo_tpu.kernels import corr_lookup

    batches, _, want, want_steps = replayed
    corr_lookup._interpret_override = True
    try:
        with jax.default_matmul_precision("highest"):
            got, rows = _program_steps(model, table, batches,
                                       mixed_precision=False)
    finally:
        corr_lookup._interpret_override = None
    nums = post_train.training_numbers(table, got, rows, want, want_steps)
    assert want_steps[0]["grad_norm"] > 1.0         # the clip is at work
    assert nums["loss_gap_rel"] < 1e-4 and nums["grad_norm_gap_rel"] < 1e-4
    assert nums["grad_gap_rel"] < 1e-3
    for module in ("cnet", "context_zqr", "update_block"):
        assert nums[f"grad_gap_rel_{module}"] < 1e-3, module
    assert nums["grad_gap_rel_fnet"] < 2e-2
    assert nums["update_gap_rel"] < 1e-2


def test_the_int8_control_fails_the_cells_limits(cell, model, table,
                                                 replayed):
    """The control — the reference with every product's inputs rounded to
    int8, straight-through — in the program's place has to fail by at
    least one of the cells' limits.  (That the bfloat16 program stays
    inside every one of them is what the rehearsed runs below assert:
    ``correct`` true.)"""
    batches, recipe, want, want_steps = replayed
    compare = cell["workload"]["compare"]
    assert compare["unit"] == {"precision": "bf16"}

    def numbers(precision):
        arrays, mu, _nu, steps = reference_train.replay(
            model, recipe, table, batches,
            reference_train.straight_through(control.LOWER[precision]))
        return post_train.training_numbers(
            table, {"params": arrays, "mu": mu}, steps, want, want_steps)

    assert set(compare["limits"]) == set(CPU_LIMITS)
    ctl = post_train.in_units(numbers("int8"), numbers("bf16"))
    for limits in (CPU_LIMITS, compare["limits"]):
        assert any(ctl[name] > limit for name, limit in limits.items()), ctl


@pytest.mark.parametrize("moved,ok", [(1.0, True), (0.0, False),
                                      (2.0, False)])
def test_an_update_that_is_not_the_recipes_fails_the_cells_limits(
        cell, table, replayed, moved, ok):
    """After two steps at the schedule's first rate the losses and Adam's
    first moment do not depend on whether or how far the weights moved:
    ``update_gap_rel`` alone says so.  The reference's own state with its
    parameters' change scaled: left where they started (a step that applies
    nothing) and moved twice as far (twice the rate) both read 1 and fail
    the cell's limits, by that number and no other."""
    from benchmark import compare

    _, _, want, want_steps = replayed
    got = {"mu": want["mu"], "params": {
        k: table[k] + moved * (np.asarray(v) - table[k])
        for k, v in want["params"].items()}}
    nums = post_train.training_numbers(table, got, want_steps, want,
                                       want_steps)
    assert nums["update_gap_rel"] == pytest.approx(abs(moved - 1.0),
                                                   abs=1e-3)
    verdict = compare.decide(
        [post_train.in_units(nums, dict.fromkeys(nums, 1.0))],
        cell["workload"]["compare"]["limits"])
    assert all(c["ok"] for c in verdict) is ok
    assert [c["name"] for c in verdict if not c["ok"]] == (
        [] if ok else ["update_gap_rel"])


# -------------------------------------------- train(): stop, resume, phases
class _SyntheticDataset:
    def __len__(self):
        return 8

    def __getitem__(self, i, epoch=0):
        rng = np.random.default_rng([i, epoch])
        img = rng.uniform(0, 255, (32, 64, 3)).astype(np.float32)
        return {"image1": img, "image2": np.roll(img, 2, axis=1),
                "flow": np.full((32, 64), -2.0, np.float32),
                "valid": np.ones((32, 64), np.float32)}


def _small_train(path, telemetry=None, should_stop=None, restore=None,
                 num_steps=6):
    # fnet_norm="none": the smallest encoder
    mcfg = RaftStereoConfig(n_gru_layers=1, hidden_dims=(32,), fnet_dim=64,
                            fnet_norm="none")
    tcfg = TrainConfig(batch_size=2, train_iters=2, num_steps=num_steps,
                       image_size=(32, 64), data_parallel=1)
    loader = StereoLoader(_SyntheticDataset(), batch_size=2, num_workers=0)
    state = train_loop.train(
        mcfg, tcfg, name="t", checkpoint_dir=str(path / "ck"),
        log_dir=str(path / "runs"), loader=loader, use_mesh=False,
        telemetry=telemetry, should_stop=should_stop, restore=restore)
    return state, train_job.logged_losses(str(path / "runs"))


def test_should_stop_stops_as_a_signal_does_and_the_run_resumes(tmp_path):
    """Asked once a step boundary with the steps dispatched; true at 3:
    three steps, the final checkpoint with its sidecar, and a resumed run
    that ends where an unbroken one does, loss for loss (as the loop's
    logger wrote them under ``log_dir``: how the entry reads a run)."""
    asked = []

    def at_three(step, state):
        asked.append((step, int(state.step)))
        return step >= 3

    state, first = _small_train(tmp_path / "a", should_stop=at_three)
    assert asked == [(0, 0), (1, 1), (2, 2), (3, 3)]
    assert int(state.step) == 3 and sorted(first) == [1, 2, 3]
    final = str(tmp_path / "a" / "ck" / "t")
    from raft_stereo_tpu.training import checkpoint as ckpt

    assert ckpt.is_valid_checkpoint(final, deep=True)
    assert ckpt.load_runtime_state(final)["loop_step"] == 3
    resumed, rest = _small_train(tmp_path / "b", restore=final)
    whole, unbroken = _small_train(tmp_path / "c")
    assert int(resumed.step) == int(whole.step) == 6
    assert sorted(rest) == [4, 5, 6] and {**first, **rest} == unbroken
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(resumed.params)),
        jax.tree_util.tree_leaves(jax.device_get(whole.params))))


def _host_events(trace_dir, prefix):
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir) + "/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, dict(e.stats)) for e in line.events
                    if e.name.startswith(prefix)]
    return out


def test_train_phases_once_a_step_with_attributes(tmp_path):
    """In a capture: one ``train.data_wait``, ``dispatch`` and ``upload`` a
    step with their attributes, one ``drain`` and ``checkpoint`` at the
    end; the histograms count the same; and the histograms that predate the
    phases are the same readings.  With ``telemetry=None`` the capture
    holds no ``train.`` event at all."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    tm = TrainTelemetry()
    jax.profiler.start_trace(str(tmp_path / "on"), profiler_options=options)
    try:
        _small_train(tmp_path / "on_run", telemetry=tm, num_steps=4)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path / "on", "train.")
    by_name = {}
    for name, stats in events:
        by_name.setdefault(name, []).append(stats)
    assert set(by_name) == {"train." + p for p in TRAIN_PHASES}
    # the fifth wait finds the run complete; the prefetcher ran ahead
    assert [s["step"] for s in by_name["train.data_wait"]] == [1, 2, 3, 4, 5]
    assert [s["step"] for s in by_name["train.dispatch"]] == [1, 2, 3, 4]
    assert all(s["batch_size"] == 2 for s in by_name["train.dispatch"])
    uploads = sorted(s["step"] for s in by_name["train.upload"])
    assert uploads[:4] == [1, 2, 3, 4]
    assert all(s["batch_size"] == 2 and s["bytes"] == 2 * 32 * 64 * (
        2 * 3 * 4 + 2 + 1) for s in by_name["train.upload"])
    assert [(s["step"], s["window"]) for s in by_name["train.drain"]] == [
        (4, 4)]
    assert [s["step"] for s in by_name["train.checkpoint"]] == [4, 4]
    hist = tm.phases.histograms
    assert {p: hist[p].count for p in TRAIN_PHASES} == {
        "data_wait": 5, "dispatch": 4, "drain": 1, "checkpoint": 2,
        "upload": len(uploads)}
    assert tm.step_time.count == 4 and tm.data_wait.count == 4
    assert tm.step_time.sum == pytest.approx(hist["dispatch"].sum)
    assert tm.drain_time.sum == pytest.approx(hist["drain"].sum)
    assert tm.checkpoint_time.sum == pytest.approx(hist["checkpoint"].sum)

    jax.profiler.start_trace(str(tmp_path / "off"), profiler_options=options)
    try:
        _small_train(tmp_path / "off_run", num_steps=2)
    finally:
        jax.profiler.stop_trace()
    assert _host_events(tmp_path / "off", "train.") == []


# -------------------------------------------------------------- the readers
def test_readers_on_a_reduced_trace_and_on_nothing(cell):
    pairs = 5 * 4                             # one lookup backward each
    level0 = flops_train.lookup_bwd_level0_elements(cell["config"]["model"],
                                                    320, 720)
    observed = {
        "cell": cell, "seconds": 50.0, "pairs_completed": 400,
        "device_kind": "TPU v5 lite",
        "counters": {'train_phase_seconds_sum{phase="data_wait"}': 0.5,
                     'train_phase_seconds_count{phase="data_wait"}': 100.0,
                     'train_phase_seconds_sum{phase="dispatch"}': 9.0,
                     'train_phase_seconds_count{phase="dispatch"}': 100.0},
        "trace": {"busy_s": 1.8, "window_s": 2.0, "scopes": {},
                  "kernels": {
                      "corr_lookup_bwd": {"seconds": 0.04, "launches": 110,
                                          "out_elements": pairs * level0}}}}
    got = harness.read_per_layer(cell, observed)
    assert got["step_mfu_pct.train"]["value"] == pytest.approx(
        100 * 3 * flops.forward_flops(cell["config"]["model"], 320, 720, 22)
        * 400 / 50.0 / 197e12)
    assert got["device_idle_pct.train"]["value"] == pytest.approx(10.0)
    assert got["data_wait_ms.train"]["value"] == pytest.approx(5.0)
    work = flops_train.lookup_bwd_work(cell["config"]["model"], 320, 720, 2)
    assert got["corr_lookup_bwd_roofline.train"]["value"] == pytest.approx(
        100 * pairs * work["bytes"] / 819e9 / 0.04)
    assert 0 < got["corr_lookup_bwd_roofline.train"]["value"] < 100
    # a program without the spans, a trace without the kernels, no window
    nothing = dict(observed, counters={}, pairs_completed=0,
                   trace={"busy_s": 0.0, "window_s": 0.0, "scopes": {},
                          "kernels": {}})
    assert harness.read_per_layer(cell, nothing) == {}
    assert harness.read_per_layer(cell, dict(nothing, trace=None)) == {}
