"""The published full-resolution configuration (``raftstereo-fullres``) and
its cell ``fullres.bulk.middlebury-f``, on the CPU at a tiny size: the
program against the benchmark's plain reference with the configuration's
own model dict, the cell's files, a rehearsed run of the cell with a
planted fault beside it, the readers of its per-layer metrics, and the
choices the runner's first ``infer.execute`` of a shape carries.  What is
counted and compared, never how long it takes.
"""

import json

import jax
import numpy as np
import pytest

from benchmark import (compare, control, flops, harness, reference,
                       reference_staged, run, scenes, weights)
from raft_stereo_tpu.config import RaftStereoConfig
from raft_stereo_tpu.eval.runner import (InferenceRunner,
                                         effective_inference_config)
from raft_stereo_tpu.kernels import corr_alt, corr_lookup

CELL = "fullres.bulk.middlebury-f"
SEED = 2147483659
ITERS = 3
# the tests' size of the cell: the published widths and levels on a 60x100
# pair, and the sequential fnet forced as 1984x2880 forces it on the chip
TINY = harness.TestRig(
    sizes={"iters": 2,
           "traffic": {"pool_pairs": 3, "image_hw": [60, 100]},
           "trace": {"calls": 1}},
    require_accelerator=False, device_kind="TPU v5 lite",
    program_overrides={"sequential_fnet_pixels": 1000})


@pytest.fixture(autouse=True)
def _kernels_interpreted():
    """The lookup kernel itself runs (in the interpreter), as on the chip,
    and not its XLA fallback."""
    corr_lookup._interpret_override = True
    yield
    corr_lookup._interpret_override = None


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


@pytest.fixture(scope="module")
def model(cell):
    return cell["config"]["model"]


_BUILT = {}        # (model as JSON, seed) -> the weight table


def _weights_once(cfg, seed, build=weights.make_weights):
    """``make_weights`` compiles anew on every call (half a minute on this
    CPU); a rehearsed run makes two and the tests share one seed."""
    key = (json.dumps(cfg, sort_keys=True), seed)
    if key not in _BUILT:
        _BUILT[key] = build(cfg, seed)
    return _BUILT[key]


@pytest.fixture(scope="module")
def table(model):
    return _weights_once(model, SEED)


@pytest.fixture
def one_weight_build(monkeypatch):
    monkeypatch.setattr(weights, "make_weights", _weights_once)


# ------------------------------------------------------------ the files
def test_the_cells_files_state_the_model_as_run(cell, model):
    config, wl = cell["config"], cell["workload"]
    assert config["reduced"] == [] and config["env"] == {}
    as_run = dict(RaftStereoConfig().to_dict(), corr_backend="alt",
                  mixed_precision=True, corr_fp32=True)
    assert {k: model[k] for k in as_run} == json.loads(json.dumps(as_run))
    assert set(model) == set(as_run)
    cfg = RaftStereoConfig.from_dict(model)
    # the runner's own rule at >= 16 iterations changes nothing: the file
    # already says what runs, so the roofline reader takes 4-byte features
    assert effective_inference_config(cfg, wl["iters"]) == cfg
    assert wl["iters"] == 32 and wl["entry"] == "bulk_runner_staged"
    assert wl["traffic"]["image_hw"] == [1984, 2880]
    assert cell["chips"] == 1
    assert {m["name"] for m in cell["end_to_end"]} == {"pairs_per_s",
                                                       "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "step_mfu_pct.full", "gru_loop_share_pct.full",
        "encoder_share_pct.full", "corr_alt_roofline.full",
        "device_idle_pct.full", "fnet_cnet_share_pct.full"}


def test_the_works_arithmetic_at_full_size(model):
    """The numbers ISSUE 28 reckons with: 62.24 TFLOP a pair (11.9x a
    KITTI pair) and 1.10 GB a float32 lookup, memory-bound."""
    per_pair = flops.forward_flops(model, 1984, 2880, 32)
    assert per_pair == pytest.approx(62.24e12, rel=2e-3)
    work = flops.alt_lookup_work(model, 1984, 2880, 4)
    assert work["bytes"] == pytest.approx(1.10e9, rel=5e-3)
    least, bound = flops.least_seconds(work, harness.peaks_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(1.35e-3, rel=0.01)


# ------------------------------------- the program against the reference
def _answer(model, table, pairs, iters=ITERS, **overrides):
    cfg = RaftStereoConfig.from_dict({**model, **overrides})
    runner = InferenceRunner(cfg, weights.nest(table), iters=iters)
    flows, _ = runner.run_batch([p[0] for p in pairs], [p[1] for p in pairs])
    return flows


def _reference(model, table, pair, iters=ITERS, lower=None):
    w = dict(table, __lower__=control.LOWER[lower]) if lower else table
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.disparity(model, w, *pair, iters))


@pytest.mark.parametrize("hw,batch,per_level,sequential", [
    # W2 = 32/16/8/4 (100 pads to 128): one block, partly filled
    ((60, 100), 1, False, True),
    # W2 = 48/24/12/6, two pairs a call, one launch a level as at W2 = 720
    ((40, 170), 2, True, True),
    # W2 = 136/68/34/17: two blocks along W1, the second partly filled
    ((33, 530), 1, True, False),
], ids=["w2-32-single-seq", "w2-48-per-level-seq-b2",
        "w2-136-per-level-batched"])
def test_program_agrees_with_the_reference_in_float32(
        model, table, monkeypatch, hw, batch, per_level, sequential):
    """The configuration's paths with every dtype float32: the float32
    no-volume kernel at 4 levels and 1/4 resolution under both launch
    plans, the sequential fnet scan, batch 1 and 2.  Tolerance 1e-4 of the
    largest disparity, as ``benchmark/tests/test_reference.py`` has it for
    the accepted configurations: both sides compute in float32 and differ
    by the order of their sums alone."""
    if per_level:
        monkeypatch.setattr(corr_alt, "_MOSAIC_SCOPED_VMEM", 0)
    said = corr_lookup.path_choices()
    pairs = scenes.make_pairs(5, batch, hw)
    flows = _answer(model, table, pairs, mixed_precision=False,
                    sequential_fnet_pixels=1000 if sequential else None)
    new = [m for m, n in corr_lookup.path_choices().items()
           if n != said.get(m, 0)]
    assert any(m.startswith("alt lookup") and "float32" in m
               and ("one launch per level" in m) == per_level for m in new)
    assert any(m.startswith("fnet") and ("sequential" in m) == sequential
               for m in new)
    for flow, pair in zip(flows, pairs):
        want = _reference(model, table, pair)
        assert np.abs(want).mean() > 0.5          # something was matched
        assert np.abs(flow - want).max() < 1e-4 * np.abs(want).max()


def test_program_as_run_stays_inside_the_cells_unit(cell, model, table):
    """The model dict as the cell runs it (bfloat16 outside the
    correlation), counted as the cell counts: the share of pixels further
    from the float32 reference than the cell's multiple of the gap the
    reference itself has with bfloat16 products.  The cell's own limit is
    the tolerance: a rounding that bfloat16 does not explain fails it."""
    tail = cell["workload"]["compare"]["unit"]
    limit = cell["workload"]["compare"]["limits"]["share_over_unit_tail"]
    (pair,) = scenes.make_pairs(7, 1, (60, 100))
    flow = _answer(model, table, [pair], sequential_fnet_pixels=1000)[0]
    nums = compare.answer_numbers(
        flow, _reference(model, table, pair),
        _reference(model, table, pair, lower=tail["precision"]), tail)
    assert nums["unit_p99_gap_px"] > 0
    assert nums["share_over_unit_tail"] <= limit


def test_staged_reference_is_the_plain_reference(model, table):
    """``reference_staged`` runs ``reference.py``'s own layers as four
    programs (one program does not fit a 1984x2880 pair on the chip); the
    copy of ``forward``'s second half it carries may not drift.  The same
    operations in the same order in float32; a convolution over one image
    may sum in another order than over a batch of two, and XLA may fuse
    across a program's edge differently: 1e-5 of the largest disparity, a
    tenth of what the program is allowed against either."""
    (pair,) = scenes.make_pairs(11, 1, (60, 100))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference_staged.make_disparity(model, ITERS)(
            table, *pair))
    want = _reference(model, table, pair)
    assert got.shape == want.shape == (60, 100)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    with pytest.raises(ValueError, match="shared backbone"):
        reference_staged.make_disparity(dict(model, shared_backbone=True), 1)


def test_staged_unit_is_the_plain_unit_as_a_yardstick(model, table):
    """With every product's inputs rounded to bfloat16 a last-bit
    difference upstream flips roundings downstream, so the two units agree
    pixel by pixel only to bfloat16's own noise; the cell reads one number
    of the unit, the 99th percentile of its gap to the float32 reference.
    On 6,000 pixels that percentile rests on 60 of them and read 7-8 % apart
    between the two (0.081 / 0.087 and 0.067 / 0.073 px on two pairs): 20 %
    here, against the 1.25x + 0.15 px the cell's limit is set from."""
    (pair,) = scenes.make_pairs(11, 1, (60, 100))
    lower = control.LOWER["bf16"]
    with jax.default_matmul_precision("highest"):
        staged = np.asarray(reference_staged.make_disparity(
            model, ITERS, lower)(table, *pair))
    want = _reference(model, table, pair)
    plain = _reference(model, table, pair, lower="bf16")
    p99 = [np.percentile(np.abs(u - want), 99) for u in (staged, plain)]
    assert p99[1] > 0.01                     # the rounding shows at all
    assert abs(p99[0] - p99[1]) < 0.2 * p99[1]


# ----------------------------------------------------- a rehearsed run
def _line(capsys, trace):
    assert run.run_cell(CELL, seed=SEED, seconds=1.5, trace=trace,
                        rig=TINY) == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1])


def test_rehearsed_run_is_correct_and_reads_its_metrics(capsys,
                                                        one_weight_build):
    line = _line(capsys, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["answers_compared"]["value"] == 1
    # the host stand-in trace of a bulk call holds no operation (the
    # entry keeps the host tracer off), so the trace's shares are left out
    assert set(line["metrics"]) == {"step_mfu_pct.full"}
    assert 0 < line["metrics"]["step_mfu_pct.full"]["value"] < 100
    assert line["device"]["memory_peak_bytes"] >= 0


def test_rehearsed_run_with_a_shifted_answer_is_not_correct(
        capsys, monkeypatch, one_weight_build):
    from benchmark.tests import faults

    faults.shift_batch_rows(monkeypatch)
    line = _line(capsys, trace=False)
    assert line["correct"] is False
    c = line["compared"]["share_over_unit_tail"]
    assert c["value"] > c["limit"]
    assert set(line["metrics"]) == {"pairs_per_s", "setup_s"}


# ------------------------------------------------- the metrics' readers
def _observed(cell, trace):
    return {"cell": cell, "seconds": 50.0, "pairs_completed": 20,
            "counters": {}, "trace": trace, "device_kind": "TPU v5 lite"}


def test_readers_on_a_reduced_trace(cell):
    """A trace as ``trace_reduce`` hands it over, with two lookups' worth
    of launches (four a lookup, each writing one level's 9 taps)."""
    h8, w8 = 496, 720
    trace = {"busy_s": 4.0, "window_s": 5.0,
             "scopes": {"gru_iter": 2.8, "loops": 3.6},
             "kernels": {"corr_alt": {"seconds": 0.05, "launches": 8,
                                      "out_elements": 2 * 4 * h8 * w8 * 9}}}
    got = {k: v["value"] for k, v in harness.read_per_layer(
        cell, _observed(cell, trace)).items()}
    assert got["gru_loop_share_pct.full"] == pytest.approx(70.0)
    assert got["encoder_share_pct.full"] == pytest.approx(20.0)
    assert got["device_idle_pct.full"] == pytest.approx(20.0)
    # two lookups of 1.104e9 B at 819e9 B/s over 0.05 s
    assert got["corr_alt_roofline.full"] == pytest.approx(
        100 * 2 * 1.1045e9 / 819e9 / 0.05, rel=2e-3)
    # 20 pairs of 62.24e12 in 50 s against 197e12
    assert got["step_mfu_pct.full"] == pytest.approx(12.64, rel=2e-3)
    assert all(0 < v < 100 for v in got.values())


def test_readers_find_nothing_where_there_is_nothing(cell):
    """A parent's trace has the scopes or not; a reader with nothing to
    read leaves its metric out and does not raise."""
    empty = {"busy_s": 0.0, "window_s": 1.0, "scopes": {"gru_iter": 0.0},
             "kernels": {}}
    assert set(harness.read_per_layer(cell, _observed(cell, empty))) == {
        "step_mfu_pct.full"}
    assert set(harness.read_per_layer(cell, _observed(cell, None))) == {
        "step_mfu_pct.full"}
    no_encoders = {"busy_s": 1.0, "window_s": 2.0,
                   "scopes": {"gru_iter": 0.5, "loops": 0.5}, "kernels": {}}
    assert set(harness.read_per_layer(
        cell, _observed(cell, no_encoders))) == {
        "step_mfu_pct.full", "gru_loop_share_pct.full",
        "device_idle_pct.full"}


# ------------------------------- the choices a first execute carries
def test_first_execute_of_a_shape_says_what_it_built(model, table,
                                                     monkeypatch):
    """``infer.execute`` carries ``compiled`` and ``paths`` on the call
    that traced and built a (padded shape, batch), and on no later one;
    another batch of the same shape builds again and says the same, but
    for the trunk's stage, whose choice is the batch's."""
    cfg = RaftStereoConfig.from_dict(dict(model, sequential_fnet_pixels=1000))
    runner = InferenceRunner(cfg, weights.nest(table), iters=2)
    recorded = []
    real = runner.phases.record
    monkeypatch.setattr(
        runner.phases, "record",
        lambda name, *a, **attrs: (recorded.append((name, attrs)),
                                   real(name, *a, **attrs)))
    pairs = scenes.make_pairs(3, 2, (60, 100))
    lefts, rights = [p[0] for p in pairs], [p[1] for p in pairs]
    runner.run_batch(lefts[:1], rights[:1])
    runner.run_batch(lefts[:1], rights[:1])
    runner.run_batch(lefts, rights)
    first, second, other = [a for n, a in recorded if n == "execute"]
    assert first["compiled"] == 1 and other["compiled"] == 1
    assert "compiled" not in second and "paths" not in second
    paths = first["paths"].split("; ")
    others = other["paths"].split("; ")

    # ``paths`` lists the choices in the order the process first heard
    # them, and other programs of this process may have said the trunk's
    # or the mask's first: those records are held apart from that order
    mask = "upsampling mask: once after the loop (2 iterations)"
    assert mask in paths and mask in others

    def trunk(ps):
        return [p for p in ps if p.startswith("trunk stage")]
    assert trunk(paths) == [
        "trunk stage 64x128 x1 images: plain (an odd count)"]
    assert trunk(others) == [
        "trunk stage 64x128 x2 images: two images a lane row"]
    paths = [p for p in paths if p not in trunk(paths) + [mask]]
    assert [p for p in others if p not in trunk(others) + [mask]] == paths
    assert [p.split(":")[0] for p in paths] == [
        "fnet 64x128",
        "alt lookup D=256 W2=32/16/8/4 float32",
        "ConvGRU level W=8 Cin=256 Ch=128 16-bit",
        "ConvGRU level W=16 Cin=384 Ch=128 16-bit",
        "ConvGRU level W=32 Cin=384 Ch=128 16-bit"]
    assert "sequential" in paths[0]
    assert set(paths) <= set(corr_lookup.path_choices())
    assert all(type(v) in (int, str, bool) for _, attrs in recorded
               for v in attrs.values())
