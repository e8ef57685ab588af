"""The control at a size a test run can hold (published widths, a 96x160
image, the cell's own iteration count): the nearest precision below the one
the configuration states has to fail the cell's own limits where the
program, run as the configuration states, passes them."""

import jax
import numpy as np
import pytest

from benchmark import compare, control, harness, reference, scenes, weights

HW = (96, 160)
SEED = 2147483693


def _cell(name):
    return harness.load_cell(name)


def _reference(model, w, pair, iters, lower=None):
    table = dict(w, __lower__=control.LOWER[lower]) if lower else w
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.disparity(model, table, *pair, iters))


def _program(model, w, pair, iters, **overrides):
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.eval.runner import InferenceRunner

    cfg = RaftStereoConfig.from_dict({**model, **overrides})
    return InferenceRunner(cfg, weights.nest(w), iters=iters)(*pair)[0]


def test_accuracy_bfloat16_path_fails_where_float32_passes():
    cell = _cell("accuracy.serve.kitti-steady")
    model, wl = cell["config"]["model"], cell["workload"]
    limits = wl["compare"]["limits"]
    w = weights.make_weights(model, SEED)
    pair = scenes.make_pairs(SEED, 1, HW)[0]
    want = _reference(model, w, pair, wl["iters"])
    sound = compare.answer_numbers(_program(model, w, pair, wl["iters"]),
                                   want)
    rig = control.program_control_rig(cell["config"])
    lowered = compare.answer_numbers(
        _program(model, w, pair, wl["iters"], **rig.program_overrides),
        want)
    assert all(c["ok"] for c in compare.decide([sound], limits))
    verdict = compare.decide([lowered], limits)
    assert not any(c["ok"] for c in verdict), verdict
    for name in limits:
        assert lowered[name] > 3 * sound[name]


def test_realtime_int8_reference_fails_where_the_program_passes():
    cell = _cell("realtime.bulk.kitti")
    model, wl = cell["config"]["model"], cell["workload"]
    limits = wl["compare"]["limits"]
    w = weights.make_weights(model, SEED)
    pair = scenes.make_pairs(SEED, 1, HW)[0]
    tail = wl["compare"]["unit"]
    want = _reference(model, w, pair, wl["iters"])
    unit = _reference(model, w, pair, wl["iters"], lower=tail["precision"])
    sound = compare.answer_numbers(_program(model, w, pair, wl["iters"]),
                                   want, unit, tail)
    lowered = compare.answer_numbers(
        _reference(model, w, pair, wl["iters"], lower="int8"), want, unit,
        tail)
    print("program", sound, "int8 reference", lowered)
    assert all(c["ok"] for c in compare.decide([sound], limits))
    assert not any(c["ok"] for c in compare.decide([lowered], limits))
    for name in limits:
        assert lowered[name] > 3 * sound[name]
    with pytest.raises(ValueError):
        control.program_control_rig(cell["config"])
