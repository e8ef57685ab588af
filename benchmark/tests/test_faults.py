"""The rest of a run, driven past the harness's look for a chip at a tiny
size, with the timed path broken underneath: ``correct`` has to come out
false for each fault a cell can have, and true for a sound run."""

import dataclasses
import json
import os

import pytest

from benchmark import harness, run
from benchmark.tests import faults

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "tiny_overrides.json")) as f:
    TINY = {name: harness.TestRig(**fields)
            for name, fields in json.load(f).items()}

BULK = "realtime.bulk.kitti"
SERVE = "accuracy.serve.kitti-steady"
NUMBER = {BULK: "share_over_unit_tail", SERVE: "p99_gap_px"}


def _result(capsys, workload, rig, trace=False):
    assert run.run_cell(workload, seed=2147483659, seconds=2.0, trace=trace,
                        rig=rig) == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"           # it comes last
    assert "compared " + NUMBER[workload] in captured.err
    return line


def test_bulk_sound_run_is_correct(capsys):
    line = _result(capsys, BULK, TINY[BULK])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"pairs_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [faults.shift_batch_rows,
                                   faults.roll_batch_rows])
def test_bulk_fault_is_not_correct(capsys, monkeypatch, fault):
    fault(monkeypatch)
    line = _result(capsys, BULK, TINY[BULK])
    assert line["correct"] is False
    c = line["compared"][NUMBER[BULK]]
    assert c["value"] > c["limit"]


def test_serve_sound_run_is_correct_and_traced(capsys):
    line = _result(capsys, SERVE, TINY[SERVE], trace=True)
    assert line["correct"] is True and line["failed"] == 0
    assert {"device_idle_pct.lat", "step_mfu_pct.lat",
            "queue_wait_ms.lat"} <= set(line["metrics"])
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    assert len(line["breakdown"]["device_ops"]) <= 10


def test_serve_altered_answer_is_not_correct(capsys):
    rig = dataclasses.replace(
        TINY[SERVE], child_patch="benchmark.tests.faults:alter_served_answer")
    line = _result(capsys, SERVE, rig)
    assert line["correct"] is False
    assert set(line["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                    "pairs_per_s", "setup_s"}


def test_no_chip_no_result(capsys):
    """The measurement path refuses to run without a chip."""
    assert run.main(["--workload", BULK, "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) != 0
    assert capsys.readouterr().out.strip() == ""


@pytest.mark.parametrize("key, value", [
    ("require_accelerator", False), ("rehearsal_device_kind", "TPU v5 lite"),
    ("device_kind", "TPU v5 lite"), ("program_overrides", {"iters": 1}),
    ("child_patch", "benchmark.tests.faults:alter_served_answer"),
    ("env", {"JAX_PLATFORMS": "cpu"})])
def test_a_cells_file_cannot_carry_the_rig(capsys, monkeypatch, key, value):
    """What a ``TestRig`` holds is read from the rig alone: the same key in
    a workload's file changes nothing, and the run still stops at the look
    for a chip."""
    sound = harness.load_cell

    def with_key(name):
        cell = sound(name)
        return dict(cell, workload=dict(cell["workload"], **{key: value}))

    monkeypatch.setattr(harness, "load_cell", with_key)
    for cell in (BULK, SERVE):
        assert run.main(["--workload", cell, "--seed", "1", "--seconds",
                         "1", "--trace", "1"]) != 0
        assert capsys.readouterr().out.strip() == ""
