"""The cell ``sceneflow.train.b4`` rehearsed end to end on the CPU at the
tiny size of ``tests/test_train_config.py`` (whose helpers and limits this
file shares): a sound run on one virtual device, traced, and the same job
over four; two planted faults; the parent's refusal.  A file of its own so
that tier-1's workers share the two files' compiles between them.
"""

import json

import jax
import pytest

from benchmark import harness, run
from benchmark.entries import train_job
from raft_stereo_tpu.training import train_loop
from test_train_config import (B4, SEED, _compiled_once,  # noqa: F401
                               _tiny, in_tmp_work, one_weight_build)


# ----------------------------------------------------- the rehearsed runs
def _result(capsys, trace=False, data_parallel=1):
    assert run.run_cell(B4, seed=SEED, seconds=0.0, trace=trace,
                        rig=_tiny(data_parallel)) == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert "compared grad_gap_units" in captured.err
    return line


@pytest.mark.parametrize("data_parallel,trace", [(1, True), (4, False)])
def test_a_rehearsed_run_is_correct(capsys, one_weight_build, in_tmp_work,
                                    data_parallel, trace):
    """The whole cell on one virtual CPU device, traced, and the same job
    over four, untraced: tree, seeded checkpoint, call A, exact resume,
    (traced steps,) a window of one step (``--seconds 0``: it closes at the
    first boundary after it opens), the reference's replay, the readers."""
    line = _result(capsys, trace=trace, data_parallel=data_parallel)
    assert line["correct"] is True and line["failed"] == 0
    assert line["steps"] >= 1 and line["attempted"] == line["steps"]
    assert line["compared"]["steps_compared"]["value"] == 2
    if trace:
        assert {"step_mfu_pct.train", "device_idle_pct.train",
                "data_wait_ms.train", "backward_share_pct.train",
                "recompute_share_pct.train",
                "optimizer_share_pct.train"} <= set(line["metrics"])
        assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    else:
        assert set(line["metrics"]) == {"pairs_per_s", "setup_s"}
        assert line["device"]["count"] >= 4


def _cut_lookup_gradient(monkeypatch):
    """The stored volume's lookup with no gradient: the correlation
    features stop learning from it, the rest of the step is sound."""
    from raft_stereo_tpu.models import raft_stereo

    sound = raft_stereo.make_corr_fn

    def broken(*args, **kwargs):
        corr_fn = sound(*args, **kwargs)
        return lambda coords: jax.lax.stop_gradient(corr_fn(coords))

    monkeypatch.setattr(raft_stereo, "make_corr_fn", broken)


def _halve_one_batchs_truth(monkeypatch):
    """The loop trains the first compared batch on half its ground truth
    (a disparity not rescaled with its crop's zoom) where the reference
    replays the whole.  From untrained weights an L1 loss's gradient is the
    SIGN of the error, which no such fault moves (nor does a batch's truth
    rolled by a sample: the summed |truth| stays): the LOSS shows it, which
    is why the cell limits ``loss_gap_rel`` too."""
    sound = train_job._Recorded.__iter__

    def broken(self):
        for i, batch in enumerate(sound(self)):
            if i == 0:
                batch = dict(batch, flow=0.5 * batch["flow"])
            yield batch

    monkeypatch.setattr(train_job._Recorded, "__iter__", broken)


@pytest.mark.parametrize("fault", [_cut_lookup_gradient,
                                   _halve_one_batchs_truth])
def test_a_planted_fault_is_not_correct(capsys, monkeypatch,
                                        one_weight_build, in_tmp_work,
                                        fault):
    fault(monkeypatch)
    line = _result(capsys)
    assert line["correct"] is False
    failed = {name for name, c in line["compared"].items()
              if "_gap_" in name and c["value"] > c["limit"]}
    assert failed and ("loss_gap_rel" in failed) == (
        fault is _halve_one_batchs_truth)


def test_the_parents_train_is_refused_at_once(monkeypatch, capsys):
    """A program whose ``train()`` has no ``should_stop`` (the parent of
    the PR that brought the cells) fails cleanly before any work."""
    def old_train(model_cfg, train_cfg, name="raft-stereo"):
        raise AssertionError("must not be called")

    monkeypatch.setattr(train_loop, "train", old_train)
    with pytest.raises(harness.BenchError, match="should_stop"):
        run.run_cell(B4, seed=SEED, seconds=0.0, trace=False,
                     rig=_tiny())
