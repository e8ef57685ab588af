"""Host staging of ``InferenceRunner.run_batch`` / ``__call__``
(eval/runner.py ``_run_padded``): the images are written once into a reused
padded pair, the result is cropped on the device and fetched at its own
size.  The device must receive bitwise what ``np.pad(np.stack(..),
mode="edge")`` made and the caller bitwise what the older path returned;
that path is kept here as the oracle.  CPU, tiny size.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu.eval.runner import (RUNNER_PHASES, InferenceRunner,
                                         _fill_edge_padded)
from raft_stereo_tpu.ops.padding import InputPadder
from test_serving import ITERS, tiny_model  # noqa: F401

# raw (H, W) -> pads (left, right, top, bottom) on the /32 grid
KITTI_LIKE = (55, 58)       # (3, 3, 4, 5): 375x1242's pads, scaled down
ASYMMETRIC = (59, 61)       # (1, 2, 2, 3)
ROWS_ONLY = (33, 64)        # (0, 0, 15, 16)
ON_GRID = (64, 64)          # (0, 0, 0, 0)


def _images(n, hw, dtype, seed):
    rng = np.random.default_rng(seed)
    lefts = [rng.integers(0, 255, hw + (3,), dtype=np.uint8).astype(dtype)
             for _ in range(n)]
    return lefts, [np.roll(im, -3, axis=1) for im in lefts]


def _np_pad(images, divis_by=32):
    """What the runner staged before: ``np.pad(np.stack(..))``."""
    stacked = np.stack(images)
    l, r, t, b = InputPadder(stacked.shape, divis_by=divis_by).pads
    return np.pad(stacked, ((0, 0), (t, b), (l, r), (0, 0)), mode="edge")


def _older_path(runner, images1, images2):
    """The call as the runner made it before the staging pair: pad with
    NumPy, run the same program, fetch the padded result, slice it on the
    host and copy the view."""
    p1, p2 = _np_pad(images1, runner.divis_by), _np_pad(images2,
                                                        runner.divis_by)
    padder = InputPadder(np.stack(images1).shape, divis_by=runner.divis_by)
    out = runner._forward_for(p1.shape[1:3], batch=len(images1))(
        runner.variables, jnp.asarray(p1), jnp.asarray(p2))
    if runner.early_exit:
        out, _ = out
    flows = padder.unpad(np.asarray(out))
    return np.ascontiguousarray(flows.astype(np.float32))


def _owned(flows):
    """C-contiguous float32 over memory that is all its own: the ndarray
    at the bottom of its ``base`` chain (the fetch's flat array; on the CPU
    backend a view of the device's buffer, which it keeps alive) holds the
    result's bytes and no more: never a window into a padded array."""
    whole = flows
    while isinstance(whole.base, np.ndarray):
        whole = whole.base
    return (flows.dtype == np.float32 and flows.flags.c_contiguous
            and whole.nbytes == flows.nbytes)


def _recorded_phases(runner):
    """The list that every phase of ``runner`` from now on is appended to,
    as ``(name, t_start, t_end, attributes)``."""
    recorded = []
    real_record = runner.phases.record
    runner.phases.record = lambda name, t0, t1, *a, **attrs: (
        recorded.append((name, t0, t1, attrs)),
        real_record(name, t0, t1, *a, **attrs))
    return recorded


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("n", [1, 3])
@pytest.mark.parametrize("hw", [KITTI_LIKE, ASYMMETRIC, ROWS_ONLY, ON_GRID])
def test_fill_is_np_pad_edge_bitwise(hw, n, dtype):
    lefts, _ = _images(n, hw, dtype, seed=5)
    want = _np_pad(lefts)
    buf = np.full(want.shape, 171, dtype)        # stale bytes everywhere
    _fill_edge_padded(buf, lefts, InputPadder((n,) + hw + (3,),
                                              divis_by=32).pads)
    assert buf.dtype == want.dtype and np.array_equal(buf, want)


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("n", [1, 3])
def test_staged_pair_and_answer_are_the_older_paths(tiny_model, n, dtype):
    """Through the runner: the staged pair is ``np.pad``'s, and ``run_batch``
    (``__call__`` at n = 1) returns bitwise what the older path returned,
    for a shape that pads and for one on the grid."""
    cfg, variables = tiny_model
    runner = InferenceRunner(cfg, variables, iters=ITERS)
    for hw in (KITTI_LIKE, ON_GRID):
        lefts, rights = _images(n, hw, dtype, seed=7)
        if n == 1:
            flow, seconds = runner(lefts[0], rights[0])
            assert _owned(flow)
            flows = flow[None]
        else:
            flows, seconds = runner.run_batch(lefts, rights)
            assert _owned(flows)
        assert seconds > 0 and flows.shape == (n,) + hw
        staged = runner._staging[1]
        assert staged[0].dtype == dtype
        assert np.array_equal(staged[0], _np_pad(lefts))
        assert np.array_equal(staged[1], _np_pad(rights))
        assert np.array_equal(flows, _older_path(runner, lefts, rights))
    assert len(runner._compiled) == 1            # 55x58 and 64x64: one grid


@pytest.mark.parametrize("kwargs", [{"fetch_dtype": "fp16"},
                                    {"exit_threshold_px": 1e-6}],
                         ids=["fp16_fetch", "early_exit"])
def test_half_fetch_and_early_exit_keep_their_answers(tiny_model, kwargs):
    cfg, variables = tiny_model
    runner = InferenceRunner(cfg, variables, iters=2, **kwargs)
    lefts, rights = _images(2, KITTI_LIKE, np.uint8, seed=9)
    flows, _ = runner.run_batch(lefts, rights)
    assert _owned(flows) and flows.shape == (2,) + KITTI_LIKE
    assert np.array_equal(flows, _older_path(runner, lefts, rights))
    if runner.early_exit:
        assert runner.last_iters_used == 2


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("donate", [True, False])
def test_successive_calls_do_not_alias(tiny_model, donate, dtype):
    """Different images in the same staging pair: each call gets its own
    right answer and the first call's array is as it was returned, whether
    or not the backend's upload aliases the host buffer or donates it."""
    cfg, variables = tiny_model
    runner = InferenceRunner(cfg, variables, iters=ITERS,
                             donate_images=donate)
    first_in = _images(3, KITTI_LIKE, dtype, seed=11)
    second_in = _images(3, KITTI_LIKE, dtype, seed=13)
    first, _ = runner.run_batch(*first_in)
    kept = first.copy()
    staged = runner._staging[1]
    second, _ = runner.run_batch(*second_in)
    assert runner._staging[1][0] is staged[0]             # the pair again
    assert not np.shares_memory(first, second)
    assert not any(np.shares_memory(first, buf) for buf in staged)
    assert np.array_equal(first, kept)
    assert not np.array_equal(first, second)
    assert np.array_equal(second, _older_path(runner, *second_in))
    assert np.array_equal(first, _older_path(runner, *first_in))
    assert _owned(first) and _owned(second)


def test_three_raw_shapes_share_one_program_and_one_pair(tiny_model):
    """KITTI's mixed raw sizes pad to one grid: one forward executable,
    and the staging pair of the first serves the rest."""
    cfg, variables = tiny_model
    runner = InferenceRunner(cfg, variables, iters=ITERS)
    for i, hw in enumerate((KITTI_LIKE, ASYMMETRIC, ON_GRID)):
        (left,), (right,) = _images(1, hw, np.uint8, seed=17 + i)
        staged = runner._staging[1]
        flow, _ = runner(left, right)
        assert flow.shape == hw
        assert np.array_equal(flow[None],
                              _older_path(runner, [left], [right]))
        assert i == 0 or runner._staging[1] is staged
    assert len(runner._compiled) == 1
    assert list(runner._compiled) == [((64, 64), 1)]


def test_only_the_latest_pair_is_kept(tiny_model):
    """Another batch size or dtype replaces the pair; going back to the
    first allocates again."""
    cfg, variables = tiny_model
    runner = InferenceRunner(cfg, variables, iters=ITERS)
    recorded = _recorded_phases(runner)
    one = _images(1, KITTI_LIKE, np.uint8, seed=19)
    two = _images(2, KITTI_LIKE, np.uint8, seed=21)
    for lefts, rights in (one, one, two, one):
        runner.run_batch(lefts, rights)
        assert runner._staging[0][0] == (len(lefts), 64, 64, 3)
    assert [attrs["reused"] for name, _, _, attrs in recorded
            if name == "stack_pad"] == [False, True, False, False]


def test_phases_in_order_and_stack_pad_says_reused(tiny_model):
    cfg, variables = tiny_model
    runner = InferenceRunner(cfg, variables, iters=ITERS)
    recorded = _recorded_phases(runner)
    lefts, rights = _images(3, KITTI_LIKE, np.uint8, seed=23)
    _, seconds_a = runner.run_batch(lefts, rights)
    _, seconds_b = runner.run_batch(rights, lefts)
    assert [r[0] for r in recorded] == list(RUNNER_PHASES) * 2
    for call, seconds in ((recorded[:5], seconds_a),
                          (recorded[5:], seconds_b)):
        by_name = {name: (t0, t1, attrs) for name, t0, t1, attrs in call}
        assert all(attrs["batch_size"] == 3 for *_, attrs in call)
        assert by_name["stack_pad"][2]["bytes"] == 2 * 3 * 64 * 64 * 3
        assert by_name["upload"][2]["bytes"] == 2 * 3 * 64 * 64 * 3
        # the crop went before the fetch: the answer's own bytes came back
        assert by_name["fetch"][2]["bytes"] == 3 * 55 * 58 * 4
        assert seconds == by_name["fetch"][1] - by_name["stack_pad"][0]
        starts = [t0 for _, t0, _, _ in call]
        assert starts == sorted(starts)
    assert [r[3]["reused"] for r in recorded
            if r[0] == "stack_pad"] == [False, True]
