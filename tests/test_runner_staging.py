"""Host staging of ``InferenceRunner.run_batch`` / ``__call__``
(eval/runner.py ``_run_padded``): the images are written once into a reused
padded pair, the result is cropped on the device and fetched at its own
size.  The device must receive bitwise what ``np.pad(np.stack(..),
mode="edge")`` made and the caller bitwise what the older path returned;
that path is kept here as the oracle.  A large call runs as pipelined
sub-batches of that pair (``_sub_batches``): its answer is bitwise the
calls of its sub-batches.  The tests split tiny calls by lowering the
module's byte threshold, which is a test's lever and no user's.  CPU, tiny
size.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu.eval import runner as runner_module
from raft_stereo_tpu.eval.runner import (RUNNER_PHASES, InferenceRunner,
                                         _fill_edge_padded, _sub_batches)
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


def _staged_bytes(n, dtype, padded_hw=(64, 64)):
    return 2 * n * padded_hw[0] * padded_hw[1] * 3 * np.dtype(dtype).itemsize


def _split_calls_of(monkeypatch, staged_bytes, chunks):
    """From here on a call that stages ``staged_bytes`` runs as ``chunks``
    sub-batches where its ``n`` divides so, and a smaller call as one."""
    monkeypatch.setattr(runner_module, "_SUB_BATCH_MIN_BYTES",
                        staged_bytes // chunks if chunks > 1 else 1 << 60)


def _older_path(runner, images1, images2, chunks=1):
    """The call as the runner made it before the staging pair: pad with
    NumPy, run the same program, fetch the padded result, slice it on the
    host and copy the view.  With ``chunks``, each sub-batch so."""
    if chunks > 1:
        m = len(images1) // chunks
        return np.concatenate([
            _older_path(runner, images1[i:i + m], images2[i:i + m])
            for i in range(0, len(images1), m)])
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


@pytest.mark.parametrize("chunks", [1, 2])
@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("donate", [True, False])
def test_successive_calls_do_not_alias(tiny_model, monkeypatch, donate,
                                       dtype, chunks):
    """Different images in the same staging pair: each call gets its own
    right answer and the first call's array is as it was returned, whether
    or not the backend's upload aliases the host buffer or donates it, and
    whether the call is one program or two pipelined sub-batches."""
    cfg, variables = tiny_model
    runner = InferenceRunner(cfg, variables, iters=ITERS,
                             donate_images=donate)
    recorded = _recorded_phases(runner)
    n = 3 if chunks == 1 else 4
    _split_calls_of(monkeypatch, _staged_bytes(n, dtype), chunks)
    first_in = _images(n, KITTI_LIKE, dtype, seed=11)
    second_in = _images(n, KITTI_LIKE, dtype, seed=13)
    first, _ = runner.run_batch(*first_in)
    kept = first.copy()
    staged = runner._staging[1]
    second, _ = runner.run_batch(*second_in)
    assert runner._staging[1][0] is staged[0]             # the pair again
    # only the first call's first fill met no pair to reuse
    assert [(a["chunk"], a["chunks"], a["reused"])
            for name, _, _, a in recorded if name == "stack_pad"] == [
        (k, chunks, call > 0 or k > 0)
        for call in range(2) for k in range(chunks)]
    assert not np.shares_memory(first, second)
    assert not any(np.shares_memory(first, buf) for buf in staged)
    assert np.array_equal(first, kept)
    assert not np.array_equal(first, second)
    assert np.array_equal(second, _older_path(runner, *second_in, chunks))
    assert np.array_equal(first, _older_path(runner, *first_in, chunks))
    assert _owned(first) and _owned(second)
    assert first.shape == second.shape == (n,) + KITTI_LIKE


@pytest.mark.parametrize("kwargs, dtype, n, chunks", [
    ({}, np.uint8, 4, 2), ({}, np.float32, 4, 2), ({}, np.uint8, 4, 4),
    ({}, np.uint8, 6, 3), ({"donate_images": False}, np.uint8, 4, 2),
    ({"donate_images": False}, np.float32, 6, 2),
    ({"fetch_dtype": "fp16"}, np.uint8, 4, 2),
    ({"fetch_dtype": "fp16", "donate_images": False}, np.float32, 4, 2)],
    ids=["uint8", "float32", "four_of_one", "three_of_two", "undonated",
         "undonated_float32_two_of_three", "fp16_fetch",
         "fp16_fetch_undonated_float32"])
def test_split_call_is_its_sub_batches_bitwise(tiny_model, monkeypatch,
                                               kwargs, dtype, n, chunks):
    """A split call's ``(n, H, W)`` answer is, bit for bit, the calls of
    ``n / chunks`` pairs it is made of, and the unsplit call's to 1e-5 of
    the largest disparity (equal on the CPU, but a batch's size may change
    XLA's tiling; a float16 fetch may turn that into one float16 step); its
    staged pair is ``np.pad``'s of the whole call."""
    cfg, variables = tiny_model
    runner = InferenceRunner(cfg, variables, iters=ITERS, **kwargs)
    recorded = _recorded_phases(runner)
    lefts, rights = _images(n, KITTI_LIKE, dtype, seed=29)
    _split_calls_of(monkeypatch, _staged_bytes(n, dtype), chunks)
    flows, seconds = runner.run_batch(lefts, rights)
    assert {a["chunks"] for *_, a in recorded} == {chunks}
    assert seconds > 0 and flows.shape == (n,) + KITTI_LIKE
    assert _owned(flows)
    staged = runner._staging[1]
    assert staged[0].dtype == dtype and len(staged[0]) == n
    assert np.array_equal(staged[0], _np_pad(lefts))
    assert np.array_equal(staged[1], _np_pad(rights))
    assert list(runner._compiled) == [((64, 64), n // chunks)]
    kept = flows.copy()
    m = n // chunks
    del recorded[:]
    for i in range(0, n, m):        # each under the threshold: one program
        alone, _ = runner.run_batch(lefts[i:i + m], rights[i:i + m])
        assert np.array_equal(flows[i:i + m], alone)
    assert {a["chunks"] for *_, a in recorded} == {1}
    assert np.array_equal(flows, _older_path(runner, lefts, rights, chunks))
    _split_calls_of(monkeypatch, 0, 1)
    whole, _ = runner.run_batch(lefts, rights)
    # a half-precision fetch rounds that difference to one of its own steps
    step = 2.0 ** -10 if "fetch_dtype" in kwargs else 1e-5
    assert np.abs(whole - flows).max() <= step * np.abs(whole).max()
    assert np.array_equal(flows, kept)


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


def test_split_call_phases_pipeline_and_say_their_chunk(tiny_model,
                                                        monkeypatch):
    """Sub-batch k is filled, uploaded and launched before sub-batch k-1 is
    fetched; every span says which sub-batch of how many; the seconds run
    from the first fill's start to the last fetch's end."""
    cfg, variables = tiny_model
    runner = InferenceRunner(cfg, variables, iters=ITERS)
    recorded = _recorded_phases(runner)
    lefts, rights = _images(6, KITTI_LIKE, np.uint8, seed=31)
    _split_calls_of(monkeypatch, _staged_bytes(6, np.uint8), 3)
    _, seconds_a = runner.run_batch(lefts, rights)
    _, seconds_b = runner.run_batch(rights, lefts)
    head = ["stack_pad", "upload", "execute"]
    want = ([(p, 0) for p in head]
            + [(p, 1) for p in head] + [("fetch", 0)]
            + [(p, 2) for p in head] + [("fetch", 1)]
            + [("fetch", 2), ("unpad", 2)])
    assert [(r[0], r[3]["chunk"]) for r in recorded] == want * 2
    for call, seconds in ((recorded[:len(want)], seconds_a),
                          (recorded[len(want):], seconds_b)):
        assert all(a["batch_size"] == 6 and a["chunks"] == 3
                   for *_, a in call)
        for name, _, _, attrs in call:
            if name in ("stack_pad", "upload"):
                assert attrs["bytes"] == 2 * 2 * 64 * 64 * 3
            if name == "fetch":
                assert attrs["bytes"] == 2 * 55 * 58 * 4
        assert seconds == call[-2][2] - call[0][1]
        starts = [t0 for _, t0, _, _ in call]
        assert starts == sorted(starts)
    executes = [a for name, _, _, a in recorded if name == "execute"]
    assert executes[0]["compiled"] == 1 and "paths" in executes[0]
    assert not any("compiled" in a for a in executes[1:])
    assert [a["reused"] for name, _, _, a in recorded
            if name == "stack_pad"] == [False] + [True] * 5


@pytest.mark.parametrize("case", ["no_divisor", "one_pair", "early_exit",
                                  "under_threshold"])
def test_unsplit_call_records_todays_five_spans(tiny_model, monkeypatch,
                                                case):
    """A call whose ``n`` admits no split, a call of one, an early-exit
    runner's call and a call under the byte threshold are one program:
    the five spans, ``chunks`` 1, and the older path's bytes."""
    cfg, variables = tiny_model
    n = {"no_divisor": 3, "one_pair": 1}.get(case, 4)
    kwargs = {"exit_threshold_px": 1e-6} if case == "early_exit" else {}
    runner = InferenceRunner(cfg, variables, iters=ITERS, **kwargs)
    recorded = _recorded_phases(runner)
    # bytes enough for two sub-batches and not for three, or for one alone
    staged = _staged_bytes(n, np.uint8)
    _split_calls_of(monkeypatch,
                    2 * staged - 2 if case == "under_threshold" else staged,
                    2)
    lefts, rights = _images(n, KITTI_LIKE, np.uint8, seed=37)
    flows, seconds = runner.run_batch(lefts, rights)
    assert [r[0] for r in recorded] == list(RUNNER_PHASES)
    assert all(a["chunk"] == 0 and a["chunks"] == 1
               and a["batch_size"] == n for *_, a in recorded)
    by_name = {name: (t0, t1) for name, t0, t1, _ in recorded}
    assert seconds == by_name["fetch"][1] - by_name["stack_pad"][0]
    assert list(runner._compiled) == [((64, 64), n)]
    assert _owned(flows) and not flows.flags.writeable
    assert np.array_equal(flows, _older_path(runner, lefts, rights))


@pytest.mark.parametrize("n, staged_bytes, want", [
    (1, 10 << 30, 1), (2, 0, 1), (7, 10 << 30, 7), (12, 10 << 30, 12),
    (128, 2 * 128 * 384 * 1248 * 3, 8), (256, 2 * 256 * 384 * 1248 * 3, 16),
    (127, 2 * 127 * 384 * 1248 * 3, 1), (16, 2 * 16 * 384 * 1248 * 3, 1),
    (32, 2 * 32 * 384 * 1248 * 3, 2), (8, 2 * 8 * 384 * 1248 * 3 * 4, 2),
    (1, 2 * 1984 * 2880 * 3, 1)],
    ids=["one_pair", "no_bytes", "seven_of_one", "twelve_of_one",
         "realtime_cell_call", "twice_that_call", "prime_n", "sixteen_kitti",
         "thirty_two_kitti", "eight_kitti_float32", "fullres_cell_call"])
def test_sub_batches_divide_the_call(n, staged_bytes, want):
    """The rule takes the most sub-batches that divide ``n`` and leave each
    the byte threshold: 16 KITTI uint8 pairs a sub-batch whatever the
    call's size; a prime ``n``, a call of one, a call of no bytes and the
    serving-sized batches stay one program."""
    assert _sub_batches(n, staged_bytes) == want
    assert n % want == 0
    assert want == 1 or (staged_bytes // want
                         >= runner_module._SUB_BATCH_MIN_BYTES)


@pytest.mark.parametrize("chunks", [1, 2, 3, 4, 8])
def test_sub_batches_keep_each_over_the_byte_threshold(chunks):
    """840 pairs divide by every count to 8: the bytes alone decide, and
    one byte short of ``chunks`` thresholds is one sub-batch fewer."""
    least = runner_module._SUB_BATCH_MIN_BYTES
    assert _sub_batches(840, chunks * least) == chunks
    assert _sub_batches(840, chunks * least + least - 1) == chunks
    assert _sub_batches(840, chunks * least - 1) == max(chunks - 1, 1)
