"""Entry ``bulk_runner``: one caller pushes same-shape pairs through
``eval.runner.InferenceRunner.run_batch`` — the program the evaluation CLI
and the serving engine dispatch — ``pairs_per_call`` at a time, closed
loop, in this process.
"""

from __future__ import annotations

import gc
import json
import os
import time

import numpy as np

from benchmark import harness, scenes, traffic

DRAWS = 4096        # calls' worth of pairs drawn from the seed


def run(cell: dict, seed: int, seconds: float, trace: bool,
        rig: harness.TestRig = harness.NO_RIG) -> dict:
    wl, model = cell["workload"], cell["config"]["model"]
    tr = wl["traffic"]
    harness.use_cache_in_process()
    # the configuration's own environment (its stated precision), before
    # jax is first imported
    os.environ.update(cell["config"].get("env", {}))
    os.environ.update(rig.env)
    device = harness.require_chips(cell["chips"], rig.require_accelerator)
    import jax

    from benchmark import weights
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.eval.runner import InferenceRunner

    wd = harness.work_dir(cell["name"])
    hw = tuple(tr["image_hw"])
    b = tr["pairs_per_call"]
    cfg = RaftStereoConfig.from_dict({**model, **rig.program_overrides})
    tree = weights.nest(weights.make_weights(model, seed))
    runner = InferenceRunner(cfg, tree, iters=wl["iters"])
    pool = scenes.make_pairs(seed, tr["pool_pairs"], hw)
    # more calls than any window holds; the order of pairs is the seed's
    order = traffic.pair_order(DRAWS * b, tr["pool_pairs"], seed)
    keep = wl["compare"]["answers"]
    kept = {}          # (call, row) -> flow, the first calls' sampled rows
    rng = np.random.default_rng([seed, 0xC0DE])
    sample = {(int(c), int(r)) for c, r in zip(
        rng.integers(0, wl["compare"]["from_first_calls"], keep),
        rng.integers(0, b, keep))}
    sample.add((0, b - 1))

    def infer(k: int) -> tuple:
        """The ``k``-th draw of ``b`` pairs from the pool, through the
        runner: what a call of the window does."""
        idx = order[k * b:(k + 1) * b]
        flows, _ = runner.run_batch([pool[i][0] for i in idx],
                                    [pool[i][1] for i in idx])
        return idx, flows

    def call(k: int) -> None:
        idx, flows = infer(k)
        for (c, r) in sample:
            if c == k:
                kept[(c, r)] = (int(idx[r]), flows[r].copy())

    # ---- warm-up: this cell's one shape, twice (compile, then settle)
    for k in range(wl["warmup_calls"]):
        runner.run_batch([pool[i][0] for i in order[:b]],
                         [pool[i][1] for i in order[:b]])
    setup_s = time.monotonic() - harness.T_PROCESS_START

    # A traced run traces whole calls first, outside the window (the
    # profiler slows the host), then measures the window as any run does.
    # The traced calls are the window's own kind: draws from the pool as it
    # lies cold in memory, taken from the order's far end, which no window
    # reaches, and not the warm-up's list again.
    trace_dir, traced_s = None, None
    if trace:
        trace_dir = os.path.join(wd, "trace")
        traced_s = _traced(trace_dir, wl["trace"]["calls"],
                           lambda k: infer(DRAWS - 1 - k))
    calls, elapsed = traffic.closed_loop_calls(seconds, call)

    # ---- the window has closed: memory first, then free, then compare
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    answers = []
    os.makedirs(os.path.join(wd, "answers"))
    for (c, r), (pair, flow) in sorted(kept.items()):
        path = os.path.join(wd, "answers", f"{c}_{r}.npy")
        np.save(path, flow)
        answers.append([c * b + r, pair, path])
    del runner, tree, kept
    gc.collect()
    jax.clear_caches()
    post_path = os.path.join(wd, "post.json")
    result_path = os.path.join(wd, "post_result.json")
    from benchmark import post

    post.write_request(post_path, cell, seed, answers, "flow", trace_dir,
                       result_path, trace_window_s=traced_s,
                       require_accelerator=rig.require_accelerator)
    post.main([post_path])
    with open(result_path) as f:
        post_result = json.load(f)
    pairs = calls * b
    return {
        "e2e": {"pairs_per_s": pairs / elapsed, "setup_s": setup_s},
        "observed": {"cell": cell, "seconds": elapsed,
                     "pairs_completed": pairs, "counters": {},
                     "trace": post_result.get("trace"),
                     "device_kind": rig.device_kind or device["kind"]},
        "compared": post_result["compared"], "attempted": calls,
        "failed": 0,
        "device": device,
        "memory": {"memory_stats": stats},
        "trace": post_result.get("trace"),
        "extra": {"calls": calls, "window_s": elapsed},
    }


def _traced(trace_dir: str, n: int, call) -> float:
    """``n`` whole calls under the profiler; the window is clocked between
    the profiler's start and its stop, not over them.  Device events are
    what is read: the host's thread-pool events of 128-pair calls make a
    trace of hundreds of MB (my chip run, PR 24), so they stay off."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        t0 = time.monotonic()
        for k in range(n):
            call(k)
        seconds = time.monotonic() - t0
    finally:
        jax.profiler.stop_trace()
    return seconds
