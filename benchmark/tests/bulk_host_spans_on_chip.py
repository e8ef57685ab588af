"""Where the device's idle time of the bulk cell goes, by the runner's own
``infer.*`` spans, on the chip at the cell's own size:

    python3 benchmark/tests/bulk_host_spans_on_chip.py [--calls 2] [--seed N]

The cell's traced run (``entries/bulk_runner._traced``) keeps the host tracer
off, so its trace holds no program span and the cell has no metric of this
kind yet (PERF.md section 7).  This makes the same calls — the cell's
configuration, weights and pool from the seed, the warm-up, then whole calls
of ``pairs_per_call`` pairs drawn from the order's far end — under a capture
at host tracer level 1 (the program's spans; the runtime's own events of the
upload's chunks come with them and slow it, PERF.md section 6) with the
Python tracer off, and splits the device's idle time with
``host_spans.attribute``.  Before them it makes ``--plain_calls`` of the
same kind with no capture open and reads each phase's seconds from the
runner's own ``infer_phase_seconds`` histograms: what a capture costs shows
as the difference.  Prints one JSON object: the clocked window, the stretch
the spans cover with its busy and idle seconds, idle seconds by span (what
lies between two calls is ``unattributed``), each span's own seconds a call
with and without the capture, the skew of ``infer.execute`` against the device plane,
and the trace's bytes.

``--tiny`` runs the same on this machine's default backend at the tests'
size (a rehearsal: its numbers are no device's).
"""

import glob
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

CELL = "realtime.bulk.kitti"


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=2)
    ap.add_argument("--plain_calls", type=int, default=3)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import harness, host_spans, scenes, traffic, weights
    from benchmark.entries.bulk_runner import DRAWS

    rig = harness.NO_RIG
    if args.tiny:
        with open(os.path.join(HERE, "tiny_overrides.json")) as f:
            rig = harness.TestRig(**json.load(f)[CELL])
    cell = rig.resized(harness.load_cell(CELL))
    wl, model = cell["workload"], cell["config"]["model"]
    tr = wl["traffic"]
    harness.use_cache_in_process()
    os.environ.update(cell["config"].get("env", {}))
    device = harness.require_chips(cell["chips"], rig.require_accelerator)
    import jax
    from jax.profiler import ProfileData

    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.eval.runner import RUNNER_PHASES, InferenceRunner
    from raft_stereo_tpu.telemetry.registry import MetricsRegistry
    from raft_stereo_tpu.telemetry.spans import Phases

    b = tr["pairs_per_call"]
    runner = InferenceRunner(RaftStereoConfig.from_dict(model),
                             weights.nest(weights.make_weights(model,
                                                               args.seed)),
                             iters=wl["iters"])
    pool = scenes.make_pairs(args.seed, tr["pool_pairs"],
                             tuple(tr["image_hw"]))
    order = traffic.pair_order(DRAWS * b, tr["pool_pairs"], args.seed)

    def call(k: int) -> None:
        idx = order[k * b:(k + 1) * b]
        runner.run_batch([pool[i][0] for i in idx], [pool[i][1] for i in idx])

    for _ in range(wl["warmup_calls"]):
        call(0)
    # the cell's runner has no registry; give this one's phases histograms
    runner.phases = Phases("infer.", RUNNER_PHASES, MetricsRegistry(),
                           "infer_phase_seconds")
    t0 = time.monotonic()
    for k in range(args.plain_calls):
        call(DRAWS - 1 - args.calls - k)
    plain_s = (time.monotonic() - t0) / max(args.plain_calls, 1)
    plain = {"infer." + name: h.sum / max(args.plain_calls, 1)
             for name, h in runner.phases.histograms.items()}
    trace_dir = os.path.join(harness.work_dir(CELL + ".host_spans"), "trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        t0 = time.monotonic()
        for k in range(args.calls):
            call(DRAWS - 1 - k)
        clocked_s = time.monotonic() - t0
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    att = host_spans.attribute(pd, host_stand_in=not rig.require_accelerator)
    if att is None:
        sys.stderr.write("the trace holds no program span or no device "
                         "operation\n")
        return 1
    own = {}
    for a, e, name in host_spans.worker_spans(pd):
        own[name] = own.get(name, 0.0) + (e - a) * 1e-9 / args.calls
    print(json.dumps({
        "cell": CELL, "device": device, "calls": args.calls,
        "pairs_per_call": b, "clocked_window_s": clocked_s,
        "stretch_s": att["stretch_s"],
        "busy_s": att["stretch_s"] - att["idle_s"], "idle_s": att["idle_s"],
        "idle_by_span_s": att["idle_by_span"],
        "span_seconds_per_call": own,
        "plain_span_seconds_per_call": plain, "plain_call_s": plain_s,
        "executes": att["executes"],
        "execute_skew_ms": att["skew_ms"],
        "trace_bytes": os.path.getsize(path)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
