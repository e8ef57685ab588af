#!/usr/bin/env python
"""CI smoke: boot the training metrics endpoint for a 5-step CPU run and
assert ``/metrics``, ``/healthz``, ``/debug/spans``, and ``/debug/stacks``
answer with live data.

This is the acceptance check for the telemetry subsystem wired end to end —
TrainTelemetry instruments + span tracer + flight recorder → train loop →
TelemetryHTTPServer — on the same synthetic-loader path the hermetic tests
use (no datasets, no accelerator).  Exit 0 on success, non-zero with a
diagnostic on any failed assertion; on failure a flight-recorder debug
bundle is dumped under the output directory so CI can upload it as an
artifact (ci.yml).

Run from the repo root:  JAX_PLATFORMS=cpu python scripts/metrics_smoke.py
The output directory defaults to a temp dir; set SMOKE_OUT to pin it
(CI pins ``smoke-debug`` and uploads it when this script fails).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# repo root (the package, when not pip-installed) + tests (_hermetic)
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tests"))

NUM_STEPS = 5


class _SyntheticDataset:
    def __len__(self):
        return 4

    def __getitem__(self, i, epoch=0):
        import numpy as np
        img = np.full((32, 64, 3), float(i), np.float32)
        return {"image1": img, "image2": img,
                "flow": np.full((32, 64), -2.0, np.float32),
                "valid": np.ones((32, 64), np.float32)}


def main() -> int:
    from _hermetic import force_cpu
    force_cpu(1)

    from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
    from raft_stereo_tpu.data.loader import StereoLoader
    from raft_stereo_tpu.telemetry import (CompileRegistry, EventLog,
                                           FlightRecorder, MetricsRegistry,
                                           SpanTracer, TelemetryHTTPServer,
                                           TrainTelemetry, replay)
    from raft_stereo_tpu.training.train_loop import train

    tmp = os.environ.get("SMOKE_OUT") or tempfile.mkdtemp(
        prefix="metrics_smoke_")
    os.makedirs(tmp, exist_ok=True)
    events = EventLog(os.path.join(tmp, "events.jsonl"))
    tracer = SpanTracer(1.0)              # smoke samples every step
    recorder = FlightRecorder(os.path.join(tmp, "flightrecorder"),
                              tracer=tracer, min_interval_s=0.0)
    registry = MetricsRegistry()
    costs = CompileRegistry(registry=registry, events=events)
    telemetry = TrainTelemetry(registry=registry, events=events,
                               tracer=tracer, recorder=recorder,
                               costs=costs)
    recorder.registry = telemetry.registry
    server = TelemetryHTTPServer(telemetry.registry, telemetry.healthz,
                                 port=0, tracer=tracer,
                                 recorder=recorder, costs=costs).start()
    print(f"metrics endpoint: {server.url} (artifacts: {tmp})")

    # fnet_norm="none": the smallest encoder (the tests' tiny config too)
    model_cfg = RaftStereoConfig(n_gru_layers=1, hidden_dims=(32,),
                                 fnet_dim=64, fnet_norm="none")
    train_cfg = TrainConfig(batch_size=2, train_iters=2,
                            num_steps=NUM_STEPS, image_size=(32, 64),
                            validation_frequency=10_000, data_parallel=1,
                            gru_telemetry=True, trace_sample_rate=1.0)
    loader = StereoLoader(_SyntheticDataset(), batch_size=2, num_workers=0,
                          shuffle=False)
    try:
        state = train(model_cfg, train_cfg, name="smoke",
                      checkpoint_dir=os.path.join(tmp, "ckpt"),
                      log_dir=os.path.join(tmp, "runs"), loader=loader,
                      use_mesh=False, telemetry=telemetry)
        assert int(state.step) == NUM_STEPS, int(state.step)

        metrics = urllib.request.urlopen(server.url + "/metrics",
                                         timeout=10).read().decode()
        for needle in (f"train_steps_total {NUM_STEPS}",
                       "train_recompiles_total 0",
                       "train_anomalies_total 0",
                       f"train_step_seconds_count {NUM_STEPS}",
                       f"train_data_wait_seconds_count {NUM_STEPS}",
                       "train_gru_delta_px_count"):
            assert needle in metrics, f"missing {needle!r} in /metrics"

        health = json.load(urllib.request.urlopen(server.url + "/healthz",
                                                  timeout=10))
        assert health["status"] == "complete", health
        assert health["step"] == NUM_STEPS, health
        assert health["last_step_age_s"] is not None, health
        assert health["anomalies"] == 0, health

        # Span tracing end to end: every step's trace is in the ring and
        # the export is Chrome trace-event JSON Perfetto can open.
        chrome = json.load(urllib.request.urlopen(
            server.url + "/debug/spans", timeout=10))
        steps = [e for e in chrome["traceEvents"]
                 if e.get("ph") == "X" and e["name"] == "train.step"]
        assert len(steps) == NUM_STEPS, f"{len(steps)} step spans"
        names = {e["name"] for e in chrome["traceEvents"]
                 if e.get("ph") == "X"}
        assert {"train.data_wait", "train.dispatch",
                "train.metric_drain", "train.checkpoint"} <= names, names
        with open(os.path.join(tmp, "trace.json"), "w") as f:
            json.dump(chrome, f)

        stacks = urllib.request.urlopen(server.url + "/debug/stacks",
                                        timeout=10).read().decode()
        assert "MainThread" in stacks, stacks[:200]

        fr = json.load(urllib.request.urlopen(
            server.url + "/debug/flightrecorder", timeout=10))
        assert fr["dumps"] == 0, fr  # healthy run: nothing triggered
        assert fr["spans"]["ring_size"] >= NUM_STEPS, fr

        # Compile-cost registry end to end: the AOT-instrumented train
        # step is in the inventory with cost + memory analysis, and the
        # drain turned its flops into a live gauge.
        compiles = json.load(urllib.request.urlopen(
            server.url + "/debug/compiles", timeout=10))
        assert compiles["count"] >= 1, compiles
        execs = {e["key"]: e for e in compiles["executables"]}
        assert "train.step" in execs, sorted(execs)
        step_exec = execs["train.step"]
        assert step_exec["flops"] and step_exec["flops"] > 0, step_exec
        assert step_exec["memory"] and \
            step_exec["memory"]["argument_size_in_bytes"] > 0, step_exec
        flops_line = [l for l in metrics.splitlines()
                      if l.startswith("train_step_flops ")]
        assert flops_line and float(flops_line[0].split()[1]) > 0, \
            f"train_step_flops missing/zero: {flops_line}"

        kinds = [e["event"] for e in replay(events.path)]
        assert kinds[0] == "run_start" and kinds[-1] == "run_end", kinds
        assert "step_stats" in kinds and "checkpoint" in kinds, kinds
        assert "compile" in kinds, kinds  # the AOT step compile evented
    except BaseException:
        # Leave the evidence where ci.yml uploads it from.
        try:
            recorder.dump("smoke_failure", force=True)
        except Exception:
            pass
        raise
    finally:
        server.shutdown()
        events.close()
    print("metrics smoke OK:", json.dumps(health))
    return 0


if __name__ == "__main__":
    sys.exit(main())
