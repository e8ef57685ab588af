"""Unified telemetry subsystem (raft_stereo_tpu/telemetry/): shared
registry, structured events, training instruments + endpoint, trace
capture, and the zero-overhead-when-disabled guarantee."""

import json
import os
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu import telemetry
from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
from raft_stereo_tpu.telemetry import (EventLog, TelemetryHTTPServer,
                                       TraceBusy, TraceCapture,
                                       TrainTelemetry, bench_record, replay,
                                       write_record)


# ------------------------------------------------------- registry promotion
def test_serving_metrics_reexports_shared_registry():
    """The serving imports keep working unchanged AND resolve to the one
    shared implementation in telemetry/registry.py."""
    from raft_stereo_tpu.serving import metrics as serving_metrics
    from raft_stereo_tpu.telemetry import registry as shared

    for name in ("Counter", "Gauge", "Histogram", "MetricsRegistry",
                 "DEFAULT_LATENCY_BUCKETS"):
        assert getattr(serving_metrics, name) is getattr(shared, name), name

    m = serving_metrics.ServingMetrics()
    text = m.render_text()
    assert "serve_requests_admitted_total" in text
    assert "serve_queue_wait_seconds_bucket" in text


# ------------------------------------------------------------------ events
def test_event_log_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with EventLog(path) as ev:
        ev.emit("run_start", name="x", step=0)
        ev.emit("step_stats", step=100, means={"loss": 1.5})
        ev.emit("run_end", status="complete", step=100)
    recs = list(replay(path))
    assert [r["event"] for r in recs] == ["run_start", "step_stats",
                                          "run_end"]
    assert [r["seq"] for r in recs] == [0, 1, 2]
    assert all(r["schema_version"] == telemetry.SCHEMA_VERSION for r in recs)
    assert recs[1]["means"]["loss"] == 1.5
    assert recs[0]["ts"] <= recs[2]["ts"]


def test_event_log_numpy_values_and_torn_tail(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with EventLog(path) as ev:
        ev.emit("step_stats", loss=np.float32(2.5),
                deltas=np.arange(3, dtype=np.float32))
    with open(path, "a") as f:
        f.write('{"event": "torn')  # SIGKILL mid-write
    recs = list(replay(path))
    assert len(recs) == 1
    assert recs[0]["loss"] == 2.5
    assert recs[0]["deltas"] == [0.0, 1.0, 2.0]


def test_bench_record_header_and_write(tmp_path):
    rec = bench_record({"metric": "m", "value": 1.25, "unit": "u"})
    assert rec["schema_version"] == telemetry.SCHEMA_VERSION
    assert rec["metric"] == "m" and rec["value"] == 1.25  # contract intact
    assert rec["run"]["platform"] == "cpu"
    assert rec["run"]["n_devices"] == len(jax.devices())
    json.dumps(rec)  # must be serializable as-is

    path = str(tmp_path / "BENCH.json")
    write_record(path, {"metric": "m2", "value": 2})
    with open(path) as f:
        back = json.load(f)
    assert back["schema_version"] == telemetry.SCHEMA_VERSION
    assert back["metric"] == "m2"
    # already-wrapped records are not double-wrapped
    write_record(path, rec)
    with open(path) as f:
        assert json.load(f)["run"] == rec["run"]


# ----------------------------------------------------------- trace capture
def test_trace_capture_bounded_window(tmp_path):
    cap = TraceCapture(root=str(tmp_path / "prof"))
    info = cap.start(duration_ms=telemetry.trace.MAX_TRACE_MS * 10)
    assert info["duration_ms"] == telemetry.trace.MAX_TRACE_MS  # clamped
    assert cap.active
    with pytest.raises(TraceBusy):
        cap.start()
    x = jnp.ones((32, 32))
    jax.block_until_ready(jax.jit(lambda a: a @ a)(x))
    assert cap.stop() is True
    assert cap.stop() is False  # idempotent
    found = [f for _, _, fs in os.walk(info["trace_dir"]) for f in fs]
    assert found, "trace capture produced no files"
    with pytest.raises(ValueError):
        cap.start(duration_ms=0)


# ------------------------------------------- the instrumented training run
class _SyntheticDataset:
    def __len__(self):
        return 4

    def __getitem__(self, i, epoch=0):
        img = np.full((32, 64, 3), float(i), np.float32)
        return {"image1": img, "image2": img,
                "flow": np.full((32, 64), -2.0, np.float32),
                "valid": np.ones((32, 64), np.float32)}


def _tiny_cfgs(num_steps=5, train_iters=2, gru_telemetry=True):
    # fnet_norm="none": the smallest encoder
    mcfg = RaftStereoConfig(n_gru_layers=1, hidden_dims=(32,), fnet_dim=64,
                            fnet_norm="none")
    tcfg = TrainConfig(batch_size=2, train_iters=train_iters,
                       num_steps=num_steps, image_size=(32, 64),
                       validation_frequency=10_000, data_parallel=1,
                       gru_telemetry=gru_telemetry)
    return mcfg, tcfg


def _run_train(tmp_path, telemetry_obj, num_steps=5, **cfg_kw):
    from raft_stereo_tpu.data.loader import StereoLoader
    from raft_stereo_tpu.training.train_loop import train

    mcfg, tcfg = _tiny_cfgs(num_steps=num_steps, **cfg_kw)
    loader = StereoLoader(_SyntheticDataset(), batch_size=2, num_workers=0,
                          shuffle=False)
    return train(mcfg, tcfg, name="tel", checkpoint_dir=str(tmp_path / "ck"),
                 log_dir=str(tmp_path / "runs"), loader=loader,
                 use_mesh=False, telemetry=telemetry_obj)


@pytest.fixture(scope="module")
def scraped_run(tmp_path_factory):
    """ONE instrumented 5-step CPU run with a live endpoint; the scrape
    results and event log are shared by the assertions below (the
    acceptance path: train --metrics_port is live-scrapable)."""
    tmp_path = tmp_path_factory.mktemp("telemetry_run")
    events = EventLog(str(tmp_path / "events.jsonl"))
    tm = TrainTelemetry(events=events)
    server = TelemetryHTTPServer(
        tm.registry, tm.healthz, port=0,
        trace=TraceCapture(root=str(tmp_path / "profiles"))).start()
    try:
        state = _run_train(tmp_path, tm, num_steps=5)
        metrics_text = urllib.request.urlopen(
            server.url + "/metrics", timeout=10).read().decode()
        health = json.load(urllib.request.urlopen(
            server.url + "/healthz", timeout=10))
        req = urllib.request.Request(
            server.url + "/debug/trace",
            data=json.dumps({"duration_ms": 150}).encode(), method="POST")
        trace_reply = json.load(urllib.request.urlopen(req, timeout=10))
        server.trace.stop()
    finally:
        server.shutdown()
        events.close()
    return dict(state=state, metrics=metrics_text, health=health,
                trace=trace_reply, events_path=events.path, telemetry=tm)


def test_train_run_is_live_scrapable(scraped_run):
    text = scraped_run["metrics"]
    assert int(scraped_run["state"].step) == 5
    assert "train_steps_total 5" in text
    assert "train_recompiles_total 0" in text
    # wall-time split histograms populated once per step
    assert "train_step_seconds_count 5" in text
    assert "train_data_wait_seconds_count 5" in text
    assert "train_metric_drain_seconds_count" in text
    assert "train_checkpoint_seconds_count 2" in text  # boundary + final
    # memory gauges refreshed at the drain
    assert "train_host_rss_bytes" in text


def test_healthz_reports_last_step_age(scraped_run):
    health = scraped_run["health"]
    assert health["status"] == "complete"
    assert health["step"] == 5 and health["total_steps"] == 5
    assert health["last_step_age_s"] is not None
    assert 0 <= health["last_step_age_s"] < 600
    assert health["recompiles"] == 0


def test_debug_trace_endpoint_opens_window(scraped_run):
    reply = scraped_run["trace"]
    assert reply["duration_ms"] == 150
    assert "trace_dir" in reply


def test_gru_convergence_histogram_populated(scraped_run):
    # gru_telemetry=True with train_iters=2 -> one delta per step
    hist = scraped_run["telemetry"].gru_delta
    assert hist.count == 5
    assert hist.mean() > 0  # params move, so consecutive preds differ


def test_event_log_replays_into_coherent_timeline(scraped_run):
    recs = list(replay(scraped_run["events_path"]))
    kinds = [r["event"] for r in recs]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    start = recs[0]
    assert start["schema_version"] == telemetry.SCHEMA_VERSION
    assert start["model_config"]["n_gru_layers"] == 1  # config snapshot
    assert start["train_config"]["num_steps"] == 5
    assert start["run"]["platform"] == "cpu"  # device topology
    assert "step_stats" in kinds and "checkpoint" in kinds
    stats = [r for r in recs if r["event"] == "step_stats"]
    assert all(a["step"] <= b["step"] for a, b in zip(stats, stats[1:]))
    assert "loss" in stats[-1]["means"]
    end = recs[-1]
    assert end["status"] == "complete" and end["step"] == 5
    assert [r["seq"] for r in recs] == list(range(len(recs)))
    assert all(a["ts"] <= b["ts"] for a, b in zip(recs, recs[1:]))


def test_telemetry_disabled_adds_no_device_fetches(tmp_path, monkeypatch):
    """The acceptance guarantee: with telemetry off (default) the loop
    issues EXACTLY the fetches the instrumented loop issues — i.e. the
    instrumentation adds none, and disabling it takes the pre-telemetry
    path.  Counted at jax.device_get, the loop's only fetch primitive."""
    real_device_get = jax.device_get
    counts = []

    def run_counting(telemetry_obj, sub):
        calls = [0]

        def counting_get(x):
            calls[0] += 1
            return real_device_get(x)

        monkeypatch.setattr(jax, "device_get", counting_get)
        try:
            _run_train(tmp_path / sub, telemetry_obj, num_steps=2,
                       train_iters=1, gru_telemetry=False)
        finally:
            monkeypatch.setattr(jax, "device_get", real_device_get)
        counts.append(calls[0])

    run_counting(None, "off")
    run_counting(TrainTelemetry(), "on")
    assert counts[0] == counts[1], counts


# ---------------------------------------------------------- telemetry http
def test_telemetry_endpoint_errors():
    from raft_stereo_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("x_total", "t").inc(3)
    server = TelemetryHTTPServer(reg, lambda: {"status": "ok"},
                                 port=0).start()
    try:
        body = urllib.request.urlopen(server.url + "/metrics",
                                      timeout=10).read().decode()
        assert "x_total 3" in body
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(server.url + "/nope", timeout=10)
        assert e.value.code == 404
        bad = urllib.request.Request(server.url + "/debug/trace",
                                     data=b'{"duration_ms": "soon"}',
                                     method="POST")
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(bad, timeout=10)
        assert e.value.code == 400
    finally:
        server.shutdown()


# ------------------------------------------------------------- logger fix
def test_logger_running_mean_uses_actual_count(caplog):
    """Regression (ISSUE 3 satellite): the first flush window holds only
    SUM_FREQ-1 pushes, and the close() drain fewer still — the mean must
    divide by the actual accumulated count, not SUM_FREQ."""
    import logging

    from raft_stereo_tpu.training.logger import SUM_FREQ, Logger

    with caplog.at_level(logging.INFO,
                         logger="raft_stereo_tpu.training.logger"):
        logger = Logger(enable_tensorboard=False)
        for _ in range(SUM_FREQ - 1):  # exactly one flush, 99 pushes
            logger.push({"loss": 2.0})
        assert logger.running_count == 0, "first window must have flushed"
        assert "loss 2.0000" in caplog.text  # old code logged 1.9800
        caplog.clear()
        for _ in range(5):
            logger.push({"loss": 4.0})
        logger.close()  # partial drain: 5 pushes, mean still exact
        assert "loss 4.0000" in caplog.text


def test_logger_context_manager_closes_writer(tmp_path):
    from raft_stereo_tpu.training.logger import Logger

    class _Writer:
        closed = False

        def add_scalar(self, *a, **k):
            pass

        def close(self):
            self.closed = True

    writer = _Writer()
    with Logger(enable_tensorboard=False) as logger:
        logger.writer = writer
        logger.push({"loss": 1.0})
    assert writer.closed
    assert logger.writer is None
