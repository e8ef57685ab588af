"""Compiler-cost & efficiency layer (telemetry/costs.py): the AOT compile
registry, backend-degradation contract, runner cache eviction telemetry,
padding-waste accounting, MFU gauges, and the per-phase cost report.

The load-bearing assertions: with cost telemetry ON, chain-mode serving
stays bitwise-identical to solo inference (the AOT executable runs the
same program the jit path compiles), and with it OFF nothing in the
dispatch path changes (the registry-less runner keeps plain ``jax.jit``
callables).  A backend whose ``cost_analysis``/``memory_analysis`` raises
or returns nothing must degrade to a compile-time-only record, never an
error on the dispatch path.
"""

import json
import logging
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu.config import RaftStereoConfig
from raft_stereo_tpu.telemetry.costs import (CompileRegistry, MfuMeter,
                                             classify_bound,
                                             executable_cost,
                                             peak_flops_for,
                                             ridge_flops_per_byte)
from raft_stereo_tpu.telemetry.registry import MetricsRegistry

TINY = dict(hidden_dims=(32, 32, 32), fnet_dim=64, corr_backend="reg")
ITERS = 1


@pytest.fixture(scope="module")
def tiny_model():
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    cfg = RaftStereoConfig(**TINY)
    model = RAFTStereo(cfg)
    img = jnp.zeros((1, 48, 64, 3), jnp.float32)
    variables = jax.jit(lambda r: model.init(r, img, img, iters=1,
                                             test_mode=True)
                        )(jax.random.PRNGKey(0))
    return cfg, jax.device_get(variables)


# ------------------------------------------------------------- registry core
def test_instrumented_fn_records_cost_and_matches_jit():
    registry = MetricsRegistry()
    costs = CompileRegistry(registry=registry)
    f = jax.jit(lambda x: (x @ x).sum())
    inst = costs.instrument(f, key="t.mm", site="bench")
    x = jnp.ones((32, 32))
    assert float(inst(x)) == float(f(x))
    assert float(inst(x)) == float(f(x))  # cached-executable path

    rec = costs.get("t.mm")
    assert rec is not None and rec.site == "bench"
    assert rec.flops and rec.flops > 0
    assert rec.bytes_accessed and rec.bytes_accessed > 0
    assert rec.memory["argument_size_in_bytes"] == 32 * 32 * 4
    assert rec.compile_s > 0 and not rec.degraded
    assert rec.arithmetic_intensity == rec.flops / rec.bytes_accessed
    # one executable, one compile, instruments live
    assert costs.to_json()["count"] == 1
    assert registry.get("compiles_total").value == 1
    assert registry.get("compile_seconds").count == 1

    # shape change re-lowers (a recorded recompile), results stay correct
    y = jnp.full((16, 16), 2.0)
    assert float(inst(y)) == float(f(y))
    assert registry.get("compiles_total").value == 2


def test_record_survives_metric_registry_absence():
    costs = CompileRegistry()  # no MetricsRegistry attached at all
    f = jax.jit(lambda x: x + 1)
    inst = costs.instrument(f, key="t.add", site="eval")
    np.testing.assert_array_equal(np.asarray(inst(jnp.zeros(4))), np.ones(4))
    assert costs.get("t.add").flops is not None


# ------------------------------------------------- degradation (satellite)
class _Broken:
    """Compiled-alike whose analyses fail like older-jax/odd backends."""

    def __init__(self, cost_exc=True, mem_exc=True):
        self._cost_exc, self._mem_exc = cost_exc, mem_exc

    def cost_analysis(self):
        if self._cost_exc:
            raise NotImplementedError("backend reports no costs")
        return []          # empty list: another observed older-jax shape

    def memory_analysis(self):
        if self._mem_exc:
            raise NotImplementedError("backend reports no memory stats")
        return None


def test_executable_cost_degrades_without_raising():
    for broken in (_Broken(), _Broken(cost_exc=False),
                   _Broken(mem_exc=False)):
        out = executable_cost(broken)
        assert out["degraded"] is True
        assert out["flops"] is None and out["memory"] is None


def test_dispatch_path_survives_broken_cost_analysis(monkeypatch):
    """cost_analysis raising on a REAL compiled executable yields a
    degraded-but-valid record and an unchanged result — the satellite
    contract that cost accounting can never fail a dispatch."""
    f = jax.jit(lambda x: x * 2)
    compiled_cls = type(f.lower(jnp.ones(3)).compile())

    def _boom(self):
        raise RuntimeError("no costs on this backend")

    monkeypatch.setattr(compiled_cls, "cost_analysis", _boom)
    monkeypatch.setattr(compiled_cls, "memory_analysis", _boom)
    costs = CompileRegistry(registry=MetricsRegistry())
    inst = costs.instrument(jax.jit(lambda x: x * 2), key="t.deg",
                            site="eval")
    np.testing.assert_array_equal(np.asarray(inst(jnp.ones(3))),
                                  np.full(3, 2.0))
    rec = costs.get("t.deg")
    assert rec.degraded and rec.flops is None and rec.memory is None
    assert rec.compile_s > 0  # compile-time-only record


def test_aot_compile_falls_back_when_lowering_fails():
    class _NoAot:
        def lower(self, *a, **k):
            raise TypeError("no AOT on this stage")

        def __call__(self, x):
            return x + 41

    costs = CompileRegistry(registry=MetricsRegistry())
    fn = costs.aot_compile(_NoAot(), jnp.ones(()), key="t.noaot",
                           site="train")
    assert float(fn(jnp.ones(()))) == 42.0  # the plain callable came back
    assert costs.get("t.noaot").degraded


# --------------------------------------------- runner cache (satellite)
def test_runner_eviction_is_logged_and_counted(tiny_model, caplog):
    from raft_stereo_tpu.eval.runner import InferenceRunner

    cfg, variables = tiny_model
    registry = MetricsRegistry()
    costs = CompileRegistry(registry=registry)
    runner = InferenceRunner(cfg, variables, iters=ITERS,
                             max_cached_shapes=2, cost_registry=costs)
    # _forward_for only BUILDS the per-shape callables (no execution), so
    # filling the cache past its bound is cheap.
    shapes = [(32, 64), (64, 64), (64, 96), (96, 96)]
    with caplog.at_level(logging.INFO, logger="raft_stereo_tpu.eval.runner"):
        for s in shapes:
            runner._forward_for(s)
    # oldest-first: the two oldest shapes were evicted, newest two remain
    assert list(runner._compiled) == [(s, 1) for s in shapes[2:]]
    assert registry.get("runner_compile_evictions_total").value == 2
    assert registry.get("runner_compile_cache_size").value == 2
    evict_logs = [r for r in caplog.records if "evicting oldest" in r.message]
    assert len(evict_logs) == 2
    assert "(32, 64)" in evict_logs[0].getMessage()  # the oldest went first

    # registry-less runner: same logging, no instruments, plain jit cached
    bare = InferenceRunner(cfg, variables, iters=ITERS, max_cached_shapes=1)
    bare._forward_for((32, 64))
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="raft_stereo_tpu.eval.runner"):
        bare._forward_for((64, 64))
    assert any("evicting oldest" in r.message for r in caplog.records)
    from raft_stereo_tpu.telemetry.costs import _InstrumentedFn
    assert not isinstance(bare._forward_for((64, 64)), _InstrumentedFn)
    assert isinstance(runner._forward_for((96, 96)), _InstrumentedFn)


# --------------------------------------------------------- peaks and MFU
def test_peak_table_and_override():
    assert peak_flops_for("TPU v5 lite") == 197e12
    assert peak_flops_for("TPU v4") == 275e12
    assert peak_flops_for("weird accelerator") is None
    assert peak_flops_for("cpu", override_tflops=2.0) == 2e12
    ridge, src = ridge_flops_per_byte(197e12, 819e9)
    assert src == "device" and ridge == pytest.approx(240.5, abs=0.5)
    # no published peak: no ridge, never another chip's
    assert ridge_flops_per_byte(None, None) == (None, "unknown")
    assert classify_bound(1e9, 1e6, None) == "unknown"
    with pytest.raises(ValueError, match="peak tables"):
        peak_flops_for("TPU v9 hypothetical")
    assert classify_bound(1e9, 1e6, 240.0) == "compute"
    assert classify_bound(1e6, 1e6, 240.0) == "memory"
    assert classify_bound(None, 1e6, 240.0) == "unknown"


def test_mfu_meter_window_math():
    from raft_stereo_tpu.telemetry.registry import Gauge

    mfu, achieved = Gauge("m"), Gauge("a")
    meter = MfuMeter(mfu, peak_flops=100.0, achieved_gauge=achieved,
                     window_s=60.0)
    meter.note(500.0, now=100.0)   # first note: no elapsed window yet
    assert mfu.value == 0.0
    meter.note(500.0, now=110.0)   # 1000 flops over 10 s = 100 flop/s
    assert achieved.value == pytest.approx(100.0)
    assert mfu.value == pytest.approx(1.0)

    unknown = MfuMeter(Gauge("m2"), peak_flops=None)
    unknown.note(500.0, now=0.0)
    unknown.note(500.0, now=10.0)
    assert unknown.gauge.value == 0.0  # no fictional MFU without a peak


# ------------------------------------------- labeled instrument families
def test_registry_label_families_render_grouped():
    r = MetricsRegistry()
    a = r.counter("px_total", "pixels", labels={"bucket": "64x96"})
    b = r.counter("px_total", "pixels", labels={"bucket": "32x64"})
    with pytest.raises(ValueError):
        r.counter("px_total", "pixels", labels={"bucket": "64x96"})
    a.inc(5), b.inc(7)
    text = r.render_text()
    assert 'px_total{bucket="64x96"} 5' in text
    assert 'px_total{bucket="32x64"} 7' in text
    # exactly one HELP/TYPE header for the family, samples grouped under it
    assert text.count("# TYPE px_total counter") == 1
    assert r.get("px_total", labels={"bucket": "32x64"}) is b
    assert r.get("px_total") in (a, b)


# ----------------------------------------------------- serving integration
def test_serving_cost_telemetry_end_to_end(tiny_model, tmp_path):
    """Cost telemetry ON: chain-mode results stay bitwise-equal to a solo
    registry-less runner, /debug/compiles lists the bucket executables
    with cost+memory fields, padding waste is accounted per bucket, the
    MFU plumbing sees the dispatched flops, and the first compile of a
    bucket emits a run event (the serving satellite)."""
    from raft_stereo_tpu.eval.runner import InferenceRunner
    from raft_stereo_tpu.serving import ServeConfig, StereoService
    from raft_stereo_tpu.serving.http import StereoHTTPServer
    from raft_stereo_tpu.telemetry import EventLog, replay

    cfg, variables = tiny_model
    rng = np.random.default_rng(7)
    left = rng.integers(0, 255, (60, 90, 3), np.uint8)    # pads to 64x96
    right = rng.integers(0, 255, (60, 90, 3), np.uint8)
    small_l = rng.integers(0, 255, (30, 40, 3), np.uint8)  # pads to 32x64
    small_r = rng.integers(0, 255, (30, 40, 3), np.uint8)

    events = EventLog(str(tmp_path / "serve-events.jsonl"))
    svc = StereoService(cfg, variables,
                        ServeConfig(iters=ITERS, max_wait_ms=1.0,
                                    cost_telemetry=True,
                                    device_peak_tflops=0.001))
    svc.costs.events = events
    server = StereoHTTPServer(svc, port=0).start()
    try:
        res = svc.infer(left, right, timeout=120)
        svc.infer(small_l, small_r, timeout=120)

        solo = InferenceRunner(cfg, variables, iters=ITERS)
        flow, _ = solo(left, right)
        np.testing.assert_array_equal(res.flow, flow)  # bitwise, AOT vs jit

        compiles = json.load(urllib.request.urlopen(
            server.url + "/debug/compiles", timeout=10))
        assert compiles["count"] == 2
        by_key = {e["key"]: e for e in compiles["executables"]}
        assert set(by_key) == {"serving.forward(64x96,b1)",
                               "serving.forward(32x64,b1)"}
        for e in by_key.values():
            assert e["flops"] > 0 and e["bytes_accessed"] > 0
            assert e["memory"]["argument_size_in_bytes"] > 0
            assert not e["degraded"]

        text = urllib.request.urlopen(server.url + "/metrics",
                                      timeout=10).read().decode()
        # mixed-shape load: nonzero waste histogram + per-bucket counters
        assert "serve_padding_waste_count 2" in text
        assert ('serve_bucket_real_pixels_total{bucket="64x96"} '
                f"{60 * 90}") in text
        assert ('serve_bucket_pad_pixels_total{bucket="64x96"} '
                f"{64 * 96 - 60 * 90}") in text
        assert text.count("# TYPE serve_bucket_pad_pixels_total") == 1
        waste = svc.metrics.padding_waste
        assert 0 < waste.mean() < 1
        # MFU numerator: both dispatches' flops counted, gauge moved
        total_flops = sum(e["flops"] for e in by_key.values())
        assert svc.metrics.dispatched_flops.value == pytest.approx(
            total_flops)
        assert svc.metrics.achieved_flops_per_s.value >= 0

        kinds = [e for e in replay(events.path) if e["event"] == "compile"]
        assert len(kinds) == 2 and kinds[0]["site"] == "serving"
        assert kinds[0]["flops"] > 0
    finally:
        server.shutdown()
        svc.close()
        events.close()


def test_cost_telemetry_off_keeps_plain_jit_dispatch(tiny_model):
    """The hard constraint: registry-off leaves the dispatch path
    untouched — the workers cache the plain jitted callables and no cost
    instruments register."""
    from raft_stereo_tpu.serving import ServeConfig, StereoService

    cfg, variables = tiny_model
    svc = StereoService(cfg, variables, ServeConfig(iters=ITERS))
    try:
        assert svc.costs is None and svc._mfu is None
        assert svc.metrics.registry.get("compiles_total") is None
        fwd = svc._forward_for((32, 64), batch=1)
        from raft_stereo_tpu.telemetry.costs import _InstrumentedFn
        assert not isinstance(fwd, _InstrumentedFn)
    finally:
        svc.close()


def test_debug_compiles_404_without_registry():
    from raft_stereo_tpu.telemetry.http import handle_debug_get

    replies = []
    handled = handle_debug_get(
        "/debug/compiles", "", None, None, None,
        lambda *a: replies.append(a),
        lambda code, obj: replies.append((code, obj)), costs=None)
    assert handled and replies[0][0] == 404


# ---------------------------------------------------- training integration
def test_train_step_cost_instrumented(tmp_path):
    """The instrumented train step lands in the registry with flops; the
    drain turns them into train_step_flops / achieved-FLOP/s gauges and
    the step_stats event carries step_flops; recompile detection stays at
    zero (the step-0 AOT compile is not a recompile)."""
    from raft_stereo_tpu.config import TrainConfig
    from raft_stereo_tpu.data.loader import StereoLoader
    from raft_stereo_tpu.telemetry import (CompileRegistry, EventLog,
                                           TrainTelemetry, replay)
    from raft_stereo_tpu.training.train_loop import train

    class _Synthetic:
        def __len__(self):
            return 4

        def __getitem__(self, i, epoch=0):
            img = np.full((32, 64, 3), float(i), np.float32)
            return {"image1": img, "image2": img,
                    "flow": np.full((32, 64), -2.0, np.float32),
                    "valid": np.ones((32, 64), np.float32)}

    registry = MetricsRegistry()
    events = EventLog(str(tmp_path / "events.jsonl"))
    costs = CompileRegistry(registry=registry, events=events,
                            device_peak_tflops=0.001)
    telemetry = TrainTelemetry(registry=registry, events=events, costs=costs)
    model_cfg = RaftStereoConfig(n_gru_layers=1, hidden_dims=(32,),
                                 fnet_dim=64, fnet_norm="none")
    train_cfg = TrainConfig(batch_size=2, train_iters=2, num_steps=3,
                            image_size=(32, 64), validation_frequency=10_000,
                            data_parallel=1)
    loader = StereoLoader(_Synthetic(), batch_size=2, num_workers=0,
                          shuffle=False)
    state = train(model_cfg, train_cfg, name="cost-test",
                  checkpoint_dir=str(tmp_path / "ckpt"),
                  log_dir=str(tmp_path / "runs"), loader=loader,
                  use_mesh=False, telemetry=telemetry)
    events.close()
    assert int(state.step) == 3

    rec = costs.get("train.step")
    assert rec is not None and rec.flops > 0 and not rec.degraded
    assert registry.get("train_step_flops").value == rec.flops
    assert registry.get("train_achieved_flops_per_s").value > 0
    assert registry.get("train_mfu").value > 0  # peak was given
    assert registry.get("train_recompiles_total").value == 0

    recs = list(replay(events.path))
    compile_events = [e for e in recs if e["event"] == "compile"]
    assert any(e.get("key") == "train.step" and e.get("flops")
               for e in compile_events)
    stats = [e for e in recs if e["event"] == "step_stats"]
    assert stats and stats[-1]["step_flops"] == rec.flops
    assert stats[-1]["mfu"] > 0


def test_unrolled_gru_matches_scan(tiny_model):
    """unroll_gru (every iteration as its own set of operations, which
    benchmark/tests/test_reference.py applies) runs the same math as the
    deployed scan."""
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    cfg, variables = tiny_model
    model = RAFTStereo(cfg)
    rng = np.random.default_rng(3)
    i1 = jnp.asarray(rng.uniform(0, 255, (1, 48, 64, 3)), jnp.float32)
    i2 = jnp.asarray(rng.uniform(0, 255, (1, 48, 64, 3)), jnp.float32)
    d_scan, f_scan = model.apply(variables, i1, i2, iters=2, test_mode=True)
    d_un, f_un = model.apply(variables, i1, i2, iters=2, test_mode=True,
                             unroll_gru=True)
    # Same math, two programs: XLA fuses the scan body and the unrolled
    # body differently, and the untrained GRU amplifies that
    # reassociation noise ~5x per iteration (measured 5.6e-5 abs at 2
    # iterations under jaxlib 0.9.0 on flows of order 10 px) — so the
    # tolerance is the 2-iteration noise floor, not bitwise.
    np.testing.assert_allclose(np.asarray(f_scan), np.asarray(f_un),
                               atol=2e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(d_scan), np.asarray(d_un),
                               atol=2e-4, rtol=1e-5)
