"""Host phases (telemetry/spans.py ``Phases``): the serving engine's and the
runner's boundaries as spans on the profiler's clock, one pair of clock
reads each feeding the profiler, ``/metrics`` and the sampled request's
trace.  CPU, tiny size: what is counted and in which order, never how long.
"""

import contextlib
import glob
import io
import json
import subprocess
import sys
import urllib.request

import jax
import numpy as np
import pytest

from raft_stereo_tpu.serving.engine import HTTP_PHASES, WORKER_PHASES
from test_serving import (ITERS, _pairs, _post, _staged,  # noqa: F401
                          tiny_model)

DISPATCH_PHASES = WORKER_PHASES[1:]         # all but the wait for work


def _engine(tiny_model, **kw):
    from raft_stereo_tpu.serving import ServeConfig, StereoService

    cfg, variables = tiny_model
    return StereoService(cfg, variables,
                         ServeConfig(iters=ITERS, **kw))


def _phase_sums(svc):
    return {name: (h.count, h.sum)
            for name, h in svc.phases.histograms.items()}


@contextlib.contextmanager
def _capture(trace_dir):
    """A capture that keeps the program's own spans and nothing else of the
    host (no Python calls, no thread-pool events): small and quick."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _host_events(trace_dir, prefix):
    """Per line (a thread) of the capture under ``trace_dir`` that holds
    any: its events whose name starts with ``prefix``, in time order, as
    ``(name, start_ns, end_ns, stats)``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir) + "/**/*.xplane.pb", recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name,
                          dict(e.stats)) for e in line.events
                         if e.name.startswith(prefix))
            if evs:
                lines.append([(n, a, b, st) for a, b, n, st in evs])
    return lines


# ------------------------------------------------- always on, and only that
def test_a_dispatch_feeds_every_worker_phase_and_nothing_else(
        tiny_model, monkeypatch):
    """No capture, sampling 0: one dispatch grows each worker phase's
    histogram by one, the span ring by nothing, and the phases are handed
    nothing that lives on the device."""
    recorded, gets = [], [0]
    lefts, rights = _pairs(2)
    with _engine(tiny_model, max_batch=2) as svc:
        real_record = svc.phases.record
        monkeypatch.setattr(
            svc.phases, "record",
            lambda name, *a, **attrs: (recorded.append((name, attrs)),
                                       real_record(name, *a, **attrs)))
        real_get = jax.device_get
        monkeypatch.setattr(
            jax, "device_get",
            lambda x: (gets.__setitem__(0, gets[0] + 1), real_get(x))[1])
        before = _phase_sums(svc)
        results = _staged(svc, lefts, rights)       # one batch-2 dispatch
        after = _phase_sums(svc)
        assert svc.tracer.spans() == []
    assert [r.batch_size for r in results] == [2, 2]
    assert set(after) == set(WORKER_PHASES + HTTP_PHASES)
    for name in DISPATCH_PHASES:
        assert after[name][0] - before[name][0] == 1, name
    assert after["wait_work"][0] - before["wait_work"][0] == 1
    assert after["admission"][0] - before["admission"][0] == 2
    assert gets[0] == 0
    assert {name for name, _ in recorded} >= set(WORKER_PHASES)
    for name, attrs in recorded:
        assert all(type(v) in (int, str) for v in attrs.values()), (name,
                                                                    attrs)
    by_name = dict(recorded)
    assert by_name["assemble"]["batch_size"] == 2
    assert by_name["assemble"]["bucket"] == "(64, 64)"
    assert by_name["assemble"]["seq"] >= 1
    assert by_name["upload"]["bytes"] == 2 * 2 * 64 * 64 * 3
    assert by_name["fetch"]["bytes"] == 2 * 64 * 64 * 4


def test_device_seconds_is_assemble_upload_execute(tiny_model):
    """The legs that kept their names are sums of the phases' readings."""
    lefts, rights = _pairs(1)
    with _engine(tiny_model, max_batch=1) as svc:
        svc.infer(lefts[0], rights[0], timeout=120)       # compiles
        before = _phase_sums(svc)
        d0, f0 = svc.metrics.device_time.sum, svc.metrics.fetch_time.sum
        res = svc.infer(lefts[0], rights[0], timeout=120)
        after = _phase_sums(svc)
        grew = {n: after[n][1] - before[n][1] for n in after}
        device = svc.metrics.device_time.sum - d0
        fetch = svc.metrics.fetch_time.sum - f0
    assert device == pytest.approx(
        grew["assemble"] + grew["upload"] + grew["execute"], abs=1e-9)
    assert fetch == pytest.approx(grew["fetch"], abs=1e-9)
    assert res.device_s == pytest.approx(device, abs=1e-9)
    assert res.fetch_s == pytest.approx(fetch, abs=1e-9)
    assert res.total_s >= res.queue_wait_s + res.device_s + res.fetch_s


def test_wait_work_carries_queue_depth_and_popped(tiny_model):
    """Three staged requests on a ladder of (1, 2): the first pop took two
    (its wait began before, between or after their submits), the second
    began with one queued and took it."""
    lefts, rights = _pairs(3)
    with _engine(tiny_model, max_batch=2, batch_sizes=(1, 2),
                 trace_sample_rate=1.0) as svc:
        results = _staged(svc, lefts, rights)
        spans = svc.tracer.spans()
    assert [r.batch_size for r in results] == [2, 2, 1]
    waits = {}
    for s in spans:
        if s.name == "serve.wait_work":
            waits[s.trace_id] = s.attrs
    first, _, third = (waits[r.trace_id] for r in results)
    assert first["queued_at_start"] in (0, 1, 2, 3) and first["popped"] == 2
    assert (third["queued_at_start"], third["popped"]) == (1, 1)


# ------------------------------------------- on the profiler's own timeline
@pytest.fixture(scope="module")
def captured_serving(tiny_model, tmp_path_factory):
    """Engine and HTTP front end built, driven (three sequential posts, all
    sampled) and closed inside one capture, so that every wait of the
    worker opens and closes inside it."""
    from raft_stereo_tpu.serving.http import StereoHTTPServer

    trace_dir = tmp_path_factory.mktemp("capture")
    lefts, rights = _pairs(1)
    buf = io.BytesIO()
    np.savez(buf, left=lefts[0], right=rights[0])
    replies = []
    with _capture(trace_dir):
        svc = _engine(tiny_model, max_batch=1, trace_sample_rate=1.0)
        server = StereoHTTPServer(svc, port=0).start()
        try:
            for _ in range(3):
                status, headers, _ = _post(server.url + "/v1/disparity",
                                           buf.getvalue())
                assert status == 200
                with urllib.request.urlopen(
                        server.url + "/debug/spans?trace="
                        + headers["X-Trace-Id"], timeout=30) as resp:
                    replies.append(json.loads(resp.read())["spans"])
        finally:
            server.shutdown()
            svc.close()
        sums = _phase_sums(svc)
    return {"lines": _host_events(trace_dir, "serve."), "sums": sums,
            "debug_spans": replies}


def test_capture_holds_one_event_per_phase_per_dispatch(captured_serving):
    lines = captured_serving["lines"]
    worker = [evs for evs in lines
              if any(n == "serve.execute" for n, *_ in evs)]
    assert len(worker) == 1             # all of a worker's phases, one line
    (evs,) = worker
    for (_, _, end, _), (_, start, _, _) in zip(evs, evs[1:]):
        assert end <= start             # siblings: none overlaps the next
    cycle = ["serve." + p for p in WORKER_PHASES]
    # wait, then the six phases of a dispatch, three times; then the wait
    # that the close ended
    assert [n for n, *_ in evs] == cycle * 3 + ["serve.wait_work"]
    assert [st["seq"] for n, _, _, st in evs
            if n == "serve.assemble"] == [1, 2, 3]
    assert all(st["batch_size"] == 1 for n, _, _, st in evs
               if n != "serve.wait_work")
    assert [st["popped"] for n, _, _, st in evs
            if n == "serve.wait_work"] == [1, 1, 1, 0]


@pytest.mark.parametrize("phase", WORKER_PHASES + ("decode", "encode"))
def test_an_events_duration_is_the_histograms(captured_serving, phase):
    """The profiler's event and the histogram come from the same scope:
    their sums agree within 2 ms."""
    traced = sum(b - a for evs in captured_serving["lines"]
                 for n, a, b, _ in evs if n == "serve." + phase) * 1e-9
    count, total = captured_serving["sums"][phase]
    assert count == (4 if phase == "wait_work" else 3)
    assert traced == pytest.approx(total, abs=2e-3)


def test_http_threads_scope_decode_and_encode(captured_serving):
    on_http = [evs for evs in captured_serving["lines"]
               if any(n == "serve.decode" for n, *_ in evs)]
    names = sorted(n for evs in on_http for n, *_ in evs)
    assert names == ["serve.decode"] * 3 + ["serve.encode"] * 3
    assert all(st["bytes"] > 0 for evs in on_http for _, _, _, st in evs)


def test_a_sampled_request_shows_the_same_phases(captured_serving):
    for spans in captured_serving["debug_spans"]:
        by_name = {s["name"]: s for s in spans}
        assert {"serve." + p for p in WORKER_PHASES} <= set(by_name)
        # the legs of before, made of the phases' own readings (wall-clock
        # microseconds in a double: good to a quarter of one)
        dispatch = by_name["serve.dispatch"]
        assert dispatch["start_us"] == by_name["serve.assemble"]["start_us"]
        end = lambda s: s["start_us"] + s["duration_us"]  # noqa: E731
        assert end(dispatch) == pytest.approx(
            end(by_name["serve.execute"]), abs=1.0)
        assert end(by_name["serve.queue"]) == pytest.approx(
            dispatch["start_us"], abs=1.0)


# ------------------------------------------------------------------ runner
def test_run_batch_emits_the_five_infer_events(tiny_model, tmp_path):
    from raft_stereo_tpu.eval.runner import RUNNER_PHASES, InferenceRunner
    from raft_stereo_tpu.telemetry import MetricsRegistry
    from raft_stereo_tpu.telemetry.costs import CompileRegistry

    cfg, variables = tiny_model
    registry = MetricsRegistry()
    runner = InferenceRunner(cfg, variables, iters=ITERS,
                             cost_registry=CompileRegistry(registry))
    lefts, rights = _pairs(2)
    runner.run_batch(lefts, rights)                         # compiles
    with _capture(tmp_path):
        flows, seconds = runner.run_batch(lefts, rights)
        flow, _ = runner(lefts[0], rights[0])
    assert flows.shape == (2, 48, 64) and flow.shape == (48, 64)
    (evs,) = _host_events(tmp_path, "infer.")
    assert [n for n, *_ in evs] == ["infer." + p for p in RUNNER_PHASES] * 2
    assert [st["batch_size"] for *_, st in evs] == [2] * 5 + [1] * 5
    assert evs[1][3]["bytes"] == 2 * 2 * 64 * 64 * 3        # upload
    assert evs[3][3]["bytes"] == 2 * 48 * 64 * 4            # fetch, cropped
    # the staging pair: the batch of 2 again, then a new one for the single
    assert evs[0][3]["bytes"] == 2 * 2 * 64 * 64 * 3
    assert [evs[i][3]["reused"] for i in (0, 5)] == [True, False]
    # the seconds end with the fetch, as they did before the phases
    assert 0 < (evs[3][2] - evs[0][1]) * 1e-9 - seconds < 2e-3
    for p in RUNNER_PHASES:
        assert registry.get("infer_phase_seconds",
                            {"phase": p}).count == 3, p
    # a runner with no registry still scopes its phases
    assert InferenceRunner(cfg, variables, iters=ITERS
                           ).phases.histograms == {}


# ------------------------------------------------------------------ router
_IMPORT_PROBE = """
import sys
from raft_stereo_tpu.telemetry.registry import MetricsRegistry
from raft_stereo_tpu.telemetry.spans import Phases, SpanTracer
phases = Phases("route.", ("pick",), MetricsRegistry(), "route_phase_seconds",
                SpanTracer(1.0))
with phases.phase("pick", replica="r0") as p:
    p.set(tried=1)
bridge = sys.modules.get("jax._src.xla_bridge")
print("BACKEND_UP", bool(bridge is not None
                         and bridge.backends_are_initialized()))
"""


def test_importing_and_using_phases_initialises_no_backend():
    """The fleet router imports ``telemetry.spans`` and must never hold the
    chip: neither the import nor a phase may initialise a jax backend."""
    import os

    out = subprocess.run([sys.executable, "-c", _IMPORT_PROBE],
                         capture_output=True, text=True, timeout=120,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "BACKEND_UP False" in out.stdout, out.stdout
