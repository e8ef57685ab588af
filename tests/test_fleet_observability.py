"""Fleet observability (round 23): cross-process trace propagation,
metrics federation, SLO burn-rate alerting, and the coordinated
flight-recorder dump.

Covers the ISSUE-18 acceptance surface:

* traceparent codec round-trip + malformed-input rejection, and
  ``adopt_trace`` overriding the local sample rate (the upstream
  sampling decision wins);
* federation text transforms — quote-aware label injection, label-value
  escaping round-trip, HELP/TYPE dedup across replicas — plus the
  ``MetricsFederator`` edge cases (replica dies mid-scrape → stale
  marker without a request-path stall; aged-out series vanish);
* ``BurnRateTracker`` window math under a fake clock (restart clamp,
  budget normalisation) and ``SloWatchdog`` trip/hysteresis/dump;
* the end-to-end proof: ONE trace id appearing in the router's span ring
  AND the replica's, merged by ``GET /debug/spans?trace=<id>`` — across
  a transport failover retry (two ``route.forward`` children under one
  trace) — and on a REAL engine replica (serve.request adopted as a
  child of the router's span);
* router error paths (503 ``no_replicas_ready``, 410 ``session_lost``)
  carrying ``X-Trace-Id`` and counting toward the SLO error totals.
"""

import io
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from raft_stereo_tpu.serving.fleet import (FleetRouter, MetricsFederator,
                                           RouterConfig, RouterHTTPServer,
                                           inject_label,
                                           relabel_exposition)
from raft_stereo_tpu.telemetry.registry import (MetricsRegistry,
                                                escape_label_value,
                                                unescape_label_value)
from raft_stereo_tpu.telemetry.slo import BurnRateTracker, SloWatchdog
from raft_stereo_tpu.telemetry.spans import (SpanTracer, TraceContext,
                                             decode_traceparent,
                                             encode_traceparent)

from tests.test_fleet import (FakeClock, StubReplica, TINY, _get, _post,
                              fleet3, tiny_model)  # noqa: F401  (fixtures)


# ------------------------------------------------------- traceparent codec
def test_traceparent_round_trip():
    hdr = encode_traceparent("ab" * 8, "cd" * 4)
    assert hdr == "00-abababababababab-cdcdcdcd-01"
    ctx = decode_traceparent(hdr)
    assert ctx == TraceContext("ab" * 8, "cd" * 4, sampled=True)


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-xyz-abc-01", "00-abab-01",
    "00-" + "0" * 16 + "-cdcdcdcd-01",      # all-zero trace id invalid
    "00-abababababababab-" + "0" * 8 + "-01",  # all-zero span id invalid
    "zz-abababababababab-cdcdcdcd-01",      # non-hex version
])
def test_traceparent_malformed_decodes_to_none(bad):
    assert decode_traceparent(bad) is None


def test_traceparent_lenient_widths_and_flags():
    # Foreign tracers emit 32-hex trace / 16-hex span ids; the decoder
    # is lenient on widths and only the sampled bit of flags matters.
    ctx = decode_traceparent(f"00-{'5' * 32}-{'7' * 16}-00")
    assert ctx is not None
    assert ctx.trace_id == "5" * 32 and ctx.parent_span_id == "7" * 16
    assert ctx.sampled is False


def test_adopt_trace_overrides_local_sample_rate():
    tracer = SpanTracer(sample_rate=0.0)
    assert tracer.start_trace("x") is None, "rate 0 must not sample"
    ctx = decode_traceparent(encode_traceparent("ab" * 8, "cd" * 4))
    trace = tracer.adopt_trace(ctx, "serve.request", bucket="(48, 64)")
    assert trace is not None and trace.trace_id == "ab" * 8
    tracer.finish_trace(trace)
    spans = [s for s in tracer.spans() if s.trace_id == "ab" * 8]
    assert len(spans) == 1
    # The adopted root parents to the UPSTREAM span id — the property
    # that stitches the replica subtree under the router's forward span.
    assert spans[0].parent_id == "cd" * 4
    assert spans[0].name == "serve.request"


def test_adopt_trace_none_context_falls_back_to_sampler():
    tracer = SpanTracer(sample_rate=0.0)
    assert tracer.adopt_trace(None, "serve.request") is None


# ------------------------------------------------- federation text engine
def test_inject_label_no_labelset():
    assert inject_label("metric 1", "replica", "r0") == \
        'metric{replica="r0"} 1'


def test_inject_label_existing_labelset():
    assert inject_label('m{a="b"} 1', "replica", "r0") == \
        'm{replica="r0",a="b"} 1'


def test_inject_label_empty_labelset():
    assert inject_label("m{} 1", "replica", "r0") == 'm{replica="r0"} 1'


def test_inject_label_brace_inside_quoted_value():
    # A `{` inside a quoted label VALUE is legal exposition text and
    # must not be mistaken for the labelset opener.
    line = 'm{path="/v1/{id}"} 3'
    assert inject_label(line, "replica", "r0") == \
        'm{replica="r0",path="/v1/{id}"} 3'


def test_inject_label_value_escaping_round_trips():
    # Satellite 3: replica names with quotes/backslashes/newlines
    # round-trip through the registry's own escape helpers.
    nasty = 'we"ird\\na\nme'
    out = inject_label("m 1", "replica", nasty)
    quoted = out.split('replica="', 1)[1].rsplit('"}', 1)[0]
    assert unescape_label_value(quoted) == nasty
    assert "\n" not in out, "raw newline would corrupt the exposition"


def test_relabel_exposition_dedups_help_type_across_replicas():
    # Satellite 3: two replicas exposing the SAME family merge under one
    # HELP/TYPE header, their samples distinguishable only by replica=.
    text = ("# HELP reqs_total Requests.\n"
            "# TYPE reqs_total counter\n"
            "reqs_total 5\n")
    seen = {}
    out_a = relabel_exposition(text, "replica", "a", seen)
    out_b = relabel_exposition(text, "replica", "b", seen)
    merged = out_a + out_b
    assert merged.count("# HELP reqs_total Requests.") == 1
    assert merged.count("# TYPE reqs_total counter") == 1
    assert 'reqs_total{replica="a"} 5' in merged
    assert 'reqs_total{replica="b"} 5' in merged


class _ScriptedReplica:
    """Duck-typed federation target: get_metrics returns scripted text
    or raises."""

    def __init__(self, text):
        self.text = text
        self.dead = False
        self.calls = 0

    def get_metrics(self, timeout):
        self.calls += 1
        if self.dead:
            raise ConnectionError("scripted death")
        return self.text


def test_federator_marks_dead_replica_stale_then_ages_out():
    clock = FakeClock()
    a = _ScriptedReplica("m_a 1\n")
    b = _ScriptedReplica("m_b 2\n")
    fed = MetricsFederator(lambda: [("a", a), ("b", b)], poll_s=1.0,
                           timeout_s=0.5, stale_after_s=30.0, clock=clock)
    assert fed.scrape_once() == {"a": True, "b": True}
    text = fed.render()
    assert 'fleet_federation_up{replica="a"} 1' in text
    assert 'm_a{replica="a"} 1' in text and 'm_b{replica="b"} 2' in text

    # b dies mid-scrape: its entry flips stale (up 0) but the LAST-GOOD
    # series stay exposed, and render() never blocks on the dead socket.
    b.dead = True
    clock.t += 5.0
    assert fed.scrape_once() == {"a": True, "b": False}
    text = fed.render()
    assert 'fleet_federation_up{replica="b"} 0' in text
    assert 'm_b{replica="b"} 2' in text, "last-good series stay visible"
    assert fed.status()["replicas"]["b"]["fresh"] is False

    # Past stale_after_s the series vanish; only the down marker stays.
    clock.t += 31.0
    text = fed.render()
    assert 'fleet_federation_up{replica="b"} 0' in text
    assert "m_b" not in text, "aged-out series must vanish"
    assert 'm_a{replica="a"} 1' not in text  # a aged out too (no scrape)


def test_federator_render_dedups_families_across_replicas_and_own():
    clock = FakeClock()
    fam = ("# HELP x_total X.\n# TYPE x_total counter\nx_total 1\n")
    a, b = _ScriptedReplica(fam), _ScriptedReplica(fam)
    fed = MetricsFederator(lambda: [("a", a), ("b", b)], poll_s=1.0,
                           timeout_s=0.5, clock=clock)
    fed.scrape_once()
    text = fed.render(own_text="# HELP own_total O.\n"
                               "# TYPE own_total counter\nown_total 9\n")
    assert text.count("# HELP x_total") == 1
    assert "own_total 9" in text and 'x_total{replica="a"} 1' in text


# --------------------------------------------------------- SLO burn rates
def test_burn_rate_tracker_windows_and_clamp():
    clock = FakeClock()
    reg = MetricsRegistry()
    tr = BurnRateTracker(availability=0.99, registry=reg, clock=clock,
                         windows=(("5m", 300.0), ("1h", 3600.0)))
    assert tr.sample(0, 0) == {"5m": 0.0, "1h": 0.0}
    clock.t += 100.0
    # 100 good, 1 bad → bad fraction 1/101 ≈ 0.0099, budget 0.01 → ~0.99
    burns = tr.sample(100, 1)
    assert burns["5m"] == pytest.approx(1 / 101 / 0.01)
    assert burns["1h"] == burns["5m"]
    text = reg.render_text()
    assert 'fleet_slo_burn_rate{window="5m"}' in text

    # A replica restart regresses the totals; deltas clamp at zero
    # instead of manufacturing negative traffic.
    clock.t += 100.0
    burns = tr.sample(10, 0)
    assert burns["5m"] == 0.0 and burns["1h"] == 0.0

    with pytest.raises(ValueError):
        BurnRateTracker(availability=1.0)


def test_burn_rate_fast_window_forgets_old_errors():
    clock = FakeClock()
    tr = BurnRateTracker(availability=0.999, clock=clock)
    tr.sample(0, 0)
    clock.t += 60.0
    tr.sample(100, 100)          # a cliff: 50% bad
    clock.t += 400.0             # past the 5m window, inside 1h
    burns = tr.sample(300, 100)  # 200 new good, 0 new bad
    assert burns["5m"] == 0.0, "the cliff left the fast window"
    assert burns["1h"] > 0.0, "…but still burns the slow one"


class _Sink:
    def __init__(self):
        self.fired = []

    def fire(self, kind, **detail):
        self.fired.append((kind, detail))


def test_slo_watchdog_requires_both_windows_then_rearms():
    clock = FakeClock()
    tr = BurnRateTracker(availability=0.999, clock=clock)
    sink = _Sink()
    dumps = []
    wd = SloWatchdog(tr, sink, fast_burn=14.4, slow_burn=6.0,
                     dump_fn=lambda tid, d: dumps.append(tid) or
                     {"trigger": tid},
                     id_fn=lambda: "feedbeef00000001")
    # Fast window alone breaching must NOT page (a blip).
    assert wd.check({"5m": 20.0, "1h": 1.0}) is None
    assert not sink.fired and not dumps
    # Both breaching: one page, one coordinated dump, versioned detail.
    rec = wd.check({"5m": 20.0, "1h": 7.0})
    assert rec is not None
    assert rec["trigger_trace_id"] == "feedbeef00000001"
    assert rec["fleet_dump"] == {"trigger": "feedbeef00000001"}
    assert sink.fired[0][0] == "slo_burn"
    assert dumps == ["feedbeef00000001"]
    # Still breaching: latched, no double fire.
    assert wd.check({"5m": 20.0, "1h": 7.0}) is None
    # Dropping below threshold but above HALF threshold: still latched.
    assert wd.check({"5m": 10.0, "1h": 4.0}) is None
    assert wd.check({"5m": 20.0, "1h": 7.0}) is None, \
        "no re-fire before the hysteresis re-arm"
    # Below half both: re-armed; next breach fires again.
    assert wd.check({"5m": 1.0, "1h": 1.0}) is None
    assert wd.check({"5m": 20.0, "1h": 7.0}) is not None
    assert len(wd.fired) == 2


# --------------------------------------------- router: stub-fleet tracing
def _traced_fleet(stubs):
    router = FleetRouter(
        {s.name: s.url for s in stubs},
        RouterConfig(health_timeout_s=2.0, fail_after=1,
                     request_timeout_s=5.0, fleet_brownout=False,
                     trace_sample_rate=1.0, slo_ms=10_000.0))
    router.check_replicas()
    return router


def test_router_trace_spans_and_header_propagation(fleet3):
    stubs, _ = fleet3
    router = _traced_fleet(stubs)
    server = RouterHTTPServer(router, port=0).start()
    try:
        status, headers, _ = _post(f"{server.url}/v1/disparity", b"px")
        assert status == 200
        tid = headers.get("X-Trace-Id")
        assert tid, "sampled request must echo its trace id"
        # The forwarded hop carried the context header naming the SAME
        # trace id (the replica-side adoption hook).
        fwd = [h for s in stubs for h in s.stateless_headers]
        assert len(fwd) == 1
        ctx = decode_traceparent(fwd[0].get("traceparent"))
        assert ctx is not None and ctx.trace_id == tid
        # The router's own ring has the route.request tree.
        status, _, body = _get(f"{server.url}/debug/spans?trace={tid}")
        assert status == 200
        view = json.loads(body)
        names = [s["name"] for s in view["spans"]]
        assert "route.request" in names and "route.forward" in names
        assert "route.pick" in names and "route.respond" in names
        assert all(s["trace_id"] == tid for s in view["spans"])
        # The forward span's id is the replica-side parent.
        fwd_span = next(s for s in view["spans"]
                        if s["name"] == "route.forward")
        assert ctx.parent_span_id == fwd_span["span_id"]
    finally:
        server.shutdown()
        router.stop()


def test_router_rate_zero_keeps_forwarding_untraced(fleet3):
    stubs, router = fleet3          # fleet3 router has sample rate 0
    server = RouterHTTPServer(router, port=0).start()
    try:
        status, headers, _ = _post(f"{server.url}/v1/disparity", b"px")
        assert status == 200
        assert "X-Trace-Id" not in headers
        fwd = [h for s in stubs for h in s.stateless_headers]
        assert all("traceparent" not in
                   {k.lower() for k in h} for h in fwd)
        assert router.tracer.stats()["traces_started"] == 0
    finally:
        server.shutdown()


def test_failover_retry_is_two_forward_children_one_trace(fleet3):
    """ISSUE acceptance: a transport failover mid-request shows up as
    TWO route.forward children (first with error=transport) under ONE
    trace id."""
    stubs, _ = fleet3
    router = _traced_fleet(stubs)
    server = RouterHTTPServer(router, port=0).start()
    try:
        stubs[0].kill()             # dead but still in rotation: the
        tid_with_retry = None       # next pick of s0 fails over inline
        for _ in range(12):
            status, headers, _ = _post(f"{server.url}/v1/disparity",
                                       b"px")
            assert status == 200
            tid = headers["X-Trace-Id"]
            # the handler closes the root AFTER the reply's last byte
            deadline = time.monotonic() + 5.0
            while True:
                spans = [s.to_dict() for s in router.tracer.spans()
                         if s.trace_id == tid]
                if (any(s["name"] == "route.request" for s in spans)
                        or time.monotonic() > deadline):
                    break
                time.sleep(0.01)
            fwd = [s for s in spans if s["name"] == "route.forward"]
            if len(fwd) >= 2:
                tid_with_retry = tid
                errors = [s["attrs"].get("error") for s in fwd]
                assert "transport" in errors
                ok = [s for s in fwd
                      if s["attrs"].get("status") == 200]
                assert len(ok) == 1
                root = [s for s in spans
                        if s["name"] == "route.request"]
                assert len(root) == 1
                assert all(s["trace_id"] == tid for s in fwd + root)
                break
        assert tid_with_retry is not None, \
            "12 requests over a 1/3-dead fleet must hit the dead " \
            "replica at least once"
    finally:
        server.shutdown()
        router.stop()


def test_router_error_paths_carry_trace_id_and_burn_budget(fleet3):
    stubs, _ = fleet3
    router = _traced_fleet(stubs)
    server = RouterHTTPServer(router, port=0).start()
    try:
        router.slo_tick()           # baseline snapshot to burn against
        # 410 session_lost: place a session, kill its replica, probe it
        # out of rotation.
        status, headers, _ = _post(f"{server.url}/v1/stream/cam-x", b"f")
        assert status == 200 and headers.get("X-Trace-Id")
        owner = next(s for s in stubs if "cam-x" in s.sessions)
        owner.kill()
        router.check_replicas()
        router.check_replicas()
        status, headers, body = _post(f"{server.url}/v1/stream/cam-x",
                                      b"f")
        assert status == 410
        assert json.loads(body)["error"] == "session_lost"
        assert headers.get("X-Trace-Id"), \
            "typed router errors must stay traceable"
        errors_after_410 = router.slo_errors.value
        assert errors_after_410 >= 1
        # 503 no_replicas_ready.
        for s in stubs:
            if s is not owner:
                s.kill()
        router.check_replicas()
        router.check_replicas()
        status, headers, body = _post(f"{server.url}/v1/disparity", b"x")
        assert status == 503
        assert json.loads(body)["error"] == "no_replicas_ready"
        assert headers.get("X-Trace-Id")
        assert router.slo_errors.value > errors_after_410
        # The SLO sampler folds the typed errors into the bad totals.
        burns = router.slo_tick()
        assert burns["5m"] > 0.0
    finally:
        server.shutdown()
        router.stop()


def test_router_metrics_fleet_federates_stub_series(fleet3):
    stubs, _ = fleet3
    router = _traced_fleet(stubs)
    server = RouterHTTPServer(router, port=0).start()
    try:
        assert router.federator.scrape_once() == {
            s.name: True for s in stubs}
        status, headers, body = _get(f"{server.url}/metrics/fleet")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        text = body.decode()
        # Router's own series, unlabelled (the router IS this target)…
        assert "fleet_replicas_ready" in text
        # …every stub's series with replica= injected, one HELP each…
        for s in stubs:
            assert f'fleet_federation_up{{replica="{s.name}"}} 1' in text
            assert (f'stub_requests_total{{replica="{s.name}",'
                    f'stub="{s.name}"}} 0') in text
        assert text.count("# HELP stub_requests_total") == 1
        # …and a mid-scrape death degrades to a stale marker without
        # stalling the endpoint.
        stubs[1].kill()
        router.federator.scrape_once()
        t0 = time.monotonic()
        status, _, body = _get(f"{server.url}/metrics/fleet")
        assert status == 200 and time.monotonic() - t0 < 1.0
        assert (f'fleet_federation_up{{replica="{stubs[1].name}"}} 0'
                in body.decode())
    finally:
        server.shutdown()
        router.stop()


def test_router_federated_spans_merge_replica_ring(fleet3):
    stubs, _ = fleet3
    router = _traced_fleet(stubs)
    server = RouterHTTPServer(router, port=0).start()
    try:
        status, headers, _ = _post(f"{server.url}/v1/disparity", b"px")
        tid = headers["X-Trace-Id"]
        # Script the serving-side half of the trace on every stub (the
        # real-engine merge is test_e2e below); the federated view must
        # pull the owning replica's spans and tag provenance.
        handler = next(s for s in stubs if s.stateless_headers)
        ctx = decode_traceparent(
            handler.stateless_headers[0]["traceparent"])
        handler.spans[tid] = [{
            "name": "serve.request", "trace_id": tid,
            "span_id": "aa" * 4, "parent_id": ctx.parent_span_id,
            "start_us": time.time() * 1e6, "duration_us": 42.0,
            "attrs": {}}]
        status, _, body = _get(f"{server.url}/debug/spans?trace={tid}")
        view = json.loads(body)
        procs = {s["process"] for s in view["spans"]}
        assert "router" in procs and handler.name in procs
        assert view["sources"][handler.name] == 1
        served = next(s for s in view["spans"]
                      if s["name"] == "serve.request")
        fwd_ids = {s["span_id"] for s in view["spans"]
                   if s["name"] == "route.forward"}
        assert served["parent_id"] in fwd_ids, \
            "replica subtree must stitch under the forward span"
    finally:
        server.shutdown()
        router.stop()


def test_fleet_status_and_replica_probe_stats(fleet3):
    """Satellite 2: /fleet entries expose probe_latency_ms (EWMA),
    last_state_change_ts, and the consecutive-failure count."""
    stubs, _ = fleet3
    router = _traced_fleet(stubs)
    router.check_replicas()
    st = router.fleet_status()
    assert st["slo"]["availability_objective"] == 0.999
    assert "5m" in st["slo"]["burn_rates"]
    assert st["federation"]["poll_s"] == 5.0
    for name, entry in st["replicas"].items():
        assert entry["probe_latency_ms"] is not None
        assert entry["probe_latency_ms"] >= 0.0
        assert entry["last_state_change_ts"] is not None
        assert entry["consecutive_failures"] == 0
    before = {n: e["last_state_change_ts"]
              for n, e in st["replicas"].items()}
    stubs[0].kill()
    time.sleep(0.05)
    router.check_replicas()
    entry = router.fleet_status()["replicas"][stubs[0].name]
    assert entry["consecutive_failures"] >= 1
    assert entry["last_state_change_ts"] > before[stubs[0].name]
    router.stop()


def test_watchdog_triggers_coordinated_fleet_dump(fleet3, tmp_path):
    """The full detector loop: synthesized burn → watchdog trip → router
    bundle + every replica POSTed /debug/flightrecorder + one manifest
    linking them under the trigger trace id."""
    stubs, _ = fleet3
    router = FleetRouter(
        {s.name: s.url for s in stubs},
        RouterConfig(health_timeout_s=2.0, fail_after=1,
                     request_timeout_s=5.0, fleet_brownout=False,
                     trace_sample_rate=1.0,
                     flight_recorder_dir=str(tmp_path)))
    router.check_replicas()
    try:
        rec = router.slo_watchdog.check({"5m": 100.0, "1h": 100.0})
        assert rec is not None
        manifest = rec["fleet_dump"]
        assert manifest["trigger_trace_id"] == rec["trigger_trace_id"]
        assert manifest["router_bundle"] is not None
        assert set(manifest["replicas"]) == {s.name for s in stubs}
        for s in stubs:
            assert s.flightrecorder_dumps == 1
            assert manifest["replicas"][s.name]["status"] == "dumped"
        with open(manifest["manifest_path"]) as f:
            on_disk = json.load(f)
        assert on_disk["trigger_trace_id"] == rec["trigger_trace_id"]
        assert router.anomalies.value == 1
        assert router.fleet_status()["fleet_dumps"] == 1
    finally:
        router.stop()


# ------------------------------------------------- real engine end-to-end
@pytest.mark.slow
def test_e2e_one_trace_id_across_router_and_real_engine(tiny_model):
    """ISSUE acceptance (e2e): rate-1.0 router in front of a REAL
    engine replica — the response's X-Trace-Id resolves through the
    router's federated /debug/spans to a merged timeline whose
    serve.request (replica process) is a child of the router's
    route.forward span."""
    from raft_stereo_tpu.serving import ServeConfig, StereoService
    from raft_stereo_tpu.serving.http import StereoHTTPServer

    cfg, variables = tiny_model
    rng = np.random.default_rng(3)
    left = rng.integers(0, 255, (48, 64, 3), dtype=np.uint8)
    buf = io.BytesIO()
    np.savez(buf, left=left, right=np.roll(left, -3, axis=1))
    payload = buf.getvalue()
    svc = StereoService(cfg, variables,
                        ServeConfig(max_batch=1, batch_sizes=(1,),
                                    iters=1))
    server = StereoHTTPServer(svc, port=0).start()
    router = FleetRouter(
        {"r0": server.url},
        RouterConfig(health_timeout_s=5.0, fleet_brownout=False,
                     trace_sample_rate=1.0))
    router.check_replicas()
    rserver = RouterHTTPServer(router, port=0).start()
    try:
        status, headers, _ = _post(
            f"{rserver.url}/v1/disparity", payload,
            {"Content-Type": "application/x-npz"}, timeout=300)
        assert status == 200
        tid = headers["X-Trace-Id"]
        assert tid
        # Replica side: the engine ran at sample rate 0 but ADOPTED the
        # router's context — its own /debug/spans knows the trace id.
        status, _, body = _get(
            f"{server.url}/debug/spans?trace={tid}", timeout=30)
        replica_view = json.loads(body)
        assert any(s["name"] == "serve.request"
                   for s in replica_view["spans"])
        # Router side: the federated endpoint merges both processes
        # into one timeline under the one id.
        status, _, body = _get(
            f"{rserver.url}/debug/spans?trace={tid}", timeout=30)
        view = json.loads(body)
        by_proc = {}
        for s in view["spans"]:
            by_proc.setdefault(s["process"], []).append(s)
        assert "router" in by_proc and "r0" in by_proc
        serve_root = next(s for s in by_proc["r0"]
                          if s["name"] == "serve.request")
        fwd = next(s for s in by_proc["router"]
                   if s["name"] == "route.forward")
        assert serve_root["parent_id"] == fwd["span_id"]
        assert serve_root["trace_id"] == fwd["trace_id"] == tid
        # Timeline ordering: merged spans sort by wall-clock start.
        starts = [s["start_us"] for s in view["spans"]]
        assert starts == sorted(starts)
    finally:
        rserver.shutdown()
        router.stop()
        server.shutdown()
        svc.close()
