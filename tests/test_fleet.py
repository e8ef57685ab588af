"""Fleet-layer tests (tier-1, CPU): the round-16 replicated-serving
story — consistent-hash routing, failover, the typed session-loss
contract, fleet-wide brownout propagation, artifact-store GC, and the
graceful-shutdown readiness flip.

Most tests run against STUB replicas — tiny stdlib HTTP servers speaking
the replica protocol (healthz/readyz/v1/* /admin/brownout) with
scriptable load and failure modes — so routing policy is exercised in
milliseconds with no JAX.  The acceptance pin (router pass-through is
byte-identical to hitting one replica directly) additionally runs
against a REAL engine at the bottom of the file.
"""

import io
import json
import os
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

import numpy as np
import pytest

from raft_stereo_tpu.serving.fleet import (FleetRouter, HashRing,
                                           NoReplicasAvailable,
                                           RouterConfig, RouterHTTPServer,
                                           SessionLost)

TINY = dict(hidden_dims=(32, 32, 32), fnet_dim=64, corr_backend="reg")


# ------------------------------------------------------------------- ring
def test_ring_sticky_and_deterministic():
    """Same session id -> same replica, across lookups AND across fresh
    ring instances (a router restart must not reshuffle live sessions)."""
    keys = [f"sess-{i}" for i in range(200)]
    r1 = HashRing(["a", "b", "c"])
    r2 = HashRing(["c", "a", "b"])         # insertion order irrelevant
    for k in keys:
        owner = r1.lookup(k)
        assert owner in ("a", "b", "c")
        assert r1.lookup(k) == owner        # sticky
        assert r2.lookup(k) == owner        # instance-independent


def test_ring_removal_remaps_only_the_dead_members_keys():
    """The consistent-hashing invariant (NOT mod-N): removing one of N
    replicas remaps exactly the keys it owned (~1/N), and every other
    key keeps its owner."""
    keys = [f"sess-{i}" for i in range(1200)]
    ring = HashRing(["a", "b", "c"])
    before = ring.assignment(keys)
    dead_keys = {k for k, v in before.items() if v == "b"}
    # roughly balanced: each member owns a nontrivial share
    frac = len(dead_keys) / len(keys)
    assert 0.15 < frac < 0.55, f"member share {frac:.2f} wildly skewed"
    ring.remove("b")
    after = ring.assignment(keys)
    for k in keys:
        if k in dead_keys:
            assert after[k] in ("a", "c")   # redistributed to survivors
        else:
            assert after[k] == before[k], \
                "a key not owned by the dead member must not move"
    # mod-N for contrast would have remapped ~2/3 of ALL keys; here the
    # remapped fraction IS the dead member's share.
    remapped = sum(1 for k in keys if after[k] != before[k])
    assert remapped == len(dead_keys)


def test_ring_readd_restores_original_assignment():
    keys = [f"sess-{i}" for i in range(500)]
    ring = HashRing(["a", "b", "c"])
    before = ring.assignment(keys)
    ring.remove("b")
    assert any(v == "b" for v in before.values())
    ring.add("b")
    assert ring.assignment(keys) == before, \
        "re-adding a member must restore the exact prior assignment " \
        "(member points are a pure function of the name)"


def test_ring_empty_and_single():
    ring = HashRing()
    assert ring.lookup("x") is None
    ring.add("only")
    assert all(ring.lookup(f"k{i}") == "only" for i in range(20))
    ring.remove("only")
    assert ring.lookup("x") is None


# ---------------------------------------------------------- stub replicas
class StubReplica:
    """A scriptable stand-in for one ``raft-serve`` process: speaks the
    replica HTTP protocol, records what it was asked, and can be killed
    or blackholed on demand."""

    def __init__(self, name: str, ready: bool = True,
                 queue_depth: int = 0, queue_limit: int = 64,
                 xl=None):
        self.name = name
        self.ready = ready
        self.queue_depth = queue_depth
        self.queue_limit = queue_limit
        self.blackhole_health = False
        self.xl = xl
        # Graceful-drain scripting (round 18): ``draining`` flips
        # healthz/readyz like a real SIGTERMed replica and the request
        # path sheds the typed draining 503; ``handoff_manifest`` is
        # what GET /admin/handoff serves (None -> 404, like an engine
        # that has not published yet).
        self.draining = False
        self.handoff_manifest = None
        self.requests = []
        self.sessions = []
        self.stream_headers = []
        self.stateless_headers = []
        self.brownout_levels = []
        # Observability scripting (round 23): what GET /metrics serves
        # (federation scrapes it), trace_id -> spans for GET
        # /debug/spans?trace=, and a count of coordinated
        # POST /debug/flightrecorder dumps.
        self.metrics_text = (
            "# HELP stub_requests_total Requests this stub handled.\n"
            "# TYPE stub_requests_total counter\n"
            f'stub_requests_total{{stub="{name}"}} 0\n')
        self.spans = {}
        self.flightrecorder_dumps = 0
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _send(self, code, body, ctype="application/json",
                      extra=()):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in extra:
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _json(self, code, obj, extra=()):
                self._send(code, (json.dumps(obj) + "\n").encode(),
                           extra=extra)

            def do_GET(self):
                if (outer.blackhole_health
                        and self.path in ("/healthz", "/readyz")):
                    self.close_connection = True
                    return
                if self.path == "/healthz":
                    self._json(200, {
                        "status": ("draining" if outer.draining
                                   else "ok"),
                        "ready": outer.ready and not outer.draining,
                        "queue_depth": outer.queue_depth,
                        "queue_limit": outer.queue_limit,
                        "inflight": 0, "brownout_level": 0,
                        "xl": outer.xl,
                        "sessions_active": len(set(outer.sessions))})
                elif self.path == "/readyz":
                    up = outer.ready and not outer.draining
                    self._json(200 if up else 503, {"ready": up})
                elif self.path == "/admin/handoff":
                    if outer.handoff_manifest is None:
                        self._json(404, {"error": "no_handoff"})
                    else:
                        self._json(200, outer.handoff_manifest)
                elif urlparse(self.path).path == "/metrics":
                    self._send(200, outer.metrics_text.encode(),
                               ctype="text/plain; version=0.0.4")
                elif urlparse(self.path).path == "/debug/spans":
                    q = parse_qs(urlparse(self.path).query)
                    tid = q.get("trace", [""])[0]
                    self._json(200, {"trace_id": tid,
                                     "spans": outer.spans.get(tid, [])})
                else:
                    self._json(404, {"error": "no route"})

            def do_POST(self):
                length = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(length) if length else b""
                path = urlparse(self.path).path
                outer.requests.append(("POST", self.path))
                if path == "/admin/brownout":
                    outer.brownout_levels.append(
                        json.loads(body)["level"])
                    self._json(200, {"status": "ok"})
                    return
                if path == "/debug/flightrecorder":
                    # The coordinated-dump fan-out target (round 23).
                    outer.flightrecorder_dumps += 1
                    self._json(200, {"status": "dumped",
                                     "bundle": f"/tmp/{outer.name}",
                                     "trigger": "forced"})
                    return
                if outer.draining and path.startswith("/v1/"):
                    # The engine's typed draining shed (begin_shutdown
                    # stopped admitting while the listener stays up).
                    self._json(503, {"error": "overloaded",
                                     "draining": True,
                                     "retry_after_s": 5.0},
                               extra=[("Retry-After", "5")])
                    return
                if path.startswith("/v1/stream/"):
                    sid = path[len("/v1/stream/"):]
                    outer.sessions.append(sid)
                    outer.stream_headers.append(
                        (sid, {k: v for k, v in self.headers.items()}))
                    warm = (outer.sessions.count(sid) > 1
                            or "X-Handoff-Artifact" in self.headers)
                    self._send(
                        200, b"frame:" + outer.name.encode() + body,
                        ctype="application/x-npy",
                        extra=[("X-Session-Id", sid),
                               ("X-Warm", "1" if warm else "0")])
                elif path == "/v1/disparity":
                    outer.stateless_headers.append(
                        {k: v for k, v in self.headers.items()})
                    self._send(
                        200, b"disp:" + outer.name.encode() + body,
                        ctype="application/x-npy",
                        extra=[("X-Batch-Size", "1"),
                               ("X-Iters-Used", "7")])
                else:
                    self._json(404, {"error": "no route"})

            def do_DELETE(self):
                path = urlparse(self.path).path
                outer.requests.append(("DELETE", self.path))
                if path.startswith("/v1/stream/"):
                    self._json(200, {"status": "closed", "frames": 0})
                else:
                    self._json(404, {"error": "no route"})

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.url = f"http://127.0.0.1:{self.server.server_address[1]}"
        self._thread = threading.Thread(target=self.server.serve_forever,
                                        daemon=True)
        self._thread.start()

    def kill(self):
        """Hard stop: connections start refusing (the router sees a dead
        replica)."""
        self.server.shutdown()
        self.server.server_close()


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.fixture()
def fleet3():
    stubs = [StubReplica(f"s{i}") for i in range(3)]
    router = FleetRouter(
        {s.name: s.url for s in stubs},
        RouterConfig(health_timeout_s=2.0, fail_after=1,
                     request_timeout_s=5.0, fleet_brownout=False))
    router.check_replicas()
    yield stubs, router
    for s in stubs:
        try:
            s.kill()
        except Exception:
            pass


# ----------------------------------------------------------- router core
def test_router_stateless_balances_and_counts(fleet3):
    stubs, router = fleet3
    assert router.fleet_status()["ready"] == 3
    for _ in range(9):
        status, headers, body = router.forward_stateless(
            "POST", "/v1/disparity", b"xyz", [])
        assert status == 200 and body.startswith(b"disp:s")
    hit = [len(s.requests) for s in stubs]
    assert sum(hit) == 9
    assert all(h > 0 for h in hit), \
        f"equal-load replicas should share round-robin traffic: {hit}"
    assert router.routed("stateless") == 9


def test_router_stateless_failover_zero_loss(fleet3):
    """A replica dying mid-traffic burns attempts, never requests: every
    stateless request still answers (inference is idempotent — the
    retry is safe), and the dead replica leaves the rotation."""
    stubs, router = fleet3
    stubs[0].kill()     # dies NOW; the router has not probed since
    ok = 0
    for i in range(30):
        status, _, body = router.forward_stateless(
            "POST", "/v1/disparity", f"req{i}".encode(), [])
        assert status == 200 and body.startswith(b"disp:s")
        ok += 1
    assert ok == 30, "zero stateless loss under replica death"
    assert router.failovers.value >= 1
    assert router.fleet_status()["ready"] == 2
    assert not router.replicas["s0"].alive


def test_router_sessions_sticky_then_lost_typed_then_reseed(fleet3):
    """The fleet-wide 410 contract: frames of one session always land on
    one replica; when that replica dies the session fails typed EXACTLY
    once, and the client's next frame reseeds cold on a survivor."""
    stubs, router = fleet3
    by_name = {s.name: s for s in stubs}
    sids = [f"cam-{i}" for i in range(12)]
    owner = {}
    for sid in sids:
        for _ in range(3):                      # three frames each
            status, headers, body = router.forward_session(
                sid, "POST", f"/v1/stream/{sid}", b"f", [])
            assert status == 200
        homes = {name for name, s in by_name.items()
                 if sid in s.sessions}
        assert len(homes) == 1, \
            f"session {sid} touched {homes}: stickiness broken"
        owner[sid] = homes.pop()
    victim_name = owner[sids[0]]
    lost_sids = [s for s in sids if owner[s] == victim_name]
    survivors = [s for s in sids if owner[s] != victim_name]
    by_name[victim_name].kill()
    # First frame after the death: transport failure -> typed loss.
    with pytest.raises(SessionLost) as e:
        router.forward_session(lost_sids[0], "POST",
                               f"/v1/stream/{lost_sids[0]}", b"f", [])
    assert e.value.replica == victim_name
    assert router.sessions_lost.value >= 1
    # Other sessions of the dead replica were tombstoned by the death:
    # their next frame fails typed WITHOUT another transport attempt.
    for sid in lost_sids[1:]:
        with pytest.raises(SessionLost):
            router.forward_session(sid, "POST", f"/v1/stream/{sid}",
                                   b"f", [])
    # Fire-once: the SAME ids now reseed cold on a surviving replica.
    for sid in lost_sids:
        status, _, _ = router.forward_session(
            sid, "POST", f"/v1/stream/{sid}", b"f", [])
        assert status == 200
        new_home = {n for n, s in by_name.items()
                    if n != victim_name and sid in s.sessions}
        assert len(new_home) == 1
    # Sessions on survivors never noticed.
    for sid in survivors:
        status, _, _ = router.forward_session(
            sid, "POST", f"/v1/stream/{sid}", b"f", [])
        assert status == 200


def test_router_remap_fraction_on_death_is_about_one_nth(fleet3):
    """Ring-level blast radius through the router: replica death loses
    ~1/3 of routed sessions, not all of them."""
    stubs, router = fleet3
    sids = [f"cam-{i}" for i in range(120)]
    for sid in sids:
        router.forward_session(sid, "POST", f"/v1/stream/{sid}", b"f", [])
    victim = stubs[1]
    owned = [sid for sid in sids if sid in victim.sessions]
    victim.kill()
    router.check_replicas()       # probe pass notices the death
    status = router.fleet_status()
    assert status["ready"] == 2
    assert status["sessions_pending_loss"] == len(owned)
    frac = len(owned) / len(sids)
    assert 0.15 < frac < 0.55


def test_router_health_blackhole_counts_as_dead(fleet3):
    """A replica whose /healthz stops answering (connection closed, no
    response) while its request path still works must leave the
    rotation: a zombie to the balancer is dead to the balancer."""
    stubs, router = fleet3
    stubs[2].blackhole_health = True
    router.check_replicas()       # fail_after=1 -> out immediately
    assert router.fleet_status()["ready"] == 2
    assert "s2" not in router.ring.members
    # recovery: probes answering again put it back
    stubs[2].blackhole_health = False
    router.check_replicas()
    assert router.fleet_status()["ready"] == 3


def test_router_not_ready_replica_out_of_rotation(fleet3):
    stubs, router = fleet3
    stubs[1].ready = False        # warming / draining: alive, not ready
    router.check_replicas()
    assert router.fleet_status()["ready"] == 2
    for _ in range(6):
        _, _, body = router.forward_stateless("POST", "/v1/disparity",
                                              b"x", [])
        assert not body.startswith(b"disp:s1")
    stubs[1].ready = True
    router.check_replicas()
    assert router.fleet_status()["ready"] == 3


def test_router_all_dead_typed_no_replicas(fleet3):
    stubs, router = fleet3
    for s in stubs:
        s.kill()
    for _ in range(2):
        router.check_replicas()
    with pytest.raises(NoReplicasAvailable):
        router.forward_stateless("POST", "/v1/disparity", b"x", [])
    assert router.unroutable.value >= 1


def test_router_brownout_propagates_fleet_wide():
    """Sustained AGGREGATE pressure pushes one brownout floor to every
    replica (lockstep degradation); sustained calm restores it."""
    stubs = [StubReplica(f"s{i}", queue_depth=60, queue_limit=64)
             for i in range(3)]
    clock = FakeClock()
    router = FleetRouter(
        {s.name: s.url for s in stubs},
        RouterConfig(health_timeout_s=2.0, fleet_brownout=True,
                     brownout_engage_s=0.5, brownout_restore_s=1.0,
                     brownout_max_level=2),
        clock=clock)
    try:
        router.check_replicas()          # pressure_since arms
        clock.t += 0.6
        router.check_replicas()          # sustained -> level 1, pushed
        assert router.brownout_level == 1
        for s in stubs:
            assert s.brownout_levels[-1:] == [1], \
                f"{s.name} never got the fleet floor: {s.brownout_levels}"
        clock.t += 0.6
        router.check_replicas()          # next rung needs its own window
        assert router.brownout_level == 2
        # calm: pressure gone, restore after the longer calm window
        for s in stubs:
            s.queue_depth = 0
        router.check_replicas()
        clock.t += 1.1
        router.check_replicas()
        assert router.brownout_level == 1
        assert all(s.brownout_levels[-1] == 1 for s in stubs)
    finally:
        for s in stubs:
            s.kill()


# ------------------------------------------------- drain handoff (round 18)
def _route_sessions(router, stubs, n=12):
    """Open n sessions through the router; returns {sid: owner_name}."""
    owner = {}
    by_name = {s.name: s for s in stubs}
    for i in range(n):
        sid = f"cam-{i}"
        router.forward_session(sid, "POST", f"/v1/stream/{sid}", b"f", [])
        owner[sid] = next(name for name, s in by_name.items()
                          if sid in s.sessions)
    return owner


def test_drain_handoff_remaps_sessions_zero_410(fleet3):
    """The round-18 acceptance shape at routing level: a replica that
    DRAINS (instead of dying) hands its sessions to survivors — zero
    SessionLost, every inherited frame tagged with the handoff
    artifact, and the tag consumed after the first 200."""
    stubs, router = fleet3
    owner = _route_sessions(router, stubs)
    victim = next(s for s in stubs
                  if any(o == s.name for o in owner.values()))
    moved = [sid for sid, o in owner.items() if o == victim.name]
    kept = [sid for sid, o in owner.items() if o != victim.name]
    victim.draining = True
    victim.handoff_manifest = {"artifact": "abc123", "sessions": moved,
                               "count": len(moved)}
    router.check_replicas()      # drain observed + manifest fetched
    st = router.fleet_status()
    assert st["ready"] == 2
    assert st["sessions_pending_loss"] == 0, \
        "a planned drain must not type its sessions lost"
    assert st["sessions_pending_handoff"] == len(moved)
    assert router.sessions_lost.value == 0
    # Every moved session's next frame: 200 on a survivor, tagged.
    for sid in moved:
        status, headers, body = router.forward_session(
            sid, "POST", f"/v1/stream/{sid}", b"f", [])
        assert status == 200
        assert not body.startswith(b"frame:" + victim.name.encode())
        tagged = [h for s2, h in
                  [e for st2 in stubs for e in st2.stream_headers]
                  if s2 == sid and "X-Handoff-Artifact" in h]
        assert tagged and tagged[-1]["X-Handoff-Artifact"] == "abc123"
    assert router.fleet_status()["sessions_pending_handoff"] == 0, \
        "the handoff tag is consumed by the first successful frame"
    # Second frame: no tag (the survivor owns the live state now).
    for sid in moved[:2]:
        status, _, _ = router.forward_session(
            sid, "POST", f"/v1/stream/{sid}", b"f", [])
        assert status == 200
    # Survivor-owned sessions never noticed.
    for sid in kept:
        status, _, _ = router.forward_session(
            sid, "POST", f"/v1/stream/{sid}", b"f", [])
        assert status == 200
    assert router.sessions_lost.value == 0
    assert router.handoff_sessions.value == len(moved)


def test_drain_503_race_rerouted_inline(fleet3):
    """A frame that reaches a draining replica BEFORE the router's next
    probe gets the typed draining 503 — the router must treat that as
    the drain signal, fetch the manifest, and retry the frame once on
    the inheriting replica.  Zero client-visible failures."""
    stubs, router = fleet3
    owner = _route_sessions(router, stubs, n=8)
    victim = next(s for s in stubs
                  if any(o == s.name for o in owner.values()))
    moved = [sid for sid, o in owner.items() if o == victim.name]
    # Drain flips WITHOUT a probe pass: the router still routes there.
    victim.draining = True
    victim.handoff_manifest = {"artifact": "race-key",
                               "sessions": moved, "count": len(moved)}
    sid = moved[0]
    status, headers, body = router.forward_session(
        sid, "POST", f"/v1/stream/{sid}", b"f", [])
    assert status == 200, "the race must be absorbed, not surfaced"
    assert not body.startswith(b"frame:" + victim.name.encode())
    assert router.sessions_lost.value == 0
    assert victim.name not in router.ring.members


def test_drain_without_manifest_falls_back_to_typed_loss(fleet3):
    """A drain that never publishes (crash mid-drain, pre-r18 replica)
    keeps the r16 contract: when the process goes away its sessions
    fail typed, exactly once."""
    stubs, router = fleet3
    owner = _route_sessions(router, stubs, n=8)
    victim = next(s for s in stubs
                  if any(o == s.name for o in owner.values()))
    moved = [sid for sid, o in owner.items() if o == victim.name]
    victim.draining = True       # manifest stays 404
    router.check_replicas()
    assert router.fleet_status()["sessions_pending_loss"] == 0
    victim.kill()                # dies before ever publishing
    router.check_replicas()
    router.check_replicas()
    assert router.fleet_status()["sessions_pending_loss"] == len(moved)
    with pytest.raises(SessionLost):
        router.forward_session(moved[0], "POST",
                               f"/v1/stream/{moved[0]}", b"f", [])
    status, _, _ = router.forward_session(       # fire-once: reseeds
        moved[0], "POST", f"/v1/stream/{moved[0]}", b"f", [])
    assert status == 200


def test_lost_ledger_bounded_by_cap_and_gauge():
    """Satellite: the lost-session ledger is capacity-capped like the
    SessionStore tombstones, with fleet_lost_ledger_size live."""
    stubs = [StubReplica(f"s{i}") for i in range(2)]
    router = FleetRouter(
        {s.name: s.url for s in stubs},
        RouterConfig(health_timeout_s=2.0, fail_after=1,
                     request_timeout_s=5.0, fleet_brownout=False,
                     session_lost_cap=5))
    try:
        router.check_replicas()
        owner = _route_sessions(router, stubs, n=20)
        victim = next(s for s in stubs
                      if sum(1 for o in owner.values()
                             if o == s.name) > 5)
        n_owned = sum(1 for o in owner.values() if o == victim.name)
        victim.kill()
        router.check_replicas()
        st = router.fleet_status()
        assert n_owned > 5
        assert st["sessions_pending_loss"] == 5, \
            "the cap must forget the oldest owed 410s"
        assert router.lost_ledger_size.value == 5
        # firing one decrements the gauge
        fired = [sid for sid, o in owner.items()
                 if o == victim.name][-1]
        with pytest.raises(SessionLost):
            router.forward_session(fired, "POST",
                                   f"/v1/stream/{fired}", b"f", [])
        assert router.lost_ledger_size.value == 4
    finally:
        for s in stubs:
            try:
                s.kill()
            except Exception:
                pass


# --------------------------------------------------- xl-capability routing
def test_xl_routing_heterogeneous_fleet():
    """``?tier=xl`` requests land only on replicas advertising the mesh
    tier; plain requests still balance over everyone; a fleet whose xl
    replicas all left rotation answers the typed xl_unavailable."""
    from raft_stereo_tpu.serving.fleet import XlUnavailable

    xl_topo = {"mesh": "rows=4", "label": "rows4", "groups": 1,
               "devices_per_group": 4, "threshold_pixels": 2_000_000,
               "batch_sizes": [1]}
    stubs = [StubReplica("plain0"), StubReplica("plain1"),
             StubReplica("big0", xl=xl_topo)]
    router = FleetRouter(
        {s.name: s.url for s in stubs},
        RouterConfig(health_timeout_s=2.0, fail_after=1,
                     request_timeout_s=5.0, fleet_brownout=False))
    try:
        router.check_replicas()
        for _ in range(6):
            status, _, body = router.forward_stateless(
                "POST", "/v1/disparity?tier=xl", b"big", [])
            assert status == 200 and body.startswith(b"disp:big0"), \
                "xl requests must route to the xl-capable replica"
        # the X-Tier header spelling routes identically
        status, _, body = router.forward_stateless(
            "POST", "/v1/disparity", b"big", [("X-Tier", "xl")])
        assert body.startswith(b"disp:big0")
        # non-xl traffic is unconstrained
        hit = set()
        for _ in range(12):
            _, _, body = router.forward_stateless(
                "POST", "/v1/disparity", b"x", [])
            hit.add(body.split(b":")[1][:6])
        assert len(hit) > 1
        # xl replica leaves rotation -> typed 503 with the counts
        stubs[2].kill()
        router.check_replicas()
        with pytest.raises(XlUnavailable) as e:
            router.forward_stateless("POST", "/v1/disparity?tier=xl",
                                     b"big", [])
        assert e.value.capable_ready == 0
        assert router.xl_unroutable.value >= 1
        # plain traffic still flows
        status, _, _ = router.forward_stateless("POST", "/v1/disparity",
                                                b"x", [])
        assert status == 200
    finally:
        for s in stubs:
            try:
                s.kill()
            except Exception:
                pass


def test_xl_unavailable_typed_over_http(fleet3):
    stubs, router = fleet3          # nobody advertises xl
    server = RouterHTTPServer(router, port=0).start()
    try:
        status, headers, body = _post(
            f"{server.url}/v1/disparity?tier=xl", b"big")
        assert status == 503
        err = json.loads(body)
        assert err["error"] == "xl_unavailable"
        assert err["capable_replicas"] == 0
        assert "Retry-After" in headers
        assert 0.5 <= err["retry_after_s"] <= 1.5
    finally:
        server.shutdown()


# --------------------------------------------------------- HA ledger + pair
def test_ledger_fencing_rejects_stale_writer(tmp_path):
    from raft_stereo_tpu.serving.fleet import FleetLedger

    a = FleetLedger(str(tmp_path), "rt-a")
    b = FleetLedger(str(tmp_path), "rt-b")
    assert a.acquire() == 1
    assert a.append("lost", sids=["s1"], replica="r0")
    assert b.acquire() == 2, "takeover bumps the fencing epoch"
    assert b.append("fired", sid="s1")
    # the stale writer's appends are REJECTED, not interleaved
    assert a.append("fired", sid="s2") is False
    assert a.rejected_appends == 1
    assert not a.active, "a fenced writer knows it lost the lease"
    kinds = [r["kind"] for r in b.replay()]
    assert kinds == ["lost", "fired"], \
        "the stale append must not have reached the ledger"
    # renew() on the fenced writer also reports the loss
    assert a.renew() is False
    assert b.renew() is True


def test_ledger_replay_skips_torn_tail(tmp_path):
    from raft_stereo_tpu.serving.fleet import FleetLedger

    a = FleetLedger(str(tmp_path), "rt-a")
    a.acquire()
    a.append("lost", sids=["x"], replica="r0")
    with open(a._ledger_path, "a") as f:
        f.write('{"kind": "lost", "sids": ["torn...')   # torn tail
    assert [r["kind"] for r in a.replay()] == ["lost"]


def test_ledger_lease_staleness(tmp_path):
    from raft_stereo_tpu.serving.fleet import FleetLedger

    clock = FakeClock(t=100.0)
    a = FleetLedger(str(tmp_path), "rt-a", clock=clock)
    b = FleetLedger(str(tmp_path), "rt-b", clock=clock)
    a.acquire()
    assert not b.is_stale(3.0)
    clock.t += 5.0
    assert b.is_stale(3.0), "an unrenewed lease goes stale"
    a.renew()
    assert not b.is_stale(3.0)
    assert not a.is_stale(3.0), "the holder never sees itself stale"


def test_ha_takeover_never_double_fires_a_loss(tmp_path):
    """The acceptance pin: a loss FIRED by the primary is never fired
    again by the standby after takeover (the ledger's fired record
    survives the router's death); a loss OWED but not yet delivered
    re-arms and fires exactly once on the standby."""
    stubs = [StubReplica(f"s{i}") for i in range(3)]
    ha = str(tmp_path)
    cfg = dict(health_timeout_s=2.0, fail_after=1,
               request_timeout_s=5.0, fleet_brownout=False)
    primary = FleetRouter({s.name: s.url for s in stubs},
                          RouterConfig(ha_dir=ha, router_name="rt-a",
                                       **cfg))
    standby = None
    try:
        assert primary.active and primary.ledger.epoch == 1
        primary.check_replicas()
        owner = _route_sessions(primary, stubs, n=10)
        victim = next(s for s in stubs
                      if sum(1 for o in owner.values()
                             if o == s.name) >= 2)
        lost = [sid for sid, o in owner.items() if o == victim.name]
        victim.kill()
        primary.check_replicas()
        # primary delivers ONE of the owed 410s, then "dies"
        with pytest.raises(SessionLost):
            primary.forward_session(lost[0], "POST",
                                    f"/v1/stream/{lost[0]}", b"f", [])
        standby = FleetRouter({s.name: s.url for s in stubs},
                              RouterConfig(ha_dir=ha,
                                           router_name="rt-b",
                                           standby=True, **cfg))
        assert not standby.active
        standby.check_replicas()
        standby.takeover()
        assert standby.active and standby.ledger.epoch == 2
        # the fired id must NOT fire again: it reseeds cold instead
        status, _, _ = standby.forward_session(
            lost[0], "POST", f"/v1/stream/{lost[0]}", b"f", [])
        assert status == 200, \
            "a 410 already delivered must never fire twice for one id"
        # an owed-but-undelivered id fires exactly once on the standby
        with pytest.raises(SessionLost):
            standby.forward_session(lost[1], "POST",
                                    f"/v1/stream/{lost[1]}", b"f", [])
        status, _, _ = standby.forward_session(
            lost[1], "POST", f"/v1/stream/{lost[1]}", b"f", [])
        assert status == 200
        # the fenced ex-primary can no longer append
        assert primary._ledger_append("fired", sid="zzz") is False
        assert not primary.active, "fencing demotes the stale primary"
    finally:
        primary.stop()
        if standby is not None:
            standby.stop()
        for s in stubs:
            try:
                s.kill()
            except Exception:
                pass


def test_ha_standby_serves_while_passive(tmp_path):
    """The standby forwards traffic the whole time (stateless balancing
    and ring-sticky sessions need no shared state) — only ledger writes
    wait for the lease."""
    stubs = [StubReplica(f"s{i}") for i in range(2)]
    cfg = dict(health_timeout_s=2.0, fail_after=1,
               request_timeout_s=5.0, fleet_brownout=False)
    primary = FleetRouter({s.name: s.url for s in stubs},
                          RouterConfig(ha_dir=str(tmp_path),
                                       router_name="rt-a", **cfg))
    standby = FleetRouter({s.name: s.url for s in stubs},
                          RouterConfig(ha_dir=str(tmp_path),
                                       router_name="rt-b",
                                       standby=True, **cfg))
    try:
        primary.check_replicas()
        standby.check_replicas()
        assert standby.fleet_status()["role"] == "standby"
        status, _, _ = standby.forward_stateless(
            "POST", "/v1/disparity", b"x", [])
        assert status == 200
        # both routers agree on session placement (deterministic ring)
        for sid in ("cam-a", "cam-b", "cam-c"):
            assert (primary.ring.lookup(sid)
                    == standby.ring.lookup(sid))
    finally:
        primary.stop()
        standby.stop()
        for s in stubs:
            try:
                s.kill()
            except Exception:
                pass


# ------------------------------------------------------------- autoscaler
class RecordingLauncher:
    """Scripted ReplicaLauncher: launches are stub replicas, drains are
    recorded and complete on demand — never a kill."""

    def __init__(self):
        self.stubs = {}
        self.drained = []
        self.killed = []
        self.exited = {}

    def launch(self, name):
        stub = StubReplica(name)
        self.stubs[name] = stub
        return stub.url

    def drain(self, name):
        self.drained.append(name)
        stub = self.stubs.get(name)
        if stub is not None:
            stub.draining = True
            stub.handoff_manifest = {"artifact": None, "sessions": [],
                                     "count": 0}

    def finish_drain(self, name):
        self.exited[name] = 0
        stub = self.stubs.get(name)
        if stub is not None:
            stub.kill()

    def poll(self, name):
        return self.exited.get(name)

    def destroy(self, name):
        self.killed.append(name)
        stub = self.stubs.pop(name, None)
        if stub is not None:
            try:
                stub.kill()
            except Exception:
                pass

    def cleanup(self):
        for name in list(self.stubs):
            self.destroy(name)


def _autoscaler(router, launcher, clock, trace):
    from raft_stereo_tpu.serving.fleet import AutoscaleConfig, Autoscaler

    it = iter(trace)

    def pressure():
        try:
            return next(it)
        except StopIteration:
            return trace[-1]

    return Autoscaler(
        router, launcher,
        AutoscaleConfig(min_replicas=1, max_replicas=3,
                        engage_fraction=0.6, engage_s=1.0,
                        restore_fraction=0.15, restore_s=2.0,
                        cooldown_s=0.5),
        clock=clock, pressure_fn=pressure)


def test_autoscaler_hysteresis_on_scripted_trace():
    """Satellite: engage needs SUSTAINED pressure, the dead band holds
    (no flapping), restore needs longer sustained calm, and scale-down
    always DRAINS the launched replica."""
    base = StubReplica("base0")
    router = FleetRouter(
        {"base0": base.url},
        RouterConfig(health_timeout_s=2.0, fail_after=1,
                     request_timeout_s=5.0, fleet_brownout=False))
    launcher = RecordingLauncher()
    clock = FakeClock(t=0.0)
    # scripted pressure: spike (not sustained) -> calm -> sustained
    # spike -> dead band -> sustained calm
    trace = [0.9, 0.1,                 # blip: must NOT scale
             0.9, 0.9, 0.9,           # sustained: scale up once
             0.4, 0.4,                # dead band: hold
             0.05, 0.05, 0.05, 0.05, 0.05, 0.05]   # calm: scale down
    try:
        router.check_replicas()
        scaler = _autoscaler(router, launcher, clock, trace)
        actions = []
        for _ in range(len(trace)):
            actions.append(scaler.check())
            clock.t += 0.6
        assert actions.count("up") == 1, f"flapped: {actions}"
        assert actions.count("down") == 1, f"flapped: {actions}"
        assert actions[0] is None and actions[1] is None, \
            "a one-poll blip must not scale (engage_s hysteresis)"
        up_i = actions.index("up")
        down_i = actions.index("down")
        assert up_i < down_i
        assert launcher.drained == ["auto1"], \
            "scale-down must DRAIN the launched replica"
        assert launcher.killed == [], "scale-down must never kill"
        assert "auto1" in router.replicas, \
            "deregistration waits for the drain to finish"
        # drain completes -> reaped on the next check
        launcher.finish_drain("auto1")
        scaler.check()
        assert "auto1" not in router.replicas
        assert scaler.draining == []
        assert scaler.scale_ups.value == 1
        assert scaler.scale_downs.value == 1
    finally:
        launcher.cleanup()
        try:
            base.kill()
        except Exception:
            pass


def test_autoscaler_respects_bounds_and_cooldown():
    base = StubReplica("base0")
    router = FleetRouter(
        {"base0": base.url},
        RouterConfig(health_timeout_s=2.0, fail_after=1,
                     request_timeout_s=5.0, fleet_brownout=False))
    launcher = RecordingLauncher()
    clock = FakeClock(t=0.0)
    trace = [0.95] * 40
    try:
        router.check_replicas()
        scaler = _autoscaler(router, launcher, clock, trace)
        ups = 0
        for _ in range(40):
            if scaler.check() == "up":
                ups += 1
            clock.t += 0.4
        assert ups == 2, "max_replicas=3 bounds growth to +2"
        assert len(router.replicas) == 3
        # endless calm drains only what the autoscaler launched (the
        # base fleet stays; min_replicas is a floor, not a target)
        scaler._pressure_fn = lambda: 0.0
        downs = 0
        for _ in range(50):
            if scaler.check() == "down":
                downs += 1
            clock.t += 0.4
        assert downs == 2, "launched replicas only; base fleet stays"
        assert launcher.killed == []
    finally:
        launcher.cleanup()
        try:
            base.kill()
        except Exception:
            pass


# ---------------------------------------------------- router HTTP surface
def _get(url, timeout=5):
    req = urllib.request.Request(url)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def _post(url, data, headers=None, timeout=10):
    req = urllib.request.Request(url, data=data, method="POST",
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def test_router_http_surface_and_passthrough(fleet3):
    stubs, router = fleet3
    server = RouterHTTPServer(router, port=0).start()
    try:
        base = server.url
        status, _, body = _get(f"{base}/healthz")
        h = json.loads(body)
        assert status == 200 and h["ready_replicas"] == 3
        status, _, body = _get(f"{base}/readyz")
        assert status == 200 and json.loads(body)["ready"]
        status, _, body = _get(f"{base}/fleet")
        assert status == 200 and len(json.loads(body)["replicas"]) == 3
        status, _, body = _get(f"{base}/metrics")
        assert status == 200 and b"fleet_replicas_ready" in body
        status, _, _ = _get(f"{base}/nope")
        assert status == 404

        # Pass-through parity: same POST direct vs via router must be
        # byte-identical (body) with the same application headers.
        payload = b"\x00\x01stereo-pair-bytes\xff"
        d_status, d_headers, d_body = _post(
            f"{stubs[0].url}/v1/disparity?format=npy", payload,
            {"Content-Type": "application/x-npz"})
        # pin the router onto the same stub: kill the other two
        stubs[1].kill()
        stubs[2].kill()
        router.check_replicas()
        router.check_replicas()
        r_status, r_headers, r_body = _post(
            f"{base}/v1/disparity?format=npy", payload,
            {"Content-Type": "application/x-npz"})
        assert (r_status, r_body) == (d_status, d_body), \
            "router must be pass-through byte-identical"
        drop = {"server", "date"}
        assert ({k.lower(): v for k, v in d_headers.items()
                 if k.lower() not in drop}
                == {k.lower(): v for k, v in r_headers.items()
                    if k.lower() not in drop})

        # stream routing + typed fleet errors over HTTP
        status, headers, body = _post(f"{base}/v1/stream/cam-a", b"f")
        assert status == 200 and headers["X-Session-Id"] == "cam-a"
        stubs[0].kill()
        router.check_replicas()
        router.check_replicas()
        status, _, body = _post(f"{base}/v1/stream/cam-a", b"f")
        assert status == 410
        assert json.loads(body)["error"] == "session_lost"
        status, headers, body = _post(f"{base}/v1/disparity", b"x")
        assert status == 503
        err = json.loads(body)
        assert err["error"] == "no_replicas_ready"
        # r13 overload contract + jitter (round 18): the body carries a
        # precise jittered retry_after_s, the header its integer
        # ceiling — synchronized clients must not retry in lockstep.
        assert 0.5 <= err["retry_after_s"] <= 1.5
        assert headers["Retry-After"] in ("1", "2")
    finally:
        server.shutdown()


# ------------------------------------------------------ artifact store GC
def _fake_entry(cache, key, size, age_s):
    """Plant a fake .jaxexe entry with a controlled atime."""
    path = cache._path(key)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(b"x" * size)
    old = time.time() - age_s
    os.utime(path, (old, old))
    return path


def test_disk_cache_gc_evicts_lru_by_atime(tmp_path):
    from raft_stereo_tpu.serving.persist import ExecutableDiskCache

    class G:
        value = None

        def set(self, v):
            self.value = v

    gauge = G()
    cache = ExecutableDiskCache(str(tmp_path), max_bytes=2500,
                                bytes_gauge=gauge)
    keys = [f"{i:02x}" + "ab" * 31 for i in range(4)]   # 64-hex keys
    paths = [_fake_entry(cache, k, 1000, age_s=100 - 30 * i)
             for i, k in enumerate(keys)]               # [0] oldest
    assert cache.total_bytes() == 4000
    evicted = cache.gc()
    assert evicted == 2, "4000 -> 2500 budget needs the 2 oldest gone"
    assert not os.path.exists(paths[0]) and not os.path.exists(paths[1])
    assert os.path.exists(paths[2]) and os.path.exists(paths[3])
    assert gauge.value == 2000
    assert cache.stats()["evictions"] == 2


def test_disk_cache_gc_unbounded_only_updates_gauge(tmp_path):
    from raft_stereo_tpu.serving.persist import ExecutableDiskCache

    cache = ExecutableDiskCache(str(tmp_path))
    _fake_entry(cache, "cd" * 32, 512, age_s=10)
    assert cache.gc() == 0
    assert cache.total_bytes() == 512


def test_disk_cache_read_only_never_writes_or_evicts(tmp_path):
    from raft_stereo_tpu.serving.persist import ExecutableDiskCache

    seed = ExecutableDiskCache(str(tmp_path))
    p = _fake_entry(seed, "ef" * 32, 4000, age_s=10)
    ro = ExecutableDiskCache(str(tmp_path), max_bytes=100,
                             read_only=True)
    assert ro.store("ab" * 32, object()) is False
    assert ro.gc() == 0 and os.path.exists(p), \
        "a read-only replica must never mutate the shared store"


def test_disk_cache_corrupt_and_legacy_entries_degrade_to_miss(tmp_path):
    from raft_stereo_tpu.serving.persist import ExecutableDiskCache

    cache = ExecutableDiskCache(str(tmp_path))
    key = "12" * 32
    _fake_entry(cache, key, 64, age_s=1)        # garbage bytes, sharded
    assert cache.load(key) is None              # unpickleable -> miss
    legacy_key = "34" * 32
    with open(os.path.join(str(tmp_path),
                           f"{legacy_key}.jaxexe"), "wb") as f:
        f.write(b"garbage")                     # flat round-13 layout
    assert cache.load(legacy_key) is None       # found, corrupt -> miss
    assert cache.stats()["misses"] == 2
    assert cache.load("56" * 32) is None        # absent -> miss
    assert cache.stats()["misses"] == 3


# ------------------------------------------------------ replica chaos unit
def test_chaos_die_after_is_deterministic():
    from raft_stereo_tpu.serving.chaos import ChaosConfig, ChaosInjector

    exits = []
    inj = ChaosInjector(ChaosConfig(die_after_dispatches=3),
                        exit_fn=exits.append)
    inj.on_dispatch(0)
    inj.on_dispatch(0)
    assert exits == []
    inj.on_dispatch(0)
    assert exits == [137], "the Nth dispatch kills the process, kill -9 " \
                           "style (exit code 137)"
    inj.on_dispatch(0)
    assert exits == [137]       # fires once


def test_chaos_blackhole_and_slow_start_windows():
    from raft_stereo_tpu.serving.chaos import ChaosConfig, ChaosInjector

    clock = FakeClock(t=0.0)
    inj = ChaosInjector(
        ChaosConfig(healthz_blackhole_after_s=5.0, slow_start_s=2.0),
        clock=clock)
    assert inj.ready_blocked() and not inj.blackhole()
    clock.t = 2.5
    assert not inj.ready_blocked() and not inj.blackhole()
    clock.t = 5.5
    assert inj.blackhole()


def test_chaos_spec_parses_replica_level_keys():
    from raft_stereo_tpu.serving.chaos import parse_chaos_spec

    cfg = parse_chaos_spec("die_after=7,blackhole_after_s=3,"
                           "slow_start_s=1.5")
    assert cfg.die_after_dispatches == 7
    assert cfg.healthz_blackhole_after_s == 3.0
    assert cfg.slow_start_s == 1.5
    assert cfg.enabled


# --------------------------------------------- real engine: shutdown + http
@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    cfg = RaftStereoConfig(**TINY)
    model = RAFTStereo(cfg)
    dummy = jnp.zeros((1, 32, 48, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), dummy, dummy, iters=1,
                           test_mode=True)
    return cfg, variables


def _pair(hw=(48, 64), seed=3):
    rng = np.random.default_rng(seed)
    left = rng.integers(0, 255, hw + (3,), dtype=np.uint8)
    return left, np.roll(left, -3, axis=1)


def test_graceful_shutdown_flips_ready_and_drains(tiny_model):
    """Satellite: SIGTERM phase 1 (engine.begin_shutdown) flips the
    readiness gate (router out-of-rotation signal) and refuses new work
    typed, while already-admitted work still completes; drain() then
    finishes clean."""
    from raft_stereo_tpu.serving import (Overloaded, ServeConfig,
                                         StereoService)

    cfg, variables = tiny_model
    left, right = _pair()
    svc = StereoService(cfg, variables,
                        ServeConfig(max_batch=1, batch_sizes=(1,),
                                    iters=1))
    try:
        assert svc.ready                      # no warm surface declared
        svc.queue.pause()                     # hold the queue: work is
        fut = svc.submit(left, right)         # admitted, not dispatched
        svc.begin_shutdown()
        assert not svc.ready, \
            "/readyz must flip 503 the moment shutdown begins"
        assert svc.warm_status()["draining"]
        with pytest.raises(Overloaded) as e:
            svc.submit(left, right)
        assert e.value.draining
        svc.queue.resume()
        res = fut.result(timeout=300)         # admitted work still lands
        assert res.flow.shape == left.shape[:2]
        assert svc.drain(timeout=300)
    finally:
        svc.close()


def test_admin_brownout_endpoint_and_queue_limit(tiny_model):
    """POST /admin/brownout sets the fleet floor (requests degrade with
    no local pressure at all) and /healthz reports queue_limit — the
    signals the fleet router needs from every replica."""
    from raft_stereo_tpu.serving import ServeConfig, StereoService
    from raft_stereo_tpu.serving.http import StereoHTTPServer

    cfg, variables = tiny_model
    left, right = _pair()
    svc = StereoService(
        cfg, variables,
        ServeConfig(max_batch=1, batch_sizes=(1,), iters=1,
                    tiers=("interactive", "quality"),
                    default_tier="quality", brownout=True,
                    brownout_poll_s=5.0))   # poll too slow to interfere
    server = StereoHTTPServer(svc, port=0).start()
    try:
        status, _, body = _get(f"{server.url}/healthz")
        h = json.loads(body)
        assert status == 200 and h["queue_limit"] == 64
        status, _, body = _post(
            f"{server.url}/admin/brownout",
            json.dumps({"level": 1}).encode(),
            {"Content-Type": "application/json"})
        assert status == 200 and json.loads(body)["level"] == 1
        res = svc.infer(left, right, tier="quality", timeout=300)
        assert res.tier == "interactive" and res.degraded, \
            "the pushed floor must degrade with zero local pressure"
        status, _, body = _get(f"{server.url}/healthz")
        assert json.loads(body)["brownout_level"] == 1
        # restore
        status, _, body = _post(
            f"{server.url}/admin/brownout",
            json.dumps({"level": 0}).encode(),
            {"Content-Type": "application/json"})
        assert status == 200 and json.loads(body)["level"] == 0
        res = svc.infer(left, right, tier="quality", timeout=300)
        assert res.tier == "quality" and not res.degraded
        # malformed body
        status, _, body = _post(f"{server.url}/admin/brownout", b"{}",
                                {"Content-Type": "application/json"})
        assert status == 400
    finally:
        server.shutdown()
        svc.close()


def test_admin_brownout_unavailable_without_controller(tiny_model):
    from raft_stereo_tpu.serving import ServeConfig, StereoService
    from raft_stereo_tpu.serving.http import StereoHTTPServer

    cfg, variables = tiny_model
    svc = StereoService(cfg, variables,
                        ServeConfig(max_batch=1, batch_sizes=(1,),
                                    iters=1))
    server = StereoHTTPServer(svc, port=0).start()
    try:
        status, _, body = _post(
            f"{server.url}/admin/brownout",
            json.dumps({"level": 1}).encode(),
            {"Content-Type": "application/json"})
        assert status == 409
        assert json.loads(body)["error"] == "brownout_unavailable"
    finally:
        server.shutdown()
        svc.close()


def test_router_passthrough_byte_identical_real_engine(tiny_model):
    """ISSUE acceptance: with chaos off, hitting the fleet router is
    byte-identical to hitting the single replica directly — the bitwise
    solo-parity contract survives the routing layer."""
    from raft_stereo_tpu.serving import ServeConfig, StereoService
    from raft_stereo_tpu.serving.http import StereoHTTPServer

    cfg, variables = tiny_model
    left, right = _pair(seed=11)
    buf = io.BytesIO()
    np.savez(buf, left=left, right=right)
    payload = buf.getvalue()
    svc = StereoService(cfg, variables,
                        ServeConfig(max_batch=1, batch_sizes=(1,),
                                    iters=1))
    server = StereoHTTPServer(svc, port=0).start()
    router = FleetRouter({"r0": server.url},
                         RouterConfig(health_timeout_s=5.0,
                                      fleet_brownout=False))
    router.check_replicas()
    rserver = RouterHTTPServer(router, port=0).start()
    try:
        d_status, d_headers, d_body = _post(
            f"{server.url}/v1/disparity", payload,
            {"Content-Type": "application/x-npz"}, timeout=300)
        r_status, r_headers, r_body = _post(
            f"{rserver.url}/v1/disparity", payload,
            {"Content-Type": "application/x-npz"}, timeout=300)
        assert d_status == r_status == 200
        assert d_body == r_body, \
            "routed disparity bytes must equal the direct response"
        # Headers match apart from the per-request timing measurements
        # (two separate dispatches legitimately clock differently).
        drop = {"server", "date", "x-queue-wait-ms", "x-device-ms"}
        assert ({k.lower(): v for k, v in d_headers.items()
                 if k.lower() not in drop}
                == {k.lower(): v for k, v in r_headers.items()
                    if k.lower() not in drop})
        # the streaming path, routed: typed session headers intact
        s_status, s_headers, s_body = _post(
            f"{rserver.url}/v1/stream/cam-1", payload,
            {"Content-Type": "application/x-npz"}, timeout=300)
        assert s_status == 400     # engine runs without sessions: typed
        assert json.loads(s_body)["error"] == "sessions_disabled"
    finally:
        rserver.shutdown()
        router.stop()
        server.shutdown()
        svc.close()


# ------------------------------------------------- one process per chip
_ROUTER_PROBE = r"""
import sys, time
from raft_stereo_tpu.cli import route
from raft_stereo_tpu.serving.fleet import RouterHTTPServer

args = route.build_parser().parse_args([
    "--replica", "http://127.0.0.1:9", "--port", "0",
    "--health_poll_s", "0.05", "--health_timeout_s", "0.2",
    "--autoscale_cmd", sys.executable + " -c pass {name} {port}"])
router = route.build_router(args).start()
autoscaler = route.build_autoscaler(args, router)
server = RouterHTTPServer(router, host=args.host, port=args.port,
                          max_workers=args.http_workers)
time.sleep(0.3)                       # a few health polls
router.fleet_status()
router.stop()
bridge = sys.modules.get("jax._src.xla_bridge")
print("BACKEND_UP", bool(bridge is not None
                         and bridge.backends_are_initialized()))
"""


def test_router_process_stays_off_the_device():
    """The router (and its autoscaler) start ``raft-serve`` children that
    each need a chip; a chip belongs to one process at a time, so the
    router process itself must never initialise a jax backend — checked
    in a fresh interpreter that builds everything ``raft-route`` builds
    (this pytest process has long since initialised its CPU backend)."""
    import subprocess
    import sys

    out = subprocess.run([sys.executable, "-c", _ROUTER_PROBE],
                         capture_output=True, text=True, timeout=120,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "BACKEND_UP False" in out.stdout, out.stdout
