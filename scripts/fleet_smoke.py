#!/usr/bin/env python
"""CI fleet smoke: replicated serving end to end — compile farm, warm
replica boot, session-sticky routing, replica kill -9, typed session
loss, and graceful SIGTERM drain.  Hermetic on CPU.

The round-16 acceptance properties, proven on a REAL 3-replica fleet
(each replica a ``raft-serve`` subprocess) behind the in-process fleet
router:

1. **Warm fleet boot from the shared artifact store** —
   tools/compile_farm.py builds the full shape x batch x tier x family
   ladder ONCE; every replica then reaches ``/readyz`` with
   ``serve_compiles_cold_total == 0`` (readiness bounded by artifact
   fetch, not compilation).
2. **Router pass-through parity** — with chaos off, the routed
   ``/v1/disparity`` response is byte-identical to hitting a replica
   directly (the bitwise solo-parity contract survives the routing
   layer).
3. **Zero stateless loss under replica death** — one replica is
   SIGKILLed mid-traffic; every one of >= 60 stateless requests still
   answers 200 (transport failover + retry), and the router's
   degraded-capacity window (kill -> fleet marks it dead) is measured.
4. **Typed fleet-wide session loss + reseed** — the dead replica's
   streaming sessions fail 410 ``session_lost`` exactly once, then the
   same ids reseed COLD on a surviving replica; a session on a survivor
   streams on warm, untouched.
5. **Fleet brownout floor** — ``POST /admin/brownout`` on a live
   replica degrades a quality request with zero local pressure
   (X-Degraded), and resets cleanly.
6. **Graceful SIGTERM** — a replica with in-flight work drains: /readyz
   flips 503 (router out-of-rotation signal) while every admitted
   request still answers 200, then the process exits 0.

Round-18 legs (a SECOND fresh fleet + subprocess ``raft-route`` pair):

7. **Rolling restart with session handoff** — a replica holding live
   streams is SIGTERMed; every stream's next frame answers 200 with
   ZERO 410s and every handed-off stream's first post-drain frame
   dispatches on the WARM family (X-Warm: 1) on a survivor.
8. **Router kill -9 with standby takeover** — the primary ``raft-route``
   process is SIGKILLed mid-traffic; all 60/60 stateless requests
   answer (clients fail over to the standby URL), and the standby
   takes the ledger lease within the probe window.
9. **Autoscale up, drain down** — a load step pushes the aggregate
   pressure past the engage watermark, the autoscaler launches a
   replica (it boots warm from the store and joins rotation); the load
   stops, the scale-down DRAINS it via handoff, and zero typed session
   losses occur.

Round-23 observability legs (on the live 3-replica fleet):

10. **One trace id across the fleet** — a sampled routed request's
    ``X-Trace-Id`` appears in the router's span ring AND the owning
    replica's; the router's federated ``/debug/spans?trace=`` merges
    both processes into one timeline (the replica's ``serve.request``
    a child of the router's ``route.forward``).  ``/metrics/fleet``
    re-exposes every replica's series under a ``replica=`` label with
    one HELP/TYPE per family.  A forced SLO burn trips the watchdog
    into exactly ONE coordinated flight-recorder dump: router bundle +
    all three replicas' bundles, one manifest under the trigger trace
    id.

Writes ``bench_record`` JSON to FLEET_OUT (default FLEET_ci.json) and
the HA legs to FLEET_HA_OUT (default FLEET_HA_ci.json; CI uploads
both).  Exit 0 on success, non-zero with a diagnostic on any violation.

Run from the repo root:  JAX_PLATFORMS=cpu python scripts/fleet_smoke.py
"""

from __future__ import annotations

import io
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tests"))
sys.path.insert(0, os.path.join(_REPO, "tools"))

OUT = os.environ.get("FLEET_OUT", os.path.join(_REPO, "FLEET_ci.json"))
HA_OUT = os.environ.get("FLEET_HA_OUT",
                        os.path.join(_REPO, "FLEET_HA_ci.json"))

HW = (48, 64)
ITERS = 2
TIERS = "interactive,quality"
BATCH_SIZES = "1,2"
N_STATELESS = 60
KILL_AFTER = 20


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, dict(resp.headers), resp.read()


def _post(url, data, headers=None, timeout=300):
    req = urllib.request.Request(url, data=data, method="POST",
                                 headers=headers or {})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, dict(resp.headers), resp.read()


def _metric(metrics_text: str, name: str) -> float:
    hits = re.findall(rf"^{name}(?:{{[^}}]*}})?\s+([0-9.eE+-]+)$",
                      metrics_text, re.M)
    return sum(float(h) for h in hits)


def _npz_pair(seed=3):
    import numpy as np

    rng = np.random.default_rng(seed)
    left = rng.integers(0, 255, HW + (3,), dtype=np.uint8)
    right = np.roll(left, -3, axis=1)
    buf = io.BytesIO()
    np.savez(buf, left=left, right=right)
    return buf.getvalue()


class ReplicaProc:
    """One raft-serve subprocess + its log file."""

    def __init__(self, name: str, ckpt: str, store: str, workdir: str):
        self.name = name
        self.port = _free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(workdir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.t_spawn = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "raft_stereo_tpu.cli.serve",
             "--restore_ckpt", ckpt, "--host", "127.0.0.1",
             "--port", str(self.port),
             "--tiers", TIERS, "--default_tier", "quality",
             "--valid_iters", str(ITERS),
             "--batch_sizes", BATCH_SIZES, "--max_batch", "2",
             "--sessions", "--session_ttl_s", "600",
             "--brownout",
             "--warmup_shape", f"{HW[0]}x{HW[1]}",
             "--executable_cache_dir", store,
             # round 23: the coordinated fleet dump POSTs
             # /debug/flightrecorder on every replica (--watchdog is
             # what arms the recorder on the serve CLI)
             "--watchdog", "--flight_recorder_dir",
             os.path.join(workdir, f"fr-{name}"),
             "--drain_timeout_s", "60"],
            cwd=_REPO, env=env, stdout=self._log, stderr=self._log)
        self.ready_s = None
        self.cold_compiles = None
        self.warm_compiles = None

    def wait_ready(self, timeout=420.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"{self.name} exited rc={self.proc.returncode} before "
                    f"ready; log tail:\n{self.log_tail()}")
            try:
                status, _, _ = _get(f"{self.url}/readyz", timeout=5)
                if status == 200:
                    self.ready_s = time.perf_counter() - self.t_spawn
                    _, _, m = _get(f"{self.url}/metrics", timeout=5)
                    text = m.decode()
                    self.cold_compiles = _metric(
                        text, "serve_compiles_cold_total")
                    self.warm_compiles = _metric(
                        text, "serve_compiles_warm_total")
                    return
            except (urllib.error.URLError, urllib.error.HTTPError,
                    OSError):
                pass
            time.sleep(0.25)
        raise RuntimeError(f"{self.name} never became ready; log tail:\n"
                           f"{self.log_tail()}")

    def log_tail(self, n=4000):
        self._log.flush()
        try:
            with open(self.log_path, "rb") as f:
                data = f.read()
            return data[-n:].decode(errors="replace")
        except OSError:
            return "<no log>"

    def kill9(self):
        os.kill(self.proc.pid, signal.SIGKILL)
        self.proc.wait(timeout=30)

    def terminate(self):
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)

    def cleanup(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        self._log.close()


class RouterProc:
    """One raft-route subprocess (the HA legs need REAL router
    processes so kill -9 means kill -9)."""

    def __init__(self, name: str, workdir: str, replicas: dict,
                 ha_dir=None, standby=False, peer=None):
        self.name = name
        self.port = _free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(workdir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        argv = [sys.executable, "-m", "raft_stereo_tpu.cli.route",
                "--host", "127.0.0.1", "--port", str(self.port),
                "--name", name, "--health_poll_s", "0.2",
                "--fail_after", "2", "--request_timeout_s", "300",
                "--no-fleet_brownout", "--lease_ttl_s", "2.0"]
        for rname, url in replicas.items():
            argv += ["--replica", f"{rname}={url}"]
        if ha_dir:
            argv += ["--ha_dir", ha_dir]
        if standby:
            argv += ["--standby"]
        if peer:
            argv += ["--peer", peer]
        self.proc = subprocess.Popen(
            argv, cwd=_REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdout=self._log, stderr=self._log)

    def wait_ready(self, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"router {self.name} exited rc="
                    f"{self.proc.returncode}; log:\n{self.log_tail()}")
            try:
                if _get(f"{self.url}/readyz", timeout=5)[0] == 200:
                    return
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.1)
        raise RuntimeError(f"router {self.name} never ready; log:\n"
                           f"{self.log_tail()}")

    def role(self):
        try:
            return json.loads(_get(f"{self.url}/healthz",
                                   timeout=5)[2])["role"]
        except (urllib.error.URLError, OSError, ValueError, KeyError):
            return None

    def log_tail(self, n=4000):
        self._log.flush()
        try:
            with open(self.log_path, "rb") as f:
                return f.read()[-n:].decode(errors="replace")
        except OSError:
            return "<no log>"

    def kill9(self):
        os.kill(self.proc.pid, signal.SIGKILL)
        self.proc.wait(timeout=30)

    def cleanup(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        self._log.close()


def _post_failover(urls, path, data, headers):
    """POST trying each router URL in order — the client side of an HA
    pair (a VIP/LB in production, explicit failover here)."""
    last = None
    for url in urls:
        try:
            return _post(f"{url}{path}", data, headers)
        except (ConnectionError, urllib.error.URLError, OSError) as e:
            if isinstance(e, urllib.error.HTTPError):
                raise           # an HTTP answer is an answer
            last = e
    raise last


def ha_phase(ckpt: str, store: str, workdir: str, payload: bytes,
             d_body: bytes) -> dict:
    """Round-18 legs on a fresh fleet: rolling-restart handoff, router
    kill -9 with standby takeover, autoscale up/drain down."""
    from raft_stereo_tpu.serving.fleet import (AutoscaleConfig, Autoscaler,
                                               FleetRouter,
                                               LocalProcessLauncher,
                                               RouterConfig,
                                               serve_argv_template)

    record = {}
    replicas = []
    routers = []
    launcher = None
    router_c = None
    try:
        # ---- fresh 3-replica fleet + subprocess router pair ----------
        replicas = [ReplicaProc(f"h{i}", ckpt, store, workdir)
                    for i in range(3)]
        for r in replicas:
            r.wait_ready()
        rep_map = {r.name: r.url for r in replicas}
        ha_dir = os.path.join(store, "fleet")
        primary = RouterProc("rt-a", workdir, rep_map, ha_dir=ha_dir)
        primary.wait_ready()
        standby = RouterProc("rt-b", workdir, rep_map, ha_dir=ha_dir,
                             standby=True, peer=primary.url)
        standby.wait_ready()
        routers = [primary, standby]
        urls = [primary.url, standby.url]
        assert primary.role() == "primary" and standby.role() == "standby"

        # ---- leg 8: rolling restart with handoff ---------------------
        sids = [f"ha-cam-{i}" for i in range(6)]
        for sid in sids:
            for _ in range(2):
                status, headers, _ = _post_failover(
                    urls, f"/v1/stream/{sid}?tier=quality", payload,
                    {"Content-Type": "application/x-npz"})
                assert status == 200
        # ownership from the deterministic ring (both routers agree)
        from raft_stereo_tpu.serving.fleet import HashRing
        ring = HashRing(sorted(rep_map))
        owner = {sid: ring.lookup(sid) for sid in sids}
        victim = next(r for r in replicas
                      if any(o == r.name for o in owner.values()))
        moved = [s for s in sids if owner[s] == victim.name]
        print(f"[fleet_smoke] HA fleet up; rolling-restarting "
              f"{victim.name} with {len(moved)} live stream(s)",
              flush=True)
        victim.terminate()          # SIGTERM: the PLANNED restart
        status_410 = 0
        warm_first = 0
        results = {}
        for sid in sids:            # every stream's next frame, NOW —
            try:                    # racing the drain on purpose
                status, headers, _ = _post_failover(
                    urls, f"/v1/stream/{sid}?tier=quality", payload,
                    {"Content-Type": "application/x-npz"})
            except urllib.error.HTTPError as e:
                if e.code == 410:
                    status_410 += 1
                    continue
                raise
            results[sid] = headers
            if sid in moved and headers.get("X-Warm") == "1":
                warm_first += 1
        victim.proc.wait(timeout=120)
        assert status_410 == 0, (
            f"a rolling restart produced {status_410} typed 410(s) — "
            f"handoff must make planned drains zero-loss "
            f"(victim log:\n{victim.log_tail()})")
        assert len(results) == len(sids)
        assert warm_first == len(moved), (
            f"only {warm_first}/{len(moved)} handed-off streams "
            f"dispatched WARM on their first post-drain frame "
            f"(victim log:\n{victim.log_tail()})")
        assert victim.proc.returncode == 0
        print(f"[fleet_smoke] rolling restart: 0x410, {warm_first}/"
              f"{len(moved)} handed-off streams warm on frame 1",
              flush=True)
        record["rolling_restart"] = {
            "streams": len(sids), "moved": len(moved),
            "typed_410": 0, "warm_first_frames": warm_first,
            "drain_exit_code": victim.proc.returncode}

        # ---- leg 9: router kill -9, standby takeover -----------------
        answered = 0
        t_kill = None
        for i in range(N_STATELESS):
            if i == KILL_AFTER:
                t_kill = time.monotonic()
                primary.kill9()
            status, _, body = _post_failover(
                urls, "/v1/disparity", payload,
                {"Content-Type": "application/x-npz"})
            assert status == 200 and body == d_body, \
                f"stateless request {i} failed across the router kill"
            answered += 1
        takeover_deadline = time.monotonic() + 15
        while (standby.role() != "primary"
               and time.monotonic() < takeover_deadline):
            time.sleep(0.1)
        takeover_s = time.monotonic() - t_kill
        assert standby.role() == "primary", (
            f"standby never took over; log:\n{standby.log_tail()}")
        print(f"[fleet_smoke] router kill -9: {answered}/"
              f"{N_STATELESS} stateless answered, takeover in "
              f"{takeover_s:.1f}s", flush=True)
        record["router_kill"] = {
            "stateless_sent": N_STATELESS,
            "stateless_answered": answered,
            "takeover_s": round(takeover_s, 2)}
        for r in replicas:
            r.terminate()

        # ---- leg 10: autoscale up under load, drain down -------------
        launcher = LocalProcessLauncher(
            serve_argv_template(
                f"python -m raft_stereo_tpu.cli.serve "
                f"--restore_ckpt {ckpt} --host 127.0.0.1 "
                f"--port {{port}} --tiers {TIERS} "
                f"--default_tier quality --valid_iters {ITERS} "
                f"--batch_sizes {BATCH_SIZES} --max_batch 2 "
                f"--max_queue 4 --sessions --session_ttl_s 600 "
                f"--warmup_shape {HW[0]}x{HW[1]} "
                f"--executable_cache_dir {store} "
                f"--drain_timeout_s 60"),
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            log_dir=workdir)
        base_url = launcher.launch("base0")
        router_c = FleetRouter(
            {"base0": base_url},
            RouterConfig(health_poll_s=0.2, health_timeout_s=2.0,
                         fail_after=3, request_timeout_s=300.0,
                         fleet_brownout=False)).start()
        scaler = Autoscaler(
            router_c, launcher,
            AutoscaleConfig(min_replicas=1, max_replicas=2,
                            engage_fraction=0.25, engage_s=0.4,
                            restore_fraction=0.12, restore_s=1.0,
                            cooldown_s=1.0))
        deadline = time.monotonic() + 180
        while (router_c.fleet_status()["ready"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.2)
        assert router_c.fleet_status()["ready"] == 1

        stop_load = threading.Event()
        load_errors = []

        def _hammer():
            while not stop_load.is_set():
                try:
                    router_c.forward_stateless(
                        "POST", "/v1/disparity", payload,
                        [("Content-Type", "application/x-npz")])
                except Exception as e:  # noqa: BLE001 — shed = fine
                    load_errors.append(type(e).__name__)

        threads = [threading.Thread(target=_hammer, daemon=True)
                   for _ in range(8)]
        t_load = time.monotonic()
        for t in threads:
            t.start()
        scaled = None
        while scaled != "up" and time.monotonic() - t_load < 60:
            scaled = scaler.check()
            time.sleep(0.1)
        assert scaled == "up", (
            "load step never engaged the autoscaler (pressure "
            f"{router_c.fleet_pressure()})")
        t_up = time.monotonic() - t_load
        # the new replica boots WARM from the store and joins rotation
        deadline = time.monotonic() + 180
        while (router_c.fleet_status()["ready"] < 2
               and time.monotonic() < deadline):
            scaler.check()
            time.sleep(0.2)
        assert router_c.fleet_status()["ready"] == 2, \
            "the scaled-up replica never joined rotation"
        print(f"[fleet_smoke] autoscale UP in {t_up:.1f}s after load "
              f"step; fleet at 2 replicas", flush=True)
        # live streams, so scale-down has warmth to hand off (retry
        # through the load: a 429 shed is a typed answer, not a frame)
        scale_sids = [f"as-cam-{i}" for i in range(4)]
        for sid in scale_sids:
            ok, t0 = 0, time.monotonic()
            while ok < 2 and time.monotonic() - t0 < 120:
                status, _, _ = router_c.forward_session(
                    sid, "POST", f"/v1/stream/{sid}?tier=quality",
                    payload, [("Content-Type", "application/x-npz")])
                if status == 200:
                    ok += 1
                else:
                    time.sleep(0.1)
            assert ok == 2, f"session {sid} never got 2 frames through"
        stop_load.set()
        for t in threads:
            t.join(timeout=30)
        t_calm = time.monotonic()
        action = None
        while action != "down" and time.monotonic() - t_calm < 120:
            action = scaler.check()
            time.sleep(0.1)
        assert action == "down", (
            f"pressure drop never restored (pressure "
            f"{router_c.fleet_pressure()})")
        deadline = time.monotonic() + 180
        while scaler.draining and time.monotonic() < deadline:
            scaler.check()
            time.sleep(0.2)
        assert not scaler.draining, "drained replica never reaped"
        assert len(router_c.replicas) == 1
        # THE acceptance line: the scripted pressure drop produced
        # zero typed session losses — scale-down drained, never killed
        assert router_c.sessions_lost.value == 0, \
            "autoscale scale-down must hand sessions off, not 410 them"
        frames_after = 0
        for sid in scale_sids:
            status, headers, _ = router_c.forward_session(
                sid, "POST", f"/v1/stream/{sid}?tier=quality",
                payload, [("Content-Type", "application/x-npz")])
            assert status == 200
            frames_after += 1
        print(f"[fleet_smoke] autoscale DOWN drained cleanly: 0 typed "
              f"losses, {frames_after}/{len(scale_sids)} streams "
              f"continued", flush=True)
        record["autoscale"] = {
            "scale_up_s": round(t_up, 1),
            "scale_ups": scaler.scale_ups.value,
            "scale_downs": scaler.scale_downs.value,
            "typed_session_losses": router_c.sessions_lost.value,
            "streams_continued": frames_after,
            "load_shed_errors": len(load_errors)}
        return record
    finally:
        if router_c is not None:
            router_c.stop()
        if launcher is not None:
            launcher.stop_all()
        for rt in routers:
            print(f"---- {rt.name} log tail ----\n{rt.log_tail()}",
                  file=sys.stderr)
            rt.cleanup()
        for r in replicas:
            r.cleanup()


def observability_phase(replicas, workdir: str, payload: bytes) -> dict:
    """Round-23 acceptance leg, on the live 3-replica fleet:

    * one sampled request's trace id appears in BOTH the router's span
      ring and the owning replica's, and the router's federated
      ``/debug/spans?trace=`` merges them into one timeline;
    * ``/metrics/fleet`` re-exposes every replica's series under a
      ``replica=`` label behind one scrape;
    * a forced SLO burn trips the watchdog into ONE coordinated
      flight-recorder dump with a bundle from the router and every
      replica, linked by the trigger trace id."""
    from raft_stereo_tpu.serving.fleet import (FleetRouter, RouterConfig,
                                               RouterHTTPServer)

    record = {}
    fr_dir = os.path.join(workdir, "fleet-recorder")
    router = FleetRouter(
        {r.name: r.url for r in replicas},
        RouterConfig(health_poll_s=0.2, health_timeout_s=2.0,
                     fail_after=2, request_timeout_s=300.0,
                     fleet_brownout=False, trace_sample_rate=1.0,
                     slo_ms=120_000.0,
                     flight_recorder_dir=fr_dir)).start()
    rserver = RouterHTTPServer(router, port=0).start()
    try:
        base = rserver.url
        router.slo_tick()               # baseline burn-rate snapshot

        # -- one trace id, two processes, one merged timeline ----------
        status, headers, _ = _post(
            f"{base}/v1/disparity", payload,
            {"Content-Type": "application/x-npz"})
        assert status == 200
        tid = headers.get("X-Trace-Id")
        assert tid, "sampled routed request must echo X-Trace-Id"
        owners = []
        for r in replicas:
            _, _, b = _get(f"{r.url}/debug/spans?trace={tid}")
            if any(s["name"] == "serve.request"
                   for s in json.loads(b)["spans"]):
                owners.append(r.name)
        assert len(owners) == 1, (
            f"exactly one replica must hold the server half: {owners}")
        _, _, b = _get(f"{base}/debug/spans?trace={tid}")
        view = json.loads(b)
        procs = {s["process"] for s in view["spans"]}
        assert procs == {"router", owners[0]}, procs
        names = {s["name"] for s in view["spans"]}
        assert {"route.request", "route.forward",
                "serve.request"} <= names, names
        serve_root = next(s for s in view["spans"]
                          if s["name"] == "serve.request")
        fwd_ids = {s["span_id"] for s in view["spans"]
                   if s["name"] == "route.forward"}
        assert serve_root["parent_id"] in fwd_ids, (
            "the replica subtree must stitch under the router's "
            "forward span")
        record["trace"] = {"trace_id": tid, "owner": owners[0],
                           "merged_spans": len(view["spans"])}
        print(f"[fleet_smoke] trace {tid}: one id across router + "
              f"{owners[0]}, {len(view['spans'])}-span merged "
              f"timeline: OK", flush=True)

        # -- metrics federation: one scrape, every replica labelled ----
        router.federator.scrape_once()
        _, _, b = _get(f"{base}/metrics/fleet")
        text = b.decode()
        for r in replicas:
            assert (f'fleet_federation_up{{replica="{r.name}"}} 1'
                    in text), f"{r.name} missing from federation"
            assert re.search(
                rf'serve_requests_admitted_total{{replica="{r.name}"',
                text), f"{r.name} series not re-exposed"
        assert text.count("# HELP serve_requests_admitted_total") == 1, \
            "duplicate families must merge under one header"
        n_series = sum(1 for ln in text.splitlines()
                       if ln and not ln.startswith("#"))
        record["federation"] = {"replicas": len(replicas),
                                "series": n_series}
        print(f"[fleet_smoke] /metrics/fleet: {len(replicas)} replicas "
              f"federated, {n_series} series, one HELP per family: OK",
              flush=True)

        # -- forced SLO burn -> coordinated fleet dump -----------------
        for _ in range(64):
            router.slo_errors.inc()     # synthesized routed failures
        burns = router.slo_tick()
        assert burns["5m"] > 14.4 and burns["1h"] > 6.0, burns
        assert len(router.fleet_dumps) == 1, (
            "both windows breaching must trigger exactly ONE "
            "coordinated dump")
        manifest = router.fleet_dumps[0]
        assert manifest["router_bundle"], "router bundle missing"
        bundles = {n: v for n, v in manifest["replicas"].items() if v}
        assert set(bundles) == {r.name for r in replicas}, (
            f"every replica must contribute a bundle: "
            f"{manifest['replicas']}")
        assert os.path.isfile(manifest["manifest_path"])
        assert manifest["trigger_trace_id"]
        # latched: continuing to burn must not re-fire
        router.slo_errors.inc()
        router.slo_tick()
        assert len(router.fleet_dumps) == 1
        record["slo_dump"] = {
            "trigger_trace_id": manifest["trigger_trace_id"],
            "burn_5m": round(burns["5m"], 1),
            "replica_bundles": len(bundles)}
        print(f"[fleet_smoke] SLO burn {burns['5m']:.0f}x -> one "
              f"coordinated dump, {len(bundles)} replica bundles + "
              f"router bundle, manifest "
              f"{os.path.basename(manifest['manifest_path'])}: OK",
              flush=True)
        return record
    finally:
        rserver.shutdown()
        router.stop()


def build_checkpoint_and_store(workdir: str) -> tuple:
    """Random-init the tiny architecture, save an orbax checkpoint, and
    run the compile farm over it -> the shared artifact store."""
    import jax
    import jax.numpy as jnp

    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo
    from raft_stereo_tpu.training import checkpoint as ckpt_mod
    import compile_farm

    cfg = RaftStereoConfig(hidden_dims=(32, 32, 32), fnet_dim=64,
                           corr_backend="reg")
    model = RAFTStereo(cfg)
    dummy = jnp.zeros((1, 32, 48, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), dummy, dummy, iters=1,
                           test_mode=True)
    ckpt = os.path.join(workdir, "ckpt")
    state = {"params": variables["params"]}
    if "batch_stats" in variables:   # cnet batch norm runs stats
        state["batch_stats"] = variables["batch_stats"]
    ckpt_mod.save_checkpoint(ckpt, cfg, state)
    store = os.path.join(workdir, "artifact-store")
    manifest_path = os.path.join(workdir, "farm_manifest.json")
    t0 = time.perf_counter()
    rc = compile_farm.main([
        "--restore_ckpt", ckpt, "--out", store,
        "--shape", f"{HW[0]}x{HW[1]}",
        "--batch_sizes", BATCH_SIZES, "--max_batch", "2",
        "--tiers", TIERS, "--default_tier", "quality",
        "--valid_iters", str(ITERS), "--sessions",
        "--manifest", manifest_path])
    assert rc == 0, "compile farm failed"
    with open(manifest_path) as f:
        manifest = json.load(f)
    assert manifest["artifacts_built"] > 0
    print(f"[fleet_smoke] farm built {manifest['artifacts_built']} "
          f"artifacts ({manifest['store_bytes']} bytes) in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    return ckpt, store, manifest


def main() -> int:
    from _hermetic import force_cpu

    force_cpu(1)

    from raft_stereo_tpu.serving.fleet import (FleetRouter, RouterConfig,
                                               RouterHTTPServer)
    from raft_stereo_tpu.telemetry.events import bench_record, write_record

    workdir = tempfile.mkdtemp(prefix="raft-fleet-smoke-")
    replicas = []
    router = None
    rserver = None
    try:
        ckpt, store, manifest = build_checkpoint_and_store(workdir)

        # ---- 1. three replicas boot WARM from the shared store --------
        replicas = [ReplicaProc(f"r{i}", ckpt, store, workdir)
                    for i in range(3)]
        for r in replicas:
            r.wait_ready()
            assert r.cold_compiles == 0, (
                f"{r.name} cold-compiled {r.cold_compiles} executables — "
                f"the shared artifact store must make boot fetch-bound "
                f"(log tail:\n{r.log_tail()})")
            assert r.warm_compiles == manifest["artifacts_built"], (
                f"{r.name} restored {r.warm_compiles} != farm's "
                f"{manifest['artifacts_built']}")
        boot = {r.name: round(r.ready_s, 2) for r in replicas}
        print(f"[fleet_smoke] 3 replicas ready, all cold_compiles == 0: "
              f"{boot}", flush=True)

        router = FleetRouter(
            {r.name: r.url for r in replicas},
            RouterConfig(health_poll_s=0.2, health_timeout_s=2.0,
                         fail_after=2, request_timeout_s=300.0,
                         fleet_brownout=False)).start()
        rserver = RouterHTTPServer(router, port=0).start()
        base = rserver.url
        assert json.loads(_get(f"{base}/readyz")[2])["ready_replicas"] == 3

        # ---- 2. pass-through parity (chaos off) ----------------------
        payload = _npz_pair()
        d_status, _, d_body = _post(
            f"{replicas[0].url}/v1/disparity", payload,
            {"Content-Type": "application/x-npz"})
        r_status, _, r_body = _post(
            f"{base}/v1/disparity", payload,
            {"Content-Type": "application/x-npz"})
        assert d_status == r_status == 200
        assert d_body == r_body, (
            "routed response must be byte-identical to the direct one "
            "(pass-through parity)")
        print("[fleet_smoke] router pass-through byte-identical: OK",
              flush=True)

        # ---- 2b. round-23 observability leg (all 3 replicas alive) ---
        obs_record = observability_phase(replicas, workdir, payload)

        # ---- 3. sessions: sticky streams across the fleet ------------
        sids = [f"cam-{i}" for i in range(8)]
        owner = {sid: router.ring.lookup(sid) for sid in sids}
        victim = next(r for r in replicas
                      if any(o == r.name for o in owner.values()))
        lost_sids = [s for s in sids if owner[s] == victim.name]
        survivor_sids = [s for s in sids if owner[s] != victim.name]
        assert survivor_sids, "ring put every session on one replica?"
        warm_seen = 0
        for sid in sids:
            for frame in range(2):
                status, headers, _ = _post(
                    f"{base}/v1/stream/{sid}?tier=quality", payload,
                    {"Content-Type": "application/x-npz"})
                assert status == 200
                if frame > 0:
                    assert headers["X-Warm"] == "1"
                    warm_seen += 1
        print(f"[fleet_smoke] {len(sids)} sessions streaming "
              f"({warm_seen} warm frames); victim={victim.name} owns "
              f"{len(lost_sids)}", flush=True)

        # ---- 4. kill -9 mid-traffic: zero stateless loss -------------
        latencies = []
        t_kill = None
        for i in range(N_STATELESS):
            if i == KILL_AFTER:
                t_kill = time.monotonic()
                victim.kill9()
            t0 = time.perf_counter()
            status, _, body = _post(
                f"{base}/v1/disparity", payload,
                {"Content-Type": "application/x-npz"})
            assert status == 200 and body == d_body, \
                f"stateless request {i} failed after the kill"
            latencies.append(time.perf_counter() - t0)
        # degraded-capacity window: kill -> the fleet marks it dead (the
        # router's transition audit trail carries the monotonic stamp of
        # the removal, which a transport-failure mid-storm makes much
        # earlier than the end of the request loop).
        detect_deadline = time.monotonic() + 30
        while (router.fleet_status()["ready"] != 2
               and time.monotonic() < detect_deadline):
            time.sleep(0.05)
        assert router.fleet_status()["ready"] == 2, \
            "the dead replica never left the rotation"
        removed_t = [tr["t"] for tr in
                     router.fleet_status()["transitions"]
                     if tr["replica"] == victim.name
                     and tr["event"] == "removed"]
        detection_s = (min(removed_t) - t_kill if removed_t
                       else time.monotonic() - t_kill)
        failovers = router.failovers.value
        assert failovers >= 1, "no failover recorded despite the kill"
        print(f"[fleet_smoke] {N_STATELESS}/{N_STATELESS} stateless OK "
              f"across kill -9 (detected dead in {detection_s:.2f}s, "
              f"max latency {max(latencies) * 1e3:.0f}ms)", flush=True)

        # ---- 5. lost sessions: typed once, then cold reseed ----------
        lost_410 = 0
        for sid in lost_sids:
            try:
                _post(f"{base}/v1/stream/{sid}?tier=quality", payload,
                      {"Content-Type": "application/x-npz"})
                raise AssertionError(
                    f"session {sid} on the dead replica must fail 410")
            except urllib.error.HTTPError as e:
                assert e.code == 410, f"expected 410, got {e.code}"
                err = json.loads(e.read())
                assert err["error"] == "session_lost"
                assert err["replica"] == victim.name
                lost_410 += 1
        for sid in lost_sids:    # fire-once contract: same id reseeds
            status, headers, _ = _post(
                f"{base}/v1/stream/{sid}?tier=quality", payload,
                {"Content-Type": "application/x-npz"})
            assert status == 200 and headers["X-Warm"] == "0", \
                f"reseeded session {sid} must COLD-start on a survivor"
        for sid in survivor_sids:   # untouched streams keep chaining
            status, headers, _ = _post(
                f"{base}/v1/stream/{sid}?tier=quality", payload,
                {"Content-Type": "application/x-npz"})
            assert status == 200 and headers["X-Warm"] == "1", \
                f"survivor session {sid} must be unaffected by the kill"
        sessions_lost_metric = router.sessions_lost.value
        assert sessions_lost_metric >= len(lost_sids)
        print(f"[fleet_smoke] {lost_410} sessions failed typed 410 "
              f"session_lost and reseeded cold; {len(survivor_sids)} "
              f"survivor sessions stayed warm", flush=True)

        # ---- 6. fleet brownout floor on a live replica ---------------
        live = next(r for r in replicas if r is not victim)
        status, _, body = _post(
            f"{live.url}/admin/brownout",
            json.dumps({"level": 1}).encode(),
            {"Content-Type": "application/json"})
        assert status == 200 and json.loads(body)["level"] == 1
        status, headers, _ = _post(
            f"{live.url}/v1/disparity?tier=quality", payload,
            {"Content-Type": "application/x-npz"})
        assert status == 200 and "X-Degraded" in headers, \
            "a pushed brownout floor must degrade with no local pressure"
        status, _, body = _post(
            f"{live.url}/admin/brownout",
            json.dumps({"level": 0}).encode(),
            {"Content-Type": "application/json"})
        assert status == 200
        status, headers, _ = _post(
            f"{live.url}/v1/disparity?tier=quality", payload,
            {"Content-Type": "application/x-npz"})
        assert "X-Degraded" not in headers
        print("[fleet_smoke] brownout floor degrade + restore: OK",
              flush=True)

        # ---- 7. graceful SIGTERM: readyz flips, nothing drops --------
        drain_target = next(r for r in replicas
                            if r is not victim and r is not live)
        results = []

        def _one():
            try:
                s, _, b = _post(f"{drain_target.url}/v1/disparity",
                                payload,
                                {"Content-Type": "application/x-npz"})
                results.append((s, b == d_body))
            except Exception as e:   # noqa: BLE001 — recorded, asserted
                results.append((type(e).__name__, False))

        _, _, m = _get(f"{drain_target.url}/metrics")
        admitted_before = _metric(m.decode(),
                                  "serve_requests_admitted_total")
        threads = [threading.Thread(target=_one) for _ in range(10)]
        for t in threads:
            t.start()
        # SIGTERM only once all 10 are ADMITTED: the satellite property
        # is "admitted work survives a SIGTERM" — work arriving after
        # the drain begins gets the typed 503, which is a different
        # (also correct) outcome this phase is not measuring.
        for _ in range(200):
            _, _, m = _get(f"{drain_target.url}/metrics")
            if (_metric(m.decode(), "serve_requests_admitted_total")
                    - admitted_before) >= 10:
                break
            time.sleep(0.02)
        drain_target.terminate()     # SIGTERM
        saw_503 = False
        for _ in range(400):
            try:
                s, _, _ = _get(f"{drain_target.url}/readyz", timeout=2)
            except urllib.error.HTTPError as e:
                s = e.code
            except (urllib.error.URLError, OSError):
                break                # listener closed: drain finished
            if s == 503:
                saw_503 = True
            time.sleep(0.02)
        for t in threads:
            t.join(timeout=120)
        drain_target.proc.wait(timeout=120)
        ok = [r for r in results if r == (200, True)]
        assert len(ok) == 10, (
            f"SIGTERM dropped in-flight work: {results} (log tail:\n"
            f"{drain_target.log_tail()})")
        assert saw_503, ("/readyz never answered 503 during the drain "
                         "window — the router had no signal to stop "
                         "routing")
        assert drain_target.proc.returncode == 0, (
            f"graceful shutdown must exit 0, got "
            f"{drain_target.proc.returncode}")
        print("[fleet_smoke] graceful SIGTERM: 10/10 in-flight answered, "
              "readyz flipped 503, exit 0", flush=True)

        # ---- 8-10. round-18 HA legs on a fresh fleet -----------------
        rserver.shutdown()
        rserver = None
        router.stop()
        router = None
        ha_record = ha_phase(ckpt, store, workdir, payload, d_body)
        ha_rec = bench_record({
            "metric": "fleet_ha_zero_loss_operations",
            "value": 1.0,
            "unit": ("rolling restart 0x410 + router kill takeover + "
                     f"autoscale drain-down ({HW[0]}x{HW[1]}, "
                     f"iters={ITERS}, CPU)"),
            "fleet_ha": ha_record,
        })
        print(json.dumps(ha_rec))
        write_record(HA_OUT, ha_rec, indent=1)
        print(f"fleet HA legs OK -> {HA_OUT}", flush=True)

        rec = bench_record({
            "metric": "fleet_smoke_stateless_survival",
            "value": 1.0,
            "unit": (f"fraction of {N_STATELESS} stateless requests "
                     f"answered across a replica kill -9 "
                     f"({HW[0]}x{HW[1]}, iters={ITERS}, 3 replicas, "
                     f"CPU)"),
            "fleet": {
                "replicas": 3,
                "boot_ready_s": boot,
                "cold_compiles_per_replica": 0,
                "warm_loads_per_replica": manifest["artifacts_built"],
                "artifact_store": {
                    "artifacts": manifest["artifacts_built"],
                    "bytes": manifest["store_bytes"],
                    "farm_wall_s": manifest["wall_s"]},
                "passthrough_byte_identical": True,
                "stateless": {
                    "sent": N_STATELESS, "answered": N_STATELESS,
                    "killed_after": KILL_AFTER,
                    "failovers": failovers,
                    "death_detection_s": round(detection_s, 3),
                    "max_latency_ms":
                        round(max(latencies) * 1e3, 1),
                    "p50_latency_ms": round(
                        sorted(latencies)[len(latencies) // 2] * 1e3,
                        1)},
                "sessions": {
                    "opened": len(sids),
                    "lost_typed_410": lost_410,
                    "reseeded_cold": len(lost_sids),
                    "survivor_warm": len(survivor_sids),
                    "fleet_sessions_lost_total": sessions_lost_metric},
                "brownout_floor": {"degraded_header": True},
                "graceful_sigterm": {
                    "inflight_answered": len(ok),
                    "readyz_503_observed": saw_503,
                    "exit_code": 0},
                "observability": obs_record,
            },
        })
        print(json.dumps(rec))
        write_record(OUT, rec, indent=1)
        print(f"fleet smoke OK -> {OUT}")
        return 0
    except BaseException:
        for r in replicas:
            print(f"---- {r.name} log tail ----\n{r.log_tail()}",
                  file=sys.stderr)
        raise
    finally:
        if rserver is not None:
            rserver.shutdown()
        if router is not None:
            router.stop()
        for r in replicas:
            r.cleanup()
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
