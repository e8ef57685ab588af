#!/usr/bin/env python
"""CI smoke: streaming stereo sessions end to end over the HTTP API.

The round-14 acceptance check, hermetic on CPU: brief-train the tiny
architecture (an untrained GRU has no meaningful convergence gate — the
same reason tools/early_exit_report.py trains first), start the serving
engine with ``sessions=True`` behind the real HTTP front door, and push
a short synthetic panned-scene video through ``POST /v1/stream/<id>``.

Asserts:

* frame 0 is a cold start (``X-Warm: 0``) and every later coherent frame
  warm-starts (``X-Warm: 1``);
* warm frames use FEWER GRU iterations than frame 0 (``X-Iters-Used`` —
  the entire point of carrying temporal state);
* a hard scene cut mid-stream falls back to cold (``X-Scene-Cut: 1``)
  instead of warm-starting from a disparity field the cut invalidated;
* session metrics appear in ``/metrics`` (``serve_sessions_active``,
  ``serve_session_frames_total{mode=...}``, the inter-frame delta
  histogram);
* an expired session id gets the typed 410 and ``DELETE`` returns the
  session's lifetime stats;
* the sessionless ``POST /v1/disparity`` path still answers (stateless
  traffic and streams share one engine);
* **multi-stream leg (round 19)**: 4 concurrent sessions over HTTP
  through an engine with ``session_hidden`` + the EDF bounded-slack
  scheduler must produce FEWER device dispatches than frames (the
  cross-session coalescing observed in the metrics), and warm-h frames
  must use <= the warm-flow-only leg's GRU iterations (the hidden
  state can only help convergence) — STREAM_ci.json asserts both.

Writes ``STREAM_ci.json`` (set STREAM_CI_OUT; CI uploads it).  Exit 0 on
success, non-zero with a diagnostic on any failed assertion.

Run from the repo root:  JAX_PLATFORMS=cpu python scripts/stream_smoke.py
"""

from __future__ import annotations

import io
import json
import os
import sys
import time
import urllib.error
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tests"))
sys.path.insert(0, os.path.join(_REPO, "tools"))

OUT = os.environ.get("STREAM_CI_OUT", os.path.join(_REPO, "STREAM_ci.json"))
STEPS = int(os.environ.get("STREAM_SMOKE_STEPS", "60"))
ITERS_CAP = 8
# Exit threshold calibrated for THIS smoke's deterministic brief
# training (60 steps at 32x48, train_iters=4, the early_exit_report
# recipe): the cold zero-init needs 2 iterations before its mean
# |Δdisparity| drops below 2.0 px while a warm-started frame's first
# update is already below it (exits at the min_iters=1 floor) — the
# warm-start discrimination the production thresholds provide on fully
# trained weights.  Weakly-trained GRUs are NOT contractive enough for
# tight thresholds: chaining warm starts at 0.3-1.0 px made the loop run
# LONGER (measured), which is exactly why this smoke trains first and
# pins the loose operating point.
TIER = "stream:2.0:1"


def _post_frame(url: str, sid: str, left, right, tier: str,
                deadline_ms=None):
    import numpy as np

    buf = io.BytesIO()
    np.savez(buf, left=left, right=right)
    headers = {"Content-Type": "application/x-npz"}
    if deadline_ms is not None:
        headers["X-Deadline-Ms"] = str(deadline_ms)
    req = urllib.request.Request(
        f"{url}/v1/stream/{sid}?tier={tier}", data=buf.getvalue(),
        method="POST", headers=headers)
    with urllib.request.urlopen(req, timeout=600) as resp:
        return {
            "status": resp.status,
            "warm": resp.headers["X-Warm"] == "1",
            "scene_cut": resp.headers.get("X-Scene-Cut") == "1",
            "frame_index": int(resp.headers["X-Frame-Index"]),
            "iters_used": int(resp.headers["X-Iters-Used"]),
            "delta": (float(resp.headers["X-Frame-Delta"])
                      if "X-Frame-Delta" in resp.headers else None),
            "disp": np.load(io.BytesIO(resp.read())),
        }


def main() -> int:
    from _hermetic import force_cpu

    force_cpu(1)
    import numpy as np

    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    from early_exit_report import model_config, trained_variables
    from golden_data import disparity_field, textured_image, warp_right
    from raft_stereo_tpu.serving import ServeConfig, StereoService
    from raft_stereo_tpu.serving.http import StereoHTTPServer
    from raft_stereo_tpu.telemetry.events import bench_record, write_record

    hw = (32, 48)
    cfg = model_config()
    t0 = time.perf_counter()
    variables = trained_variables(cfg, STEPS, hw, 4)
    print(f"brief-trained {STEPS} steps in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    # Synthetic panned video: 5 coherent frames, then a hard scene cut
    # (a DIFFERENT scene, darkened so the mean-pooled thumbnail delta is
    # unambiguous — two independent mid-gray textures pool to similar
    # means, a brightness change does not).
    rng = np.random.default_rng(17)
    scene, disp = textured_image(rng, *hw), disparity_field(rng, *hw)
    frames = []
    for t in range(5):
        left = np.roll(scene, -2 * t, axis=1)
        d = np.roll(disp, -2 * t, axis=1)
        frames.append((left.astype(np.uint8),
                       warp_right(left, d).astype(np.uint8)))
    cut_scene = (textured_image(rng, *hw) * 0.3).astype(np.uint8)
    cut_disp = disparity_field(rng, *hw)
    frames.append((cut_scene,
                   warp_right(cut_scene, cut_disp).astype(np.uint8)))

    tier = TIER
    serve_cfg = ServeConfig(
        max_batch=1, batch_sizes=(1,), iters=ITERS_CAP,
        sessions=True, session_ttl_s=600.0, scene_cut_threshold=40.0,
        tiers=(tier, "quality"), default_tier="quality")
    with StereoService(cfg, variables, serve_cfg) as svc:
        server = StereoHTTPServer(svc, port=0).start()
        url = server.url
        try:
            results = [_post_frame(url, "cam0", l, r, "stream")
                       for l, r in frames]
            f0, coherent, cut = results[0], results[1:5], results[5]

            assert not f0["warm"] and f0["frame_index"] == 0, f0
            assert all(r["warm"] for r in coherent), \
                [r["warm"] for r in results]
            assert [r["frame_index"] for r in results] == list(range(6))
            # The acceptance bar: warm frames converge in fewer GRU
            # iterations than the cold frame 0.
            warm_iters = [r["iters_used"] for r in coherent]
            assert max(warm_iters) < f0["iters_used"], (
                f"warm frames must use fewer GRU iterations than frame "
                f"0: warm {warm_iters} vs cold {f0['iters_used']}")
            # Scene cut: cold fallback, flagged, large measured delta.
            assert not cut["warm"] and cut["scene_cut"], cut
            assert cut["delta"] is not None and cut["delta"] > 40.0, cut

            # Session metrics in /metrics.
            with urllib.request.urlopen(url + "/metrics",
                                        timeout=60) as resp:
                metrics = resp.read().decode()
            for needle in ("serve_sessions_active 1",
                           'serve_session_frames_total{mode="warm"} 4',
                           'serve_session_frames_total{mode="cold"} 2',
                           "serve_session_scene_cuts_total 1",
                           "serve_session_frame_delta_count"):
                assert needle in metrics, f"{needle!r} missing:\n" + \
                    "\n".join(ln for ln in metrics.splitlines()
                              if "session" in ln)

            # Stateless traffic still served by the same engine.
            buf = io.BytesIO()
            np.savez(buf, left=frames[0][0], right=frames[0][1])
            req = urllib.request.Request(
                url + "/v1/disparity", data=buf.getvalue(), method="POST",
                headers={"Content-Type": "application/x-npz"})
            with urllib.request.urlopen(req, timeout=600) as resp:
                assert resp.status == 200
                assert "X-Session-Id" not in resp.headers

            # DELETE returns lifetime stats; the id then 410s.
            req = urllib.request.Request(url + "/v1/stream/cam0",
                                         method="DELETE")
            with urllib.request.urlopen(req, timeout=60) as resp:
                stats = json.loads(resp.read())
            assert stats["frames"] == 6 and stats["warm_frames"] == 4, stats
            try:
                _post_frame(url, "cam0", *frames[0], "stream")
                raise AssertionError("closed session must 410")
            except urllib.error.HTTPError as e:
                assert e.code == 410, e.code
                body = json.loads(e.read())
                assert body["error"] == "session_expired", body
        finally:
            server.shutdown()

    # ---- multi-stream leg (round 19): warm-h + EDF coalescing --------
    import threading

    n_streams = 4
    stream_frames = frames[:5]              # the coherent prefix only
    serve_cfg2 = ServeConfig(
        max_batch=4, batch_sizes=(1, 2, 4), iters=ITERS_CAP,
        sessions=True, session_hidden=True, session_ttl_s=600.0,
        scene_cut_threshold=40.0, edf_scheduler=True,
        edf_max_slack_ms=50.0,
        tiers=(tier, "quality"), default_tier="quality")
    with StereoService(cfg, variables, serve_cfg2) as svc2:
        server = StereoHTTPServer(svc2, port=0).start()
        url = server.url
        try:
            health = json.loads(urllib.request.urlopen(
                url + "/healthz", timeout=60).read())
            assert health["session_hidden"] and health["edf_scheduler"], \
                health
            results2 = {j: [] for j in range(n_streams)}
            errors = []
            barrier = threading.Barrier(n_streams)

            def stream(j):
                try:
                    barrier.wait()
                    for left, right in stream_frames:
                        results2[j].append(_post_frame(
                            url, f"cam{j}", left, right, "stream",
                            deadline_ms=60000))
                except Exception as e:  # pragma: no cover - diagnostics
                    errors.append((j, e))

            d0 = svc2.metrics.batches.value
            threads = [threading.Thread(target=stream, args=(j,),
                                        daemon=True)
                       for j in range(n_streams)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            assert not errors, errors
            dispatches = svc2.metrics.batches.value - d0
            frames_total = n_streams * len(stream_frames)
            # The coalescing assertion: concurrent sessions' frames
            # merged into batch-N dispatches — deliberately, via the
            # EDF bounded-slack wait, not by accident.
            assert dispatches < frames_total, (
                f"EDF coalescing must issue fewer dispatches than "
                f"frames: {dispatches} dispatches for {frames_total} "
                f"frames")
            coalescing = frames_total / dispatches
            multi = sum(svc2.metrics.dispatches_at(n) for n in (2, 4))
            assert multi >= 1, \
                "at least one batch>1 dispatch must have occurred"
            # /metrics carries the evidence the assertion used.
            with urllib.request.urlopen(url + "/metrics",
                                        timeout=60) as resp:
                metrics2 = resp.read().decode()
            assert "serve_edf_slack_waits_total" in metrics2
            assert 'serve_dispatches_total{batch="2"}' in metrics2 \
                or 'serve_dispatches_total{batch="4"}' in metrics2, \
                "batch>1 dispatch families missing from /metrics"
            # warm-h frames must converge at least as fast as the
            # flow-only leg's warm frames (the hidden trajectory can
            # only help): compare mean warm iters across the legs.
            warm_h_iters = [r["iters_used"]
                            for js in results2.values() for r in js
                            if r["warm"]]
            assert warm_h_iters, "multi-stream leg produced no warm frames"
            mean_warm_h = float(np.mean(warm_h_iters))
            mean_warm_flow = float(np.mean(warm_iters))
            assert mean_warm_h <= mean_warm_flow + 1e-9, (
                f"warm-h frames must use <= warm-flow-only GRU "
                f"iterations: {mean_warm_h} vs {mean_warm_flow}")
        finally:
            server.shutdown()

        rec = bench_record({
            "metric": "stream_ci_smoke",
            "value": round(float(np.mean(warm_iters)) / f0["iters_used"],
                           3),
            "unit": f"warm mean iters_used / cold frame-0 iters_used "
                    f"(cap {ITERS_CAP}, {hw[0]}x{hw[1]}, CPU)",
            "train_steps": STEPS,
            "cold_frame0_iters": f0["iters_used"],
            "warm_iters": warm_iters,
            "scene_cut_delta": round(cut["delta"], 2),
            "scene_cut_iters": cut["iters_used"],
            "tier": tier,
            "session_stats": stats,
            # Round-19 multi-stream leg: both asserted properties,
            # recorded so the artifact is auditable.
            "multi_stream": {
                "streams": n_streams,
                "frames_total": frames_total,
                "dispatches": int(dispatches),
                "coalescing_ratio": round(coalescing, 3),
                "edf_slack_waits":
                    svc2.metrics.edf_slack_waits.value,
                "mean_warm_h_iters": round(mean_warm_h, 3),
                "mean_warm_flow_iters": round(mean_warm_flow, 3),
            },
        })
    print(json.dumps(rec))
    write_record(OUT, rec, indent=1)
    print(f"stream smoke OK -> {OUT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
