#!/usr/bin/env python
"""CI chaos smoke: the serving resilience layer under injected failure.

Four acceptance properties, asserted end to end on CPU at tiny shapes
(no datasets, no accelerator):

1. **Zero lost requests under chaos** — with a 10% injected
   worker-crash rate, every submitted request TERMINATES: success after
   retries, or a typed error (RequestPoisoned / Overloaded /
   DeadlineExceeded).  No hung future, no silently dropped request, and
   the ledger balances: completed + poisoned (+ shed) == submitted.
2. **Circuit breaker quarantines and recovers a flapping device** — a
   deterministically flapping worker (crash_rate=1.0, bounded fault
   budget) drives the breaker closed -> open -> half-open -> closed,
   observed through the anomaly-sink transitions and the
   serve_circuit_state gauge, while every request still completes.
3. **Chaos off == round-12 dispatch path** — with no ChaosConfig the
   engine's batch-1 result is BITWISE-equal to solo InferenceRunner
   inference (the no-chaos overhead is one attribute check).
4. **Warm restart-to-ready >= 5x faster than cold** — with the
   persistent executable cache, a restarted engine's prewarm of the
   default bucket x tier ladder loads executables from disk instead of
   recompiling; measured and recorded, with the liveness/readiness
   split checked (ready only after the ladder is warm).

Writes ``bench_record`` JSONs: chaos results to CHAOS_SMOKE_OUT
(default CHAOS_ci.json) and the restart benchmark to RECOVERY_OUT
(default RECOVERY_ci.json) — CI uploads both.  Exit 0 on success,
non-zero with a diagnostic on any failure.

Run from the repo root:  JAX_PLATFORMS=cpu python scripts/chaos_smoke.py
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tests"))

OUT = os.environ.get("CHAOS_SMOKE_OUT",
                     os.path.join(_REPO, "CHAOS_ci.json"))
RECOVERY_OUT = os.environ.get("RECOVERY_OUT",
                              os.path.join(_REPO, "RECOVERY_ci.json"))


class _RecordingSink:
    """Duck-typed AnomalySink: records every fired kind in order."""

    def __init__(self):
        self.kinds = []

    def fire(self, kind, **detail):
        self.kinds.append(kind)
        return {"kind": kind, **detail}


def chaos_survival(cfg, variables, hw, lefts, rights) -> dict:
    """Property 1: 10% injected worker-crash rate, every request
    terminates, zero lost."""
    from raft_stereo_tpu.serving import (ChaosConfig, DeadlineExceeded,
                                         Overloaded, RequestPoisoned,
                                         ServeConfig, StereoService)

    n_requests = 60
    chaos = ChaosConfig(seed=13, crash_rate=0.10)
    sc = ServeConfig(max_batch=2, batch_sizes=(1, 2), iters=1,
                     max_queue=n_requests, chaos=chaos,
                     max_dispatch_attempts=3, retry_backoff_ms=5.0,
                     breaker_failures=3, breaker_cooldown_s=0.1)
    outcomes = {"ok": 0, "poisoned": 0, "shed": 0, "deadline": 0}
    recovered = 0
    with StereoService(cfg, variables, sc) as svc:
        svc.prewarm(hw)
        futures = []
        for i in range(n_requests):
            try:
                futures.append(svc.submit(lefts[i % len(lefts)],
                                          rights[i % len(rights)]))
            except Overloaded:
                outcomes["shed"] += 1
        for f in futures:
            # A hung future IS the failure this smoke exists to catch:
            # the bounded wait turns it into a loud one.
            try:
                res = f.result(timeout=300)
                outcomes["ok"] += 1
                if res.attempts > 1:
                    recovered += 1
            except RequestPoisoned:
                outcomes["poisoned"] += 1
            except DeadlineExceeded:
                outcomes["deadline"] += 1
            except Overloaded:
                outcomes["shed"] += 1
        m = svc.metrics
        terminated = sum(outcomes.values())
        assert terminated == n_requests, (
            f"LOST REQUESTS: {n_requests} submitted, only {terminated} "
            f"terminated ({outcomes})")
        assert m.injected_faults("crash") > 0, \
            "10% crash rate injected nothing — chaos not wired?"
        assert m.retries.value > 0, \
            "crashes happened but nothing was retried"
        assert m.worker_restarts.value > 0, \
            "crashes happened but no worker was restarted"
        assert outcomes["ok"] > 0.5 * n_requests, (
            f"supervised recovery should save most requests at a 10% "
            f"crash rate: {outcomes}")
        record = {
            "submitted": n_requests, "outcomes": outcomes,
            "recovered_after_retry": recovered,
            "injected_crashes": m.injected_faults("crash"),
            "retries": m.retries.value,
            "worker_restarts": m.worker_restarts.value,
            "poisoned": m.poisoned.value,
            "crash_rate": chaos.crash_rate, "seed": chaos.seed,
        }
    print(f"[chaos_smoke] survival: {record}")
    return record


def breaker_flapping_device(cfg, variables, hw, lefts, rights) -> dict:
    """Property 2: a flapping device is quarantined by its breaker and
    recovered through the half-open probe; no request is lost."""
    from raft_stereo_tpu.serving import (CIRCUIT_CLOSED, ChaosConfig,
                                         ServeConfig, StereoService)

    # crash_rate=1.0 with a 2-fault budget: exactly two consecutive
    # dispatch failures (= breaker_failures), then the device is healthy
    # again — the deterministic flap.
    chaos = ChaosConfig(seed=7, crash_rate=1.0, max_faults=2)
    sc = ServeConfig(max_batch=1, batch_sizes=(1,), iters=1,
                     chaos=chaos, max_dispatch_attempts=4,
                     retry_backoff_ms=5.0, breaker_failures=2,
                     breaker_cooldown_s=0.2)
    sink = _RecordingSink()
    with StereoService(cfg, variables, sc) as svc:
        svc.attach_anomaly_sink(sink)
        svc.prewarm(hw)
        futures = [svc.submit(lefts[i % len(lefts)],
                              rights[i % len(rights)]) for i in range(4)]
        results = [f.result(timeout=300) for f in futures]
        assert all(r.flow.shape == hw for r in results)
        assert any(r.attempts > 1 for r in results), \
            "the flapped requests must have recovered via retry"
        kinds = list(sink.kinds)
        assert "circuit_open" in kinds, \
            f"breaker never opened on the flapping device: {kinds}"
        assert "circuit_closed" in kinds and (
            kinds.index("circuit_closed") > kinds.index("circuit_open")), \
            f"breaker never recovered after quarantine: {kinds}"
        final_state = svc.metrics.circuit_gauge(0).value
        assert final_state == CIRCUIT_CLOSED, (
            f"circuit must end closed, gauge says {final_state}")
        record = {
            "transitions": kinds,
            "injected_crashes": svc.metrics.injected_faults("crash"),
            "worker_restarts": svc.metrics.worker_restarts.value,
            "completed": svc.metrics.completed.value,
            "final_circuit_state": final_state,
        }
    print(f"[chaos_smoke] flapping device: {record}")
    return record


def no_chaos_bitwise(cfg, variables, hw, lefts, rights) -> dict:
    """Property 3: chaos off -> batch-1 result bitwise-equal to solo."""
    import numpy as np

    from raft_stereo_tpu.eval.runner import InferenceRunner
    from raft_stereo_tpu.serving import ServeConfig, StereoService

    solo = InferenceRunner(cfg, variables, iters=1)
    want, _ = solo(lefts[0], rights[0])
    with StereoService(cfg, variables,
                       ServeConfig(max_batch=1, batch_sizes=(1,),
                                   iters=1)) as svc:
        res = svc.infer(lefts[0], rights[0], timeout=300)
        assert res.attempts == 1 and not res.degraded
        assert np.array_equal(res.flow, want), (
            "no-chaos dispatch must be bitwise-equal to solo inference")
        assert svc.chaos is None and svc.metrics.retries.value == 0
    print("[chaos_smoke] no-chaos path bitwise-equal to solo: OK")
    return {"bitwise_equal": True}


def restart_to_ready(cfg, variables, shapes) -> dict:
    """Property 4: persistent-cache warm restart >= 5x faster to ready
    than cold compile-from-scratch, on the default bucket x tier ladder."""
    from raft_stereo_tpu.serving import ServeConfig, StereoService

    cache_dir = tempfile.mkdtemp(prefix="raft-exe-cache-")
    tiers = ("interactive", "quality")
    sc = ServeConfig(max_batch=2, batch_sizes=(1, 2), iters=1,
                     tiers=tiers, executable_cache_dir=cache_dir,
                     warmup_shapes=tuple(shapes), prewarm_on_init=False)

    def boot() -> tuple:
        t0 = time.perf_counter()
        svc = StereoService(cfg, variables, sc)
        assert not svc.ready, ("readiness gate must be CLOSED before the "
                               "configured ladder is warm")
        for hw in shapes:
            svc.prewarm(hw)
        assert svc.ready, (f"readiness gate never opened: "
                           f"{svc.warm_status()}")
        return svc, time.perf_counter() - t0

    try:
        svc_cold, cold_s = boot()
        cold_compiles = svc_cold.metrics.compiles_cold.value
        status_cold = svc_cold.warm_status()
        svc_cold.close()

        svc_warm, warm_s = boot()
        warm_loads = svc_warm.metrics.compiles_warm.value
        warm_cold_compiles = svc_warm.metrics.compiles_cold.value
        status_warm = svc_warm.warm_status()
        svc_warm.close()
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)

    speedup = cold_s / warm_s if warm_s > 0 else float("inf")
    assert cold_compiles > 0, "cold boot compiled nothing?"
    assert warm_cold_compiles == 0 and warm_loads == cold_compiles, (
        f"warm boot must restore every executable from disk: "
        f"{warm_loads} loaded, {warm_cold_compiles} recompiled "
        f"(cold boot built {cold_compiles})")
    assert speedup >= 5.0, (
        f"warm restart-to-ready must beat cold prewarm by >= 5x: "
        f"cold {cold_s:.2f}s vs warm {warm_s:.2f}s ({speedup:.1f}x)")
    record = {
        "cold_ready_s": round(cold_s, 3),
        "warm_ready_s": round(warm_s, 3),
        "speedup": round(speedup, 2),
        "executables": cold_compiles,
        "warm_loads": warm_loads,
        "ladder": {"shapes": [list(s) for s in shapes],
                   "tiers": list(tiers), "batch_sizes": [1, 2]},
        "cold_status": status_cold, "warm_status": status_warm,
    }
    print(f"[chaos_smoke] restart-to-ready: cold {cold_s:.2f}s, warm "
          f"{warm_s:.2f}s ({speedup:.1f}x)")
    return record


def main() -> int:
    from _hermetic import force_cpu

    jax = force_cpu(1)
    import jax.numpy as jnp
    import numpy as np

    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo
    from raft_stereo_tpu.telemetry.events import bench_record, write_record

    cfg = RaftStereoConfig(hidden_dims=(32, 32, 32), fnet_dim=64,
                           corr_backend="reg")
    model = RAFTStereo(cfg)
    dummy = jnp.zeros((1, 32, 48, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), dummy, dummy, iters=1,
                           test_mode=True)
    rng = np.random.default_rng(0)
    hw = (48, 64)
    lefts = [rng.integers(0, 255, hw + (3,), dtype=np.uint8)
             for _ in range(4)]
    rights = [np.roll(l, -3, axis=1) for l in lefts]

    survival = chaos_survival(cfg, variables, hw, lefts, rights)
    flapping = breaker_flapping_device(cfg, variables, hw, lefts, rights)
    bitwise = no_chaos_bitwise(cfg, variables, hw, lefts, rights)
    rec = bench_record({
        "metric": "chaos_smoke_survival_rate",
        "value": round(survival["outcomes"]["ok"]
                       / survival["submitted"], 3),
        "unit": (f"fraction of requests answered under a "
                 f"{survival['crash_rate']:.0%} injected worker-crash "
                 f"rate ({hw[0]}x{hw[1]}, iters=1, CPU)"),
        "platform": jax.devices()[0].platform,
        "survival": survival,
        "flapping_device": flapping,
        "no_chaos_bitwise": bitwise,
    })
    print(json.dumps(rec))
    write_record(OUT, rec, indent=1)
    print(f"chaos smoke OK -> {OUT}")

    recovery = restart_to_ready(cfg, variables, [hw])
    rec2 = bench_record({
        "metric": "restart_to_ready_speedup",
        "value": recovery["speedup"],
        "unit": ("warm (persistent executable cache) vs cold "
                 "compile-from-scratch prewarm of the bucket x tier "
                 "ladder, restart-to-ready seconds (CPU; TPU pending "
                 "as in prior rounds)"),
        "platform": jax.devices()[0].platform,
        **recovery,
    })
    print(json.dumps(rec2))
    write_record(RECOVERY_OUT, rec2, indent=1)
    print(f"recovery benchmark OK -> {RECOVERY_OUT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
