"""Entry ``serve_http``: the configuration behind ``raft-serve`` in a child
process that owns the chip; this process is the load generator and never
initialises jax.  Open loop over HTTP at the rate the workload file fixes.
"""

from __future__ import annotations

import glob
import io
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import harness, post, prom, scenes, traffic

READY_TIMEOUT_S = 1100.0


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _encode(pair) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, left=pair[0], right=pair[1])
    return buf.getvalue()


def _tail(path: str, n: int = 4000) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""


class Server:
    """``raft-serve`` in its child process, from boot to drained exit."""

    def __init__(self, cell: dict, seed: int,
                 rig: harness.TestRig = harness.NO_RIG):
        wl, config = cell["workload"], cell["config"]
        self.cell, self.seed = cell, seed
        self.wd = harness.work_dir(cell["name"])
        port = _free_port()
        self.url = f"http://127.0.0.1:{port}"
        self.hw = tuple(wl["traffic"]["image_hw"])
        self.log_path = self.path("server.log")
        serve_argv = ["--restore_ckpt", self.path("ckpt"),
                      "--port", str(port),
                      "--valid_iters", str(wl["iters"]),
                      "--warmup_shape", f"{self.hw[0]}x{self.hw[1]}",
                      *wl["serve_args"]]
        with open(self.path("params.json"), "w") as f:
            json.dump({"chips": cell["chips"], "seed": seed,
                       "model": config["model"],
                       "require_accelerator": rig.require_accelerator,
                       "program_overrides": rig.program_overrides,
                       "child_patch": rig.child_patch,
                       "ckpt": self.path("ckpt"),
                       "device_path": self.path("device.json"),
                       "result_path": self.path("child_result.json"),
                       "serve_argv": serve_argv}, f)
        # the configuration's own environment (its stated precision) goes to
        # the process that runs the program, and to no other
        env = harness.cache_env()
        env.update(config.get("env", {}))
        env.update(rig.env)
        self._log_f = open(self.log_path, "wb")
        self.child = subprocess.Popen(
            [sys.executable, "-m", "benchmark.entries._serve_child",
             self.path("params.json")],
            cwd=self.wd, env=env, stdout=self._log_f,
            stderr=subprocess.STDOUT)
        self.bodies = None
        self.boot = None

    def path(self, name: str) -> str:
        return os.path.join(self.wd, name)

    def get(self, route: str, timeout: float = 10.0) -> bytes:
        with urllib.request.urlopen(self.url + route, timeout=timeout) as r:
            return r.read()

    def post_pair(self, body: bytes) -> tuple:
        req = urllib.request.Request(
            self.url + "/v1/disparity", data=body,
            headers={"Content-Type": "application/x-npz"})
        with urllib.request.urlopen(req, timeout=180) as r:
            return r.read(), int(r.headers["X-Batch-Size"])

    def wait_ready(self) -> None:
        """While the server boots, make the pool of request bodies; ready
        means every program of the ladder is compiled.  Then the warm-up:
        every program executes once."""
        wl = self.cell["workload"]
        pool = scenes.make_pairs(self.seed, wl["traffic"]["pool_pairs"],
                                 self.hw)
        with ThreadPoolExecutor(4) as ex:
            self.bodies = list(ex.map(_encode, pool))
        del pool
        t_end = time.monotonic() + READY_TIMEOUT_S
        while True:
            if self.child.poll() is not None:
                sys.stderr.write(_tail(self.log_path))
                raise harness.BenchError(
                    f"the server process exited {self.child.returncode} "
                    f"before /readyz answered 200")
            try:
                self.get("/readyz", timeout=2)
                break
            except (urllib.error.URLError, OSError):
                pass
            if time.monotonic() > t_end:
                raise harness.BenchError("/readyz not 200 in time")
            time.sleep(0.25)
        with open(self.path("device.json")) as f:
            self.boot = json.load(f)
        for burst in wl["warmup_bursts"]:
            with ThreadPoolExecutor(burst) as ex:
                list(ex.map(self.post_pair,
                            [self.bodies[k % len(self.bodies)]
                             for k in range(burst)]))

    def metrics(self) -> dict:
        return prom.parse(self.get("/metrics").decode())

    def start_trace(self, duration_s: float) -> dict:
        req = urllib.request.Request(
            self.url + "/debug/trace", method="POST",
            data=json.dumps({"duration_ms": duration_s * 1e3}).encode())
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    def window(self, rate_per_s: float, seconds: float, seed: int,
               keep=(), trace_at=None, trace_s: float = 0.0,
               phase_from_seed: bool = True) -> dict:
        """One open-loop window at ``rate_per_s``; answers of the requests
        in ``keep`` are written under ``answers/``."""
        due = traffic.open_schedule(rate_per_s, seconds, seed,
                                    phase_from_seed)
        n = len(due)
        order = traffic.pair_order(n, len(self.bodies), seed)
        keep = set(keep)
        os.makedirs(self.path("answers"), exist_ok=True)

        def send(i: int):
            payload, batch = self.post_pair(self.bodies[order[i]])
            if i in keep:
                with open(self.path(f"answers/{i}.npy"), "wb") as f:
                    f.write(payload)
            return len(payload), batch

        trace_info = {}
        tracer = None
        if trace_at is not None:
            def go():
                trace_info.update(self.start_trace(trace_s))

            tracer = threading.Timer(trace_at, go)
            tracer.daemon = True
            tracer.start()
        before = self.metrics()
        res = traffic.run_open_loop(due, send)
        after = self.metrics()
        if tracer is not None:
            tracer.join(timeout=60)
            trace_info["wait_s"] = self._wait_for_trace(
                trace_info.get("trace_dir"))
        return {"res": res, "order": order, "n": n,
                "counters": prom.delta(before, after),
                "queue_depth_end": prom.total(after, "serve_queue_depth"),
                "trace_info": trace_info}

    def _wait_for_trace(self, trace_dir, timeout_s: float = 150.0) -> float:
        """The server's own timer closes the capture, and the profiler then
        takes ten seconds and more to write a few seconds of serving (host
        events by the hundred thousand): three of six traced runs of 20 s
        stopped the server before the file was there (my chip run, PR 24,
        call 12).  So wait for the file, and for its size to stand still;
        returns the seconds waited."""
        if not trace_dir:
            raise harness.BenchError("the server opened no trace window")
        pattern = os.path.join(self.wd, trace_dir, "**", "*.xplane.pb")
        t0 = time.monotonic()
        deadline = t0 + timeout_s
        size = -1
        while time.monotonic() < deadline:
            files = glob.glob(pattern, recursive=True)
            now = os.path.getsize(files[0]) if files else -1
            if now > 0 and now == size:
                return time.monotonic() - t0
            size = now
            time.sleep(1.0)
        raise harness.BenchError(
            f"no trace under {trace_dir} {timeout_s:.0f} s after the window")

    def stop(self) -> dict:
        """SIGTERM, a drained exit 0, and what the child left behind."""
        self.child.send_signal(signal.SIGTERM)
        try:
            rc = self.child.wait(timeout=120)
        except subprocess.TimeoutExpired:
            self.child.kill()
            rc = self.child.wait()
        if rc != 0:
            sys.stderr.write(_tail(self.log_path))
            raise harness.BenchError(f"the server exited {rc} after SIGTERM")
        with open(self.path("child_result.json")) as f:
            return json.load(f)

    def close(self) -> None:
        if self.child.poll() is None:
            self.child.kill()
        self.child.wait()
        self._log_f.close()


def run(cell: dict, seed: int, seconds: float, trace: bool,
        rig: harness.TestRig = harness.NO_RIG) -> dict:
    server = Server(cell, seed, rig)
    try:
        return _drive(server, cell, seed, seconds, trace, rig)
    finally:
        server.close()


def _drive(server: Server, cell, seed, seconds, trace, rig) -> dict:
    wl = cell["workload"]
    tr = wl["traffic"]
    server.wait_ready()
    setup_s = time.monotonic() - harness.T_PROCESS_START
    n_planned = len(traffic.open_schedule(tr["rate_per_s"], seconds, seed))
    keep = traffic.sample_ids(n_planned, wl["compare"]["answers"], seed)
    w = server.window(
        tr["rate_per_s"], seconds, seed, keep=keep,
        trace_at=wl["trace"]["start_frac"] * seconds if trace else None,
        trace_s=min(wl["trace"]["duration_s"], 0.5 * seconds),
        phase_from_seed=tr.get("phase_from_seed", True))
    res, order, n = w["res"], w["order"], w["n"]
    compiles = json.loads(server.get("/debug/compiles"))
    child_result = server.stop()

    # ---- after the server has gone: the trace's reduction, the reference
    answered = [i for i in keep if res.ok[i]]
    ti = w["trace_info"]
    post.write_request(
        server.path("post.json"), cell, seed,
        [[i, int(order[i]), server.path(f"answers/{i}.npy")]
         for i in answered], "disparity",
        os.path.join(server.wd, ti["trace_dir"]) if ti else None,
        server.path("post_result.json"),
        require_accelerator=rig.require_accelerator)
    post_rc = subprocess.run(
        [sys.executable, "-m", "benchmark.post", server.path("post.json")],
        cwd=server.wd, env=harness.cache_env()).returncode
    if post_rc != 0:
        raise harness.BenchError(f"the comparison process exited {post_rc}")
    with open(server.path("post_result.json")) as f:
        post_result = json.load(f)

    # ---- the numbers
    lat_ms = (res.latency_s * 1e3).tolist()
    in_window = int(np.sum(res.ok & (res.done <= seconds)))
    batches = [info[1] for info, ok in zip(res.info, res.ok) if ok]
    e2e = {"latency_p50_ms": harness.percentile(lat_ms, 50, n),
           "latency_p95_ms": harness.percentile(lat_ms, 95, n),
           "pairs_per_s": in_window / seconds,
           "setup_s": setup_s}
    boot = server.boot
    observed = {
        "cell": cell, "seconds": seconds, "pairs_completed": in_window,
        "counters": w["counters"], "latency_ms": lat_ms, "attempted": n,
        "trace": post_result.get("trace"),
        "device_kind": rig.device_kind or boot["device"]["kind"],
    }
    return {
        "e2e": e2e, "observed": observed,
        "compared": post_result["compared"],
        "attempted": n, "failed": int(n - res.ok.sum()),
        "device": dict(boot["device"]),
        "memory": {"memory_stats": child_result["memory_stats"],
                   "executables": compiles},
        "trace": post_result.get("trace"),
        "extra": {"generator_late_ms_p95": float(
                      np.percentile(res.late_s * 1e3, 95)),
                  "answers_by_batch": {str(b): batches.count(b)
                                       for b in sorted(set(batches))},
                  "queue_depth_at_end": w["queue_depth_end"],
                  "trace_wait_s": ti.get("wait_s"),
                  "checkpoint_s": boot["checkpoint_s"],
                  "checkpoint_parts_s": boot["checkpoint_parts_s"],
                  "reference_s": post_result["reference_s"]},
    }
