#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py               # one TPU chip: serve, then train
    python3 chip_smoke.py --four-chips  # four chips: data-parallel step only

Default run, at the published widths of the accuracy architecture
(``RaftStereoConfig()``: 3-level ConvGRU, hidden 128, fnet 256, 1/4-res,
4 correlation levels x radius 4) on weights, images and a SceneFlow-layout
tree made from ``--seed``:

1. *serve* — ``raft-serve`` through its normal entry
   (``raft_stereo_tpu.cli.serve.main``) at 375x1242 / 32 iterations with a
   1,2 batch ladder: wait for /readyz, POST pairs (some concurrently, so a
   batch-2 dispatch happens), scrape /metrics, SIGTERM, expect a clean
   drain and exit 0.  Boot it a second time to show the compiled programs
   come back from jax's persistent cache.  Then compare every answer with
   the solo ``InferenceRunner`` (the same program by construction:
   bitwise) and the kernels with the pure-XLA program at one iteration,
   and look for the Mosaic kernel in the served executable's text.
2. *train* — ``raft-stereo-train`` through ``raft_stereo_tpu.cli.train.main``
   with the published SceneFlow recipe (320x720 crop, 22 iterations, bf16)
   on the synthetic tree, through the real loader.

One process per chip: this parent never initialises a jax backend, and
every phase is a child that owns the chip while it lives, one after
another.  Any phase that fails makes the exit code non-zero; without a TPU
the script fails at once and prints no result.  The LAST line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import io
import json
import logging
import os
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

HERE = os.path.dirname(os.path.abspath(__file__))

SERVE_HW = (375, 1242)        # KITTI; the engine pads it to 384x1248
SERVE_ITERS = 32
SERVE_BATCHES = "1,2"
N_PAIRS = 3                   # one per burst of concurrent requests
BURST = 4

TRAIN_CROP = (320, 720)
TRAIN_ITERS = 22
# The published recipe is batch 8.  The v5e's compiler asks 17.13e9 B of
# temp (+0.17e9 B of arguments) for that step — more than the chip's 16 GiB
# (17.18e9 B) less the runtime's share — so one chip trains at batch 4 and
# batch 8 is the four-chip data-parallel step (--four-chips).  Every width,
# the crop and the iteration count stay as published.
TRAIN_BATCH = 4
PUBLISHED_BATCH = 8
TRAIN_STEPS = 6
TRAIN_WINDOW = 3              # steps per metric drain / checkpoint

# The lookup kernel alone, against a float64 NumPy interpolation of the
# same random pyramid (the plain reference, independent of both programs),
# as a fraction of the largest sample.  Through the interpreter on the CPU
# it agrees to fp32 rounding (7e-8); the bound is where a wrong tap, a
# wrong row or a wrong level scale lands, which is wrong by the sample
# itself, and the measured error is printed for the record.  The XLA
# sampler is printed beside it and is the LESS exact of the two (it rounds
# the tap position c/2^i + dx to fp32 before taking its fraction: ~2e-5
# of a sample at KITTI width, on any backend), which is where the two
# programs start to differ.
LOOKUP_VS_FLOAT64_RTOL = 1e-2

# Kernels vs pure XLA through the whole model, one GRU iteration, fp32,
# seeded random weights, as a fraction of the largest |flow| (tens of px).
# Not bitwise by design, and not at fp32 rounding either: the samplers
# differ by ~1e-4 (above), XLA's fp32 convs on the TPU round their inputs
# to bf16 (default precision, 2^-8), which turns a 1e-4 difference into
# whole bf16 ulps on some elements, and the ConvGRU kernel runs its MXU
# passes at HIGHEST.  The first v5e run (PR 22) measured 1.0e-2 of max
# |flow| (0.50 px of 50.5, mean 0.087 px) with and without the ConvGRU
# kernel; a kernel that reads the wrong rows or taps is wrong by the flow
# itself.  One iteration only: at 32 the untrained GRU amplifies any
# difference ~5x per iteration (.claude/skills/verify/SKILL.md) and the
# comparison says nothing.
KERNELS_VS_XLA_RTOL = 3e-2

# Data-parallel step vs the one-device step, bf16, 22 untrained GRU
# iterations: per-device batch 1 and batch 4 tile their convs differently,
# bf16 rounds the difference in, and the loop amplifies it — so percent,
# not the 1e-4 that tests/test_parallel.py pins in fp32 at 2 iterations.
MESH_PARITY_RTOL = 5e-2


def say(msg: str) -> None:
    print(msg, flush=True)


class PhaseFailed(RuntimeError):
    pass


# ===================================================================== parent
def _child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _child_argv(phase: str, params: dict) -> list:
    return [sys.executable, "-c",
            "import chip_smoke; chip_smoke.child_main()", phase,
            json.dumps(params)]


def run_child(phase: str, params: dict, work: str, timeout: float) -> dict:
    """Run one phase in a process of its own (it owns the chip until it
    exits); its stdout is ours, its result comes back through a file."""
    result_path = os.path.join(work, f"{phase}.result.json")
    params = dict(params, result_path=result_path)
    t0 = time.monotonic()
    proc = subprocess.run(_child_argv(phase, params), cwd=HERE,
                          env=_child_env(), timeout=timeout)
    if proc.returncode != 0 or not os.path.exists(result_path):
        raise PhaseFailed(f"phase {phase!r} exited {proc.returncode}")
    with open(result_path) as f:
        result = json.load(f)
    result["wall_s"] = time.monotonic() - t0
    return result


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str, timeout: float = 5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read()


def _post_pair(url: str, npz_path: str):
    import numpy as np

    with open(npz_path, "rb") as f:
        body = f.read()
    req = urllib.request.Request(
        url + "/v1/disparity", data=body,
        headers={"Content-Type": "application/x-npz"})
    t0 = time.monotonic()
    with urllib.request.urlopen(req, timeout=120) as r:
        payload = r.read()
        batch = int(r.headers["X-Batch-Size"])
        device_ms = float(r.headers["X-Device-Ms"])
    return (np.load(io.BytesIO(payload)), batch, device_ms,
            (time.monotonic() - t0) * 1e3)


def _metric(text: str, name: str, label: str = "") -> float:
    """Sum of the samples of ``name`` whose label set contains ``label``."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith("#") or not line.startswith(name):
            continue
        head, _, value = line.rpartition(" ")
        if head.split("{")[0] != name or label not in head:
            continue
        total += float(value)
        seen = True
    if not seen:
        raise PhaseFailed(f"/metrics has no sample of {name} {label}")
    return total


class _Server:
    """One ``raft-serve`` process, started through its normal entry (the
    ``serve`` child phase), with its output in a log file."""

    def __init__(self, work: str, tag: str, port: int):
        self.tag = tag
        self.url = f"http://127.0.0.1:{port}"
        self.log_path = os.path.join(work, f"serve_{tag}.log")
        self.result_path = os.path.join(work, f"serve_{tag}.result.json")
        argv = ["--restore_ckpt", os.path.join(work, "ckpt"),
                "--valid_iters", str(SERVE_ITERS),
                "--warmup_shape", f"{SERVE_HW[0]}x{SERVE_HW[1]}",
                "--batch_sizes", SERVE_BATCHES, "--port", str(port)]
        self._log_f = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            _child_argv("serve", {"argv": argv,
                                  "result_path": self.result_path}),
            cwd=HERE, env=_child_env(), stdout=self._log_f,
            stderr=subprocess.STDOUT)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if self.proc.poll() is None:      # a failure above left it running
            self.proc.kill()
            self.proc.wait()
        self._log_f.close()

    def wait_ready(self, timeout: float) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            if self.proc.poll() is not None:
                raise PhaseFailed(f"{self.tag} server exited "
                                  f"{self.proc.returncode} before /readyz "
                                  f"answered 200")
            try:
                if _get(self.url + "/readyz")[0] == 200:
                    return time.monotonic() - t0
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(1.0)
        raise PhaseFailed(f"/readyz not 200 within {timeout:.0f}s")

    def stop(self) -> dict:
        """SIGTERM; a clean drain and exit 0, or the phase has failed."""
        self.proc.send_signal(signal.SIGTERM)
        rc = self.proc.wait(timeout=120)
        with open(self.log_path, errors="replace") as f:
            log_text = f.read()
        if rc != 0 or "drain complete" not in log_text:
            sys.stderr.write(log_text[-6000:])
            raise PhaseFailed(f"{self.tag} server: exit {rc}, 'drain "
                              f"complete' in its log: "
                              f"{'drain complete' in log_text}")
        with open(self.result_path) as f:
            result = json.load(f)
        result["log"] = log_text
        return result


def serve_phase(work: str, seed: int) -> None:
    """Phase 1: the server, its answers, and what they are compared with."""
    import numpy as np

    t_phase = time.monotonic()
    port = _free_port()
    url = f"http://127.0.0.1:{port}"

    # ---- cold boot: every bucket executable is compiled
    with _Server(work, "cold", port) as server:
        ready_cold = server.wait_ready(timeout=900)
        say(f"serve: /readyz 200 after {ready_cold:.1f}s (cold: "
            f"{SERVE_HW[0]}x{SERVE_HW[1]}, {SERVE_ITERS} iters, batch "
            f"ladder {SERVE_BATCHES}, default tiers)")
        answers = []   # (pair index, batch size, file with the answer)
        lat_ms, dev_ms = [], []

        def post(k, slot):
            disp, batch, d_ms, ms = _post_pair(
                url, os.path.join(work, f"pair{k}.npz"))
            path = os.path.join(work, f"answer_{slot}.npy")
            np.save(path, disp)
            answers.append((k, batch, path))
            lat_ms.append(ms)
            dev_ms.append(d_ms)

        post(0, 0)                      # one request alone: batch 1
        for k in range(N_PAIRS):        # bursts of the SAME pair: whichever
            threads = [                 # requests share a dispatch, the
                threading.Thread(       # batch-2 program saw [pair k, pair k]
                    target=post, args=(k, 1 + k * BURST + i))
                for i in range(BURST)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
        n_sent = 1 + N_PAIRS * BURST
        if len(answers) != n_sent:
            raise PhaseFailed(f"{len(answers)} of {n_sent} requests "
                              f"answered")
        metrics = _get(url + "/metrics")[1].decode()
        compiles = json.loads(_get(url + "/debug/compiles")[1])
        cold = server.stop()

    n_b2 = sum(1 for _, b, _ in answers if b == 2)
    say(f"serve: {len(answers)} requests answered, {n_b2} of them in "
        f"batch-2 dispatches; latency ms min/median/max "
        f"{min(lat_ms):.1f}/{sorted(lat_ms)[len(lat_ms) // 2]:.1f}/"
        f"{max(lat_ms):.1f}; device ms min/max "
        f"{min(dev_ms):.1f}/{max(dev_ms):.1f}")
    say(f"serve: /metrics dispatches batch=1 "
        f"{_metric(metrics, 'serve_dispatches_total', 'batch=\"1\"'):.0f}, "
        f"batch=2 "
        f"{_metric(metrics, 'serve_dispatches_total', 'batch=\"2\"'):.0f}; "
        f"cold compiles "
        f"{_metric(metrics, 'serve_compiles_cold_total'):.0f}; "
        f"/debug/compiles lists {compiles['count']} executables, "
        f"{compiles['total_compile_s']:.1f}s of compile")
    if n_b2 == 0 or _metric(metrics, "serve_dispatches_total",
                            'batch="2"') < 1:
        raise PhaseFailed("no batch-2 dispatch happened")
    if _metric(metrics, "serve_aot_compile_failures_total") != 0:
        raise PhaseFailed("the engine fell back from an AOT compile")
    for line in sorted({ln.split("kernel path: ", 1)[1]
                        for ln in cold["log"].splitlines()
                        if "kernel path: " in ln}):
        say(f"serve: kernel path: {line}")
    # memory_stats' counter: on the v5e runtime it leaves program temp out
    # (PERF.md), so it is printed under its own name, not as a peak.
    say(f"serve: clean SIGTERM drain, exit 0; peak_bytes_in_use "
        f"{cold['peak_hbm_bytes'] / 1e9:.2f}e9 B; persistent cache "
        f"hits {cold['cache_hits']} misses {cold['cache_misses']} "
        f"(cache at {cold['cache_dir']})")

    # ---- warm boot: the same programs, read back from jax's cache
    with _Server(work, "warm", port) as server:
        ready_warm = server.wait_ready(timeout=600)
        compiles_warm = json.loads(_get(url + "/debug/compiles")[1])
        warm = server.stop()
    say(f"serve: second boot /readyz 200 after {ready_warm:.1f}s against "
        f"{ready_cold:.1f}s, {compiles_warm['total_compile_s']:.1f}s in "
        f"its {compiles_warm['count']} compile calls against "
        f"{compiles['total_compile_s']:.1f}s; persistent cache hits "
        f"{warm['cache_hits']} misses {warm['cache_misses']}")
    # Every program the first boot had to compile, the second read back
    # (a machine that came with a warm cache shows hits on both boots).
    if (warm["cache_misses"] or warm["cache_hits"] < max(
            1, cold["cache_misses"])
            or (cold["cache_misses"] and ready_warm >= ready_cold)):
        raise PhaseFailed("the second boot did not come from the "
                          "persistent compilation cache")

    # ---- what the answers are compared with (the server has exited)
    cmp_ = run_child("compare", {
        "work": work, "seed": seed,
        "answers": [[k, b, p] for k, b, p in answers]}, work, timeout=900)
    say(f"serve phase: {time.monotonic() - t_phase:.1f}s wall (compare "
        f"child {cmp_['wall_s']:.1f}s, its compiles "
        f"{cmp_['compile_s']:.1f}s, cache hits {cmp_['cache_hits']})")


def train_phase(work: str) -> None:
    """Phase 2: the trainer."""
    r = run_child("train", {"work": work}, work, timeout=900)
    say(f"train phase: {r['wall_s']:.1f}s wall; cli.train.main "
        f"{r['train_s']:.1f}s of which the first step (compile) "
        f"{r['first_step_s']:.1f}s; peak_bytes_in_use "
        f"{r['peak_hbm_bytes'] / 1e9:.2f}e9 B; persistent cache hits "
        f"{r['cache_hits']} misses {r['cache_misses']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the four-chip data-parallel training "
                         "step and the one-device step it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t0 = time.monotonic()
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        want = 4 if args.four_chips else 1
        # The first child is the only one that may say "no": it looks at
        # jax.devices() and makes everything else from the seed.
        setup = run_child("setup", {"work": work, "seed": args.seed,
                                    "want_devices": want,
                                    "serve": not args.four_chips},
                          work, timeout=600)
        device = setup["device"]
        say(f"device: {device['kind']} x{device['count']}; native decoders "
            f"in use: {setup['native']}; setup {setup['wall_s']:.1f}s")
        if args.four_chips:
            r = run_child("four_chips", {"work": work, "seed": args.seed},
                          work, timeout=3000)
            say(f"four-chip phase: {r['wall_s']:.1f}s wall, compiles "
                f"{r['compile_s']:.1f}s")
        else:
            serve_phase(work, args.seed)
            train_phase(work)
        say(f"chip_smoke: all phases passed in {time.monotonic() - t0:.1f}s")
    except (PhaseFailed, subprocess.TimeoutExpired) as e:
        sys.stderr.write(f"chip_smoke FAILED: {e}\n")
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# ===================================================================== children
class _CacheEvents:
    """Counts jax's persistent-compilation-cache hits and misses in this
    process (``jax.monitoring`` events)."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def child_main() -> None:
    """Entry of every child: ``python -c "import chip_smoke;
    chip_smoke.child_main()" <phase> <json params>``."""
    phase, params = sys.argv[1], json.loads(sys.argv[2])
    import jax

    from raft_stereo_tpu.cli import common
    from raft_stereo_tpu.profiling import (device_memory_stats,
                                           setup_compilation_cache)

    common.setup_logging()
    logging.getLogger("absl").setLevel(logging.WARNING)   # orbax chatter
    cache = _CacheEvents()
    cache_dir = setup_compilation_cache()
    result = _PHASES[phase](params) or {}
    result.update(cache_hits=cache.hits, cache_misses=cache.misses,
                  cache_dir=cache_dir,
                  peak_hbm_bytes=max(
                      int(device_memory_stats(d).get("peak_bytes_in_use", 0))
                      for d in jax.local_devices()))
    with open(params["result_path"], "w") as f:
        json.dump(result, f)


def _device_or_die(want: int) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.stderr.write(f"chip_smoke needs a TPU; jax found "
                         f"{devices[0].platform!r}\n")
        sys.exit(3)
    if len(devices) != want:
        sys.stderr.write(f"chip_smoke wants {want} chip(s) here, jax found "
                         f"{len(devices)}\n")
        sys.exit(3)
    # An unknown device_kind is an error on this path, not a default.
    from raft_stereo_tpu.telemetry.costs import peak_flops_for
    peak_flops_for(devices[0].device_kind)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def kernel_launches(hlo_text: str) -> list:
    """The Mosaic kernel calls of a compiled executable's text."""
    return [ln for ln in hlo_text.splitlines()
            if "custom-call(" in ln and "tpu_custom_call" in ln]


def _lookup_operands(calls: list, rows: int, n: int, w4: int):
    """How many kernel calls carry one device's share of the finest
    correlation level, and how many the whole batch's."""
    return (sum(f"bf16[{rows},{w4},{w4}]" in ln for ln in calls),
            sum(f"bf16[{rows * n},{w4},{w4}]" in ln for ln in calls))


def _make_pair(rng, hw):
    """A textured left image and its right view under a smooth positive
    disparity (columns shifted by an integer field)."""
    import numpy as np

    h, w = hw
    coarse = rng.uniform(0, 255, (-(-h // 8), -(-w // 8), 3))
    left = np.kron(coarse, np.ones((8, 8, 1)))[:h, :w]
    left = np.clip(left + rng.integers(0, 40, (h, w, 3)), 0, 255)
    disp = (8 + 24 * np.linspace(0, 1, h))[:, None].astype(int)
    cols = np.clip(np.arange(w)[None, :] + disp, 0, w - 1)
    right = np.take_along_axis(left, cols[:, :, None].repeat(3, 2), axis=1)
    return left.astype(np.uint8), right.astype(np.uint8)


def phase_setup(p: dict) -> dict:
    """Everything the run needs, made from the seed: weights, image pairs,
    a SceneFlow-layout tree.  Nothing comes from outside the checkout."""
    device = _device_or_die(p["want_devices"])
    import jax
    import numpy as np

    from raft_stereo_tpu import native
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.training.checkpoint import save_weights
    from raft_stereo_tpu.training.state import init_model_variables

    work, seed = p["work"], p["seed"]
    if p["serve"]:
        cfg = RaftStereoConfig()
        variables = init_model_variables(cfg, jax.random.PRNGKey(seed))
        save_weights(os.path.join(work, "ckpt"), cfg, variables["params"],
                     batch_stats=variables.get("batch_stats"))
        rng = np.random.default_rng(seed)
        for k in range(N_PAIRS):
            left, right = _make_pair(rng, SERVE_HW)
            np.savez(os.path.join(work, f"pair{k}.npz"), left=left,
                     right=right)
        sys.path.insert(0, os.path.join(HERE, "tests"))
        from golden_data import build_tree
        build_tree(os.path.join(work, "datasets"), n_pairs=8, seed=seed)
    return {"device": device, "native": bool(native.available())}


def phase_serve(p: dict) -> dict:
    """The server, through its normal entry; returns when it has drained."""
    from raft_stereo_tpu.cli import serve

    rc = serve.main(p["argv"])
    if rc:
        sys.exit(rc)
    return {}


def _lookup_vs_float64(seed: int) -> None:
    """The correlation lookup at the served 1/4-res shape on a random
    pyramid: the Pallas kernel and the XLA sampler, each against a float64
    NumPy interpolation (zero outside [0, W-1], like both)."""
    import jax.numpy as jnp
    import numpy as np

    from raft_stereo_tpu.kernels.corr_lookup import lookup_pyramid_fused
    from raft_stereo_tpu.models.corr import (build_corr_pyramid,
                                             lookup_pyramid_xla)

    radius, levels = 4, 4
    h, w = (-(-d // 32) * 32 // 4 for d in SERVE_HW)
    rng = np.random.default_rng(seed)
    vol = rng.standard_normal((1, h, w, w)).astype(np.float32)
    coords = rng.uniform(-4, w + 4, (1, h, w)).astype(np.float32)
    pyramid = build_corr_pyramid(jnp.asarray(vol), levels)

    want = []
    for i, level in enumerate(np.asarray(v, np.float64) for v in pyramid):
        x = (coords.astype(np.float64) / 2 ** i)[..., None] \
            + np.arange(-radius, radius + 1)
        x0 = np.floor(x)
        taps = 0.0
        for idx, weight in ((x0, 1.0 - (x - x0)), (x0 + 1, x - x0)):
            inside = (idx >= 0) & (idx <= level.shape[-1] - 1)
            safe = np.clip(idx, 0, level.shape[-1] - 1).astype(np.int64)
            taps = taps + np.where(
                inside, np.take_along_axis(level, safe, axis=-1), 0.0
            ) * weight
        want.append(taps)
    want = np.concatenate(want, axis=-1)
    scale = float(np.abs(want).max())
    # the kernel reads its levels transposed, (B, H, W2_i, W1)
    err = {name: float(np.abs(np.asarray(fn(pyr, jnp.asarray(coords),
                                           radius), np.float64)
                              - want).max())
           for name, fn, pyr in (
               ("kernel", lookup_pyramid_fused,
                [jnp.swapaxes(v, -1, -2) for v in pyramid]),
               ("XLA sampler", lookup_pyramid_xla, pyramid))}
    say(f"compare: lookup alone at {h}x{w}, {levels} levels, fp32, vs "
        f"float64 NumPy (largest sample {scale:.2f}): "
        + ", ".join(f"{k} max |err| {v:.3g}" for k, v in err.items())
        + f" (kernel bound {LOOKUP_VS_FLOAT64_RTOL * scale:.3g})")
    if not err["kernel"] <= LOOKUP_VS_FLOAT64_RTOL * scale:
        raise PhaseFailed("the lookup kernel disagrees with float64 NumPy")


def phase_compare(p: dict) -> dict:
    """Solo inference on the same weights and pairs: bitwise against the
    server's answers; kernels against pure XLA; the kernel in the text."""
    import dataclasses

    import numpy as np

    from raft_stereo_tpu.cli.common import load_any_checkpoint
    from raft_stereo_tpu.eval.runner import InferenceRunner, make_forward
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    work = p["work"]
    cfg, variables = load_any_checkpoint(os.path.join(work, "ckpt"))
    pairs = {}
    for k in sorted({k for k, _, _ in p["answers"]}):
        with np.load(os.path.join(work, f"pair{k}.npz")) as z:
            pairs[k] = (z["left"], z["right"])
    t_compile = 0.0

    # ---- engine == solo, bitwise: same program, same weights, same pair
    runner = InferenceRunner(cfg, variables, iters=SERVE_ITERS)
    solo = {}
    worst = 0.0
    for k, batch, path in p["answers"]:
        if (k, batch) not in solo:
            left, right = pairs[k]
            t0 = time.monotonic()
            if batch == 1:
                flow, _ = runner(left, right)
            else:
                flows, _ = runner.run_batch([left] * batch, [right] * batch)
                flow = flows[0]
                if not all(np.array_equal(flow, f) for f in flows):
                    raise PhaseFailed("rows of one batch differ")
            t_compile = max(t_compile, time.monotonic() - t0)
            if not np.isfinite(flow).all() or flow.shape != SERVE_HW:
                raise PhaseFailed(f"solo flow {flow.shape} not finite")
            solo[(k, batch)] = -flow         # the wire carries +disparity
        got = np.load(path)
        if got.shape != SERVE_HW or got.dtype != np.float32:
            raise PhaseFailed(f"answer {path}: {got.dtype}{got.shape}")
        if not np.array_equal(got, solo[(k, batch)]):
            worst = max(worst, float(np.abs(got - solo[(k, batch)]).max()))
    if worst:
        raise PhaseFailed(f"server answers differ from solo inference by "
                          f"up to {worst} px (expected bitwise)")
    say(f"compare: {len(p['answers'])} answers == solo InferenceRunner, "
        f"bitwise (batch sizes {sorted({b for _, b, _ in p['answers']})}; "
        f"mean |disparity| {float(np.abs(solo[(0, 1)]).mean()):.2f} px)")

    # ---- the served program's text holds the Mosaic kernel
    left, right = pairs[0]
    img = np.zeros((1,) + tuple(-(-d // 32) * 32 for d in SERVE_HW) + (3,),
                   np.uint8)
    t0 = time.monotonic()
    text = make_forward(RAFTStereo(runner.config), SERVE_ITERS).lower(
        variables, img, img).compile().as_text()
    t_compile += time.monotonic() - t0
    n_kernels = len(kernel_launches(text))
    say(f"compare: served executable ({img.shape[1]}x{img.shape[2]}, b1, "
        f"{SERVE_ITERS} iters) "
        f"holds {n_kernels} tpu_custom_call kernel launches")
    if n_kernels == 0:
        raise PhaseFailed("no tpu_custom_call in the served executable: "
                          "the server answered through the XLA path")

    # ---- the lookup alone: kernel and XLA sampler vs float64 NumPy
    _lookup_vs_float64(p["seed"])

    # ---- kernels vs pure XLA at one iteration
    def flow_at_one_iteration(**overrides):
        nonlocal t_compile
        t0 = time.monotonic()
        r = InferenceRunner(dataclasses.replace(cfg, **overrides),
                            variables, iters=1)
        flow, _ = r(left, right)
        t_compile += time.monotonic() - t0
        return flow

    xla = flow_at_one_iteration(corr_backend="reg", fused_gru="off")
    lookup = flow_at_one_iteration(fused_gru="off")
    kernels = flow_at_one_iteration()
    scale = float(np.abs(xla).max())
    d_lookup = float(np.abs(lookup - xla).max())
    d_kernels = float(np.abs(kernels - xla).max())
    say(f"compare: 1 iteration, fp32, vs pure XLA (reg, fused_gru off), "
        f"max |flow| {scale:.2f} px: lookup kernel max |d| {d_lookup:.3g} "
        f"px, mean {float(np.abs(lookup - xla).mean()):.3g} (bound "
        f"{KERNELS_VS_XLA_RTOL * scale:.3g}); lookup + ConvGRU kernels max "
        f"|d| {d_kernels:.3g} px, mean "
        f"{float(np.abs(kernels - xla).mean()):.3g} (bound "
        f"{KERNELS_VS_XLA_RTOL * scale:.3g})")
    if not max(d_lookup, d_kernels) <= KERNELS_VS_XLA_RTOL * scale:
        raise PhaseFailed("kernels disagree with the pure-XLA program")
    return {"compile_s": t_compile}


def phase_train(p: dict) -> dict:
    """``raft-stereo-train`` with the published SceneFlow recipe."""
    import math

    import jax

    from raft_stereo_tpu.cli import train

    work = p["work"]
    batch, crop, steps = TRAIN_BATCH, TRAIN_CROP, TRAIN_STEPS
    port = _free_port()
    events_path = os.path.join(work, "train_events.jsonl")
    say(f"train: batch {batch} (the published {PUBLISHED_BATCH} does not "
        f"fit one 16 GB chip: its step compiles to 17.13e9 B of temp), "
        f"crop {crop[0]}x{crop[1]}, {TRAIN_ITERS} iterations, bf16, hidden "
        f"128 / fnet 256, {steps} steps, the CLI's loader (thread workers)")

    last_scrape = {}
    done = threading.Event()

    def scrape():
        while not done.is_set():
            try:
                last_scrape["text"] = _get(
                    f"http://127.0.0.1:{port}/metrics", 2.0)[1].decode()
            except (urllib.error.URLError, OSError):
                pass
            done.wait(0.25)

    poller = threading.Thread(target=scrape, daemon=True)
    poller.start()
    t0 = time.monotonic()
    try:
        state = train.main([
            "--name", "smoke", "--mixed_precision",
            "--batch_size", str(batch),
            "--image_size", str(crop[0]), str(crop[1]),
            "--train_iters", str(TRAIN_ITERS),
            "--num_steps", str(steps),
            "--validation_frequency", str(TRAIN_WINDOW),
            "--data_root", os.path.join(work, "datasets"),
            "--checkpoint_dir", os.path.join(work, "checkpoints"),
            "--log_dir", os.path.join(work, "runs"),
            "--metrics_port", str(port), "--event_log", events_path])
    finally:
        done.set()
        poller.join(timeout=5)
    train_s = time.monotonic() - t0
    jax.block_until_ready(state.params)

    with open(events_path) as f:
        events = [json.loads(ln) for ln in f if ln.strip()]
    windows = [(e["step"], e["means"]["loss"]) for e in events
               if e["event"] == "step_stats"]
    text = last_scrape.get("text")
    if text is None:
        raise PhaseFailed("the training /metrics endpoint never answered")
    recompiles = _metric(text, "train_recompiles_total")
    first_step_s = max((float(e["compile_s"]) for e in events
                        if e["event"] == "compile" and "compile_s" in e),
                       default=0.0)
    say(f"train: loss by {TRAIN_WINDOW}-step window "
        f"{[(s, round(v, 4)) for s, v in windows]}; "
        f"train_recompiles_total {recompiles:.0f}; steps "
        f"{_metric(text, 'train_steps_total'):.0f}; checkpoints "
        f"{_metric(text, 'train_checkpoints_total'):.0f}")
    if len(windows) < 2 or not all(math.isfinite(v) for _, v in windows):
        raise PhaseFailed(f"loss windows {windows}")
    # "Decreasing or stable": six steps of an untrained 22-iteration GRU
    # on different crops are noisy, a divergence is not subtle.
    if windows[-1][1] > 2.0 * windows[0][1]:
        raise PhaseFailed(f"loss doubled: {windows}")
    if recompiles != 0:
        raise PhaseFailed(f"train_recompiles_total {recompiles}")
    if int(state.step) != steps:
        raise PhaseFailed(f"state.step {int(state.step)} != {steps}")
    ckpt = os.path.join(work, "checkpoints", "smoke")
    if not os.path.isdir(ckpt) or not os.listdir(ckpt):
        raise PhaseFailed(f"no checkpoint at {ckpt}")
    say(f"train: checkpoint written ({sorted(os.listdir(os.path.join(work, 'checkpoints')))})")
    return {"train_s": train_s, "first_step_s": first_step_s}


def phase_four_chips(p: dict) -> dict:
    """One data-parallel training step over a 4-device ``data`` mesh
    against the same batch and init on one device, then the published
    batch-8 step on the mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
    from raft_stereo_tpu.parallel.mesh import (make_mesh, replicate,
                                               shard_batch)
    from raft_stereo_tpu.training.state import create_train_state
    from raft_stereo_tpu.training.step import make_train_step

    n = _device_or_die(4)["count"]
    h, w = TRAIN_CROP
    iters = TRAIN_ITERS
    model_cfg = RaftStereoConfig(mixed_precision=True)
    mesh = make_mesh(n_data=n)
    t_compile = 0.0

    def host_batch(b, seed):
        rng = np.random.default_rng(seed)
        return {
            "image1": rng.integers(0, 255, (b, h, w, 3), dtype=np.uint8),
            "image2": rng.integers(0, 255, (b, h, w, 3), dtype=np.uint8),
            "flow": rng.uniform(-8, 0, (b, h, w)).astype(np.float32),
            "valid": np.ones((b, h, w), np.float32)}

    def fresh_state(train_cfg):
        return create_train_state(model_cfg, train_cfg,
                                  jax.random.PRNGKey(p["seed"]),
                                  image_shape=(1, h, w, 3))

    def update_norm(s0, s1):
        return float(jnp.sqrt(sum(
            jnp.sum((jnp.asarray(a, jnp.float32)
                     - jnp.asarray(b, jnp.float32)) ** 2)
            for a, b in zip(jax.tree_util.tree_leaves(s0.params),
                            jax.tree_util.tree_leaves(s1.params),
                            strict=True))))

    def mesh_step(b, seed):
        """One step of batch ``b`` over the mesh; checks where the batch
        lives and what each device's kernel calls see."""
        nonlocal t_compile
        train_cfg = TrainConfig(batch_size=b, image_size=(h, w),
                                train_iters=iters, data_parallel=n)
        state = replicate(fresh_state(train_cfg), mesh)
        batch = shard_batch(host_batch(b, seed), mesh)
        for name, x in batch.items():
            on = {d.id for d in x.sharding.device_set}
            shard = x.addressable_shards[0].data.shape
            if len(on) != n or shard[0] != b // n:
                raise PhaseFailed(f"batch[{name}] lives on {len(on)} "
                                  f"devices in shards {shard}")
        t0 = time.monotonic()
        compiled = make_train_step(train_cfg, mesh=mesh,
                                   donate=False).lower(state, batch).compile()
        t_compile += time.monotonic() - t0
        text = compiled.as_text()
        calls = kernel_launches(text)
        rows = (b // n) * (h // 4)         # one device's 1/4-res rows
        w4 = w // 4
        own, whole = _lookup_operands(calls, rows, n, w4)
        say(f"four-chip: batch {b}: {len(calls)} kernel launches per "
            f"device; lookup operands bf16[{rows},{w4},{w4}] (this "
            f"device's quarter of the {rows * n} rows) in {own}, the "
            f"whole batch in {whole}; all-gathers in the program: "
            f"{text.count('all-gather')}; per-device temp "
            f"{compiled.memory_analysis().temp_size_in_bytes / 1e9:.2f}e9 B")
        if not own or whole:
            raise PhaseFailed("kernel operands are not a quarter of the "
                              "rows on each device")
        t0 = time.monotonic()
        new_state, metrics = compiled(state, batch)
        metrics = jax.device_get(metrics)
        step_s = time.monotonic() - t0
        if not np.isfinite(float(metrics["loss"])):
            raise PhaseFailed(f"non-finite loss at batch {b}")
        return state, new_state, metrics, step_s

    # ---- parity: the largest batch the one-device reference can hold
    b = TRAIN_BATCH
    state, new_state, m, step_s = mesh_step(b, seed=p["seed"])
    train_cfg = TrainConfig(batch_size=b, image_size=(h, w),
                            train_iters=iters)
    state_ref = fresh_state(train_cfg)
    same_batch = host_batch(b, p["seed"])
    t0 = time.monotonic()
    ref_step = make_train_step(train_cfg, mesh=None, donate=False).lower(
        state_ref, same_batch).compile()
    t_compile += time.monotonic() - t0
    ref_state, m_ref = ref_step(state_ref, same_batch)
    m_ref = jax.device_get(m_ref)
    pairs = {
        "loss": (float(m["loss"]), float(m_ref["loss"])),
        "epe": (float(m["epe"]), float(m_ref["epe"])),
        "grad_norm": (float(m["grad_norm"]), float(m_ref["grad_norm"])),
        "update_norm": (update_norm(state, new_state),
                        update_norm(state_ref, ref_state))}
    say(f"four-chip: batch {b}, {iters} iterations, bf16: mesh vs one "
        f"device " + "; ".join(
            f"{k} {a:.6g} vs {r:.6g} (rel {abs(a - r) / max(abs(r), 1e-12):.2e})"
            for k, (a, r) in pairs.items())
        + f"; tolerance {MESH_PARITY_RTOL}; mesh step (first run) "
          f"{step_s:.2f}s")
    bad = {k: v for k, v in pairs.items()
           if not abs(v[0] - v[1]) <= MESH_PARITY_RTOL * max(abs(v[1]),
                                                             1e-12)}
    if bad:
        raise PhaseFailed(f"mesh step disagrees with one device: {bad}")
    del state, new_state, state_ref, ref_state, ref_step

    # ---- the published recipe's batch, 2 per chip
    b = PUBLISHED_BATCH
    _, _, m, step_s = mesh_step(b, seed=p["seed"] + 1)
    say(f"four-chip: published batch {b} ({b // n} per chip): loss "
        f"{float(m['loss']):.6g}, epe {float(m['epe']):.6g}, step (first "
        f"run) {step_s:.2f}s")
    return {"compile_s": t_compile}


_PHASES = {"setup": phase_setup, "serve": phase_serve,
           "compare": phase_compare, "train": phase_train,
           "four_chips": phase_four_chips}


if __name__ == "__main__":
    sys.exit(main())
