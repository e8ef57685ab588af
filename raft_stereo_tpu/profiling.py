"""Profiling & timing subsystem.

The reference has no profiler — only ad-hoc wall-clock timing inside its
KITTI validator with a 50-image warmup discard (reference:
evaluate_stereo.py:77-82,105-107).  This module makes both first-class:

* ``trace(log_dir)`` — XLA/TPU profiler traces viewable in TensorBoard or
  Perfetto (``jax.profiler``), covering device kernels, HBM transfers, and
  host dispatch.
* ``annotate(name)`` — named host spans that show up inside traces; wrap
  pipeline stages (decode, augment, device step) to see overlap.
* ``FpsProtocol`` — the reference's FPS measurement protocol (warmup
  discard, per-image wall time), which the evaluation runner reports with.
  What decides a PR's speed is ``benchmark/`` (``PERF.md``).
"""

from __future__ import annotations

import contextlib
import os
import time
from dataclasses import dataclass
from typing import Callable, Iterable, List, Optional, Tuple

import jax
import numpy as np


COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def setup_compilation_cache() -> str:
    """The one rule for where jax's persistent compilation cache lives;
    returns the directory in use.

    With ``JAX_COMPILATION_CACHE_DIR`` set, jax reads it itself and no
    code names another directory.  Otherwise the cache goes to
    ``<checkout>/.jax_cache`` — a FIXED path: a directory named after a
    temp file, a pid or the time is never found again by the next process.
    Every CLI, tool and smoke calls this before its first compile
    (a whole test-mode forward at published widths costs the v5e compiler
    30-40 s, the training step 100 s and more)."""
    from_env = os.environ.get(COMPILE_CACHE_ENV)
    if from_env:
        return from_env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def trace(log_dir: str = "profiles", host_tracer_level: int = 2):
    """Capture a profiler trace into ``log_dir`` for the duration of the
    block (TensorBoard ``profile`` plugin or Perfetto reads it)."""
    os.makedirs(log_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


# The layer scopes of the device path, each one ``annotate`` name.  Every
# operation the forwards and the training step stage with device cost sits
# under one of them (tests/test_layer_scopes.py); the benchmark joins each
# traced operation to its scope through the HLO the trace carries
# (benchmark/layer_scopes.py, which keeps its own copy of this list).
LAYERS = (
    "prep",          # input normalisation (and photometric jitter) at the head
    "fnet",          # RAFT-Stereo's feature encoder
    "cnet",          # the context encoder, its tanh and context-bias convs
    "features",      # IGEV-Stereo's MobileNetV2 trunk and match descriptor
    "gev",           # IGEV-Stereo's 3-D stage: volume, hourglass, regression
    "corr_pyramid",  # RAFT-Stereo's correlation volume and its pyramid
    "gru_iter",      # the refinement loop: its set-up, loop and iterations
    "upsample",      # the upsampling to full resolution after the loop
    "confidence",    # the confidence maps (``return_confidence``)
    "loss",          # the training step's sequence loss
    "optimizer",     # gradient norm, clip, schedule and the AdamW update
)


@contextlib.contextmanager
def annotate(name: str):
    """Named span context; nests.

    Two effects, one name: a host-timeline span (``TraceAnnotation``) for
    code that RUNS inside the block, and — because model code is traced,
    not run — an XLA op-name scope (``jax.named_scope``) so every op staged
    out inside the block carries ``name/`` in its metadata.  On the device
    path the names are ``LAYERS``.  A scope never becomes the innermost one
    of a Mosaic call it did not name before: the TPU names such a call after
    its innermost scope (``%gru_iter.N``, ``%gru16.N``), and the
    benchmark's kernel patterns match those names."""
    with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
        yield


def device_memory_stats(device: Optional[jax.Device] = None) -> dict:
    """Live/peak bytes on ``device`` (default: first device); {} if the
    backend doesn't report memory stats (e.g. CPU)."""
    d = device or jax.devices()[0]
    stats = getattr(d, "memory_stats", lambda: None)()
    return dict(stats) if stats else {}


def device_hbm_bytes(fallback: int = 16 * 2 ** 30) -> int:
    """Accelerator memory capacity; ``fallback`` when the backend doesn't
    report one (CPU test runs).  Basis for the memory-derived full-res
    gates (models/raft_stereo.sequential_fnet_threshold,
    models/banded.default_band_rows)."""
    try:
        limit = int(device_memory_stats().get("bytes_limit", 0))
    except Exception:  # pragma: no cover - backend without device queries
        limit = 0
    return limit if limit > 0 else fallback


@dataclass
class FpsResult:
    fps: float
    mean_s: float
    per_image_s: List[float]
    n_timed: int

    def __str__(self):
        return f"{self.fps:.2f} fps (mean {self.mean_s * 1e3:.2f} ms over " \
               f"{self.n_timed} images)"


class FpsProtocol:
    """The reference's KITTI FPS protocol (evaluate_stereo.py:77-82,105-107):
    run ``fn`` per image, discard the first ``warmup`` timings (absorbing
    XLA compilation the way the reference absorbs cuDNN autotune), report
    1/mean of the rest."""

    def __init__(self, warmup: int = 50):
        self.warmup = warmup

    def measure(self, fn: Callable[..., object],
                inputs: Iterable[Tuple]) -> FpsResult:
        times: List[float] = []
        n = 0
        for args in inputs:
            t0 = time.perf_counter()
            out = fn(*args)
            # The stop clock is the result on the host.  device_get is a
            # no-op on the NumPy outputs of callables that already fetch
            # (e.g. eval.runner.InferenceRunner).
            jax.device_get(out)
            elapsed = time.perf_counter() - t0
            n += 1
            if n > self.warmup:
                times.append(elapsed)
        if not times:
            raise ValueError(
                f"need more than warmup={self.warmup} inputs, got {n}")
        mean = float(np.mean(times))
        return FpsResult(fps=1.0 / mean, mean_s=mean, per_image_s=times,
                         n_timed=len(times))
