"""The batch-N serving engine: compile cache, scheduler, and cost
telemetry in one place.

This replaced the round-6 split of ``StereoService`` + ``MicroBatcher`` +
per-worker ``InferenceRunner``.  That stack could not beat solo
inference by design: its default "chain" mode dispatched
the batch-1 program serially per request, its "stack" mode re-padded the
batch axis to the next power of two and lost more than it gained, and its
timed flush left the device idle while requests aged toward
``max_wait_ms``.  The engine fixes all three:

* **True batch-N bucket executables** — one compiled program per
  (padded shape, batch size) for the configured ``batch_sizes``
  (default 1/2/4/8), image buffers donated (``donate_argnums``), built by
  the same ``eval.runner.make_forward`` the solo runner uses — so the
  batch-1 bucket is **bitwise-equal** to solo inference by construction
  (the old chain mode survives as exactly that bucket).  Compiles route
  through the ``CompileRegistry`` AOT path when cost telemetry is on, and
  ``prewarm`` builds a shape's whole bucket ladder at boot.
* **Continuous batching** (`serving/batcher.py BucketQueue`) — no flush
  thread, no ``max_wait`` stall: an idle worker pops immediately and takes
  the largest compiled batch size the queue depth fills; a partial batch
  dispatches at the next size down (7 queued -> 4 + 2 + 1), never padded
  up.  Occupancy is set by queue pressure: below capacity every request
  dispatches the moment a worker frees (batch 1, minimum latency); at
  pressure the pops grab 4s and 8s.
* **Waste-driven bucket selection** (``BucketPolicy``) — the measured
  ``serve_padding_waste`` / ``serve_bucket_*_pixels_total`` accounting
  feeds back into the spatial padding policy: in adaptive mode shapes
  start at the coarsest pad grid (maximal executable reuse) and a bucket
  is refined toward the /32 floor once its observed waste fraction
  crosses ``max_padding_waste``.  The static /32 rule remains the default
  (the reference's padding semantics; parity tests require it).

Shutdown mirrors the train loop's preemption story
(training/train_loop.py): ``drain()`` refuses new work with the typed
``Overloaded``, lets the workers finish the queue, and only then stops
them.

Round 13 adds the failure story (docs/architecture.md §Resilience):

* **Supervised recovery** — a crashed dispatch no longer silently fails
  its whole batch: the requests requeue (ahead of fresh work, with
  exponential backoff) for bounded retries, the worker thread is
  restarted by the supervisor, and a request whose dispatch crashes
  ``max_dispatch_attempts`` times fails individually with the typed
  ``RequestPoisoned`` instead of retrying forever.  Every request
  admitted terminates — success or typed error, never silence.
* **Per-device circuit breakers** (serving/resilience.py) — K
  consecutive failures quarantine a device (its worker stops popping);
  after a cooldown one half-open probe batch decides whether it is back.
* **Brownout degradation** — sustained queue-saturation /
  deadline-miss pressure pushes eligible requests down the round-12
  tier ladder (quality -> balanced -> interactive) instead of shedding;
  hysteresis on restore.  Cheaper answers before no answers.
* **Fault injection** (serving/chaos.py, ``ServeConfig.chaos``) —
  deterministic seeded worker crashes / device OOM / latency / compile
  failures prove all of the above in scripts/chaos_smoke.py; off by
  default with the dispatch path bitwise-unchanged.
* **Persistent executable cache** (serving/persist.py,
  ``executable_cache_dir``) — compiled bucket executables serialize to
  disk keyed by (config, shape, batch, tier, backend fingerprint), so a
  restarted process prewarm is disk-bound, not compile-bound, and the
  ``ready`` gate (/readyz) opens in seconds.

Round 15 adds the int8 turbo tier and the per-session context cache
(docs/architecture.md §Quantization, §Streaming sessions):

* **Int8 tiers** — a tier with ``RequestTier.quant == "int8"`` (the
  "turbo" preset) compiles against the quantized variable tree
  (``_vars_for``: host-quantized once, device-put per worker; the fp32
  tree and every full-precision tier are untouched) with the int8
  correlation pyramid in its programs; its executables carry distinct
  compile-cost keys (``...,quant=int8``) and persistent-cache keys, join
  prewarm + /readyz, and sort to the BOTTOM of the brownout cost ladder.
* **Session ctx cache** (``session_ctx_cache``) — static-camera streams
  reuse the session's cnet context bundle: cold frames run the
  ``state_ctx`` family (also returns the bundle), coherent warm frames
  run ``warm_ctx`` (the context encoder never executes); invalidated by
  scene cuts, the keyframe guard, and any frame past the
  ``ctx_cache_threshold`` static-scene gate.

Round 16 makes the whole PROCESS a routine fault domain
(serving/fleet/, docs/architecture.md §Fleet): ``begin_shutdown`` is
the graceful-SIGTERM readiness flip the fleet router keys off,
``set_brownout_floor`` applies the router's fleet-wide degradation
level, the executable cache is a shareable content-addressed artifact
store with max-bytes GC (tools/compile_farm.py populates it once for
every replica), and a crashed dispatch carrying a SESSION frame demotes
its requeue to a cold start + invalidates the session's warm state
(``_invalidate_crashed_session_frame``) so no frame chains across a
crash gap.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import logging
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from raft_stereo_tpu.config import (RaftStereoConfig, RequestTier,
                                    parse_tier)
from raft_stereo_tpu.eval.runner import (early_exit_enabled,
                                         effective_inference_config,
                                         make_forward, make_forward_mesh)
from raft_stereo_tpu.models.raft_stereo import RAFTStereo
from raft_stereo_tpu.ops.padding import InputPadder
from raft_stereo_tpu.serving.batcher import (BucketQueue, Overloaded,
                                             Request, RequestPoisoned,
                                             decompose_batch)
from raft_stereo_tpu.serving.chaos import ChaosConfig, ChaosInjector
from raft_stereo_tpu.serving.metrics import MetricsRegistry, ServingMetrics
from raft_stereo_tpu.serving.models import (ModelStore, ModelUnknown,
                                            RegisteredModel, model_coord,
                                            parse_model_spec)
from raft_stereo_tpu.serving.resilience import (CIRCUIT_CLOSED,
                                                BrownoutController,
                                                CircuitBreaker,
                                                circuit_state_name,
                                                cost_ladder)
from raft_stereo_tpu.serving.sessions import (SessionsDisabled, SessionStore,
                                              frame_delta, frame_thumbnail,
                                              handoff_session_ids,
                                              parse_handoff_blob)
from raft_stereo_tpu.telemetry.spans import Phases, SpanTracer, clock

log = logging.getLogger(__name__)

# The model's divisibility constraint: every pad grid must be a multiple
# of this, and the adaptive policy can never refine below it.
MODEL_DIVIS = 32

# Host phases of one dispatch, in the order the worker thread runs them, and
# of one request on its HTTP thread (spans ``serve.<name>``,
# docs/architecture.md "Tracing").
WORKER_PHASES = ("wait_work", "assemble", "upload", "execute", "fetch",
                 "account", "respond")
HTTP_PHASES = ("decode", "admission", "encode")

# Executable families a (bucket, batch, tier) compiles under
# (eval/runner.make_forward): the base sessionless program, the
# state-returning program session cold frames run (same math, one extra
# low-res output), and the warm program that also consumes a flow_init.
# The *_CTX variants (round 15, ``ServeConfig.session_ctx_cache``) add
# the per-session CONTEXT cache: cold frames run "state_ctx" (also
# returns the context bundle) and coherent warm frames run "warm_ctx"
# (consumes the bundle and SKIPS the context encoder — cnet is the
# dominant per-frame encoder cost at streaming shapes).
FAMILY_BASE = None
FAMILY_STATE = "state"
FAMILY_WARM = "warm"
FAMILY_STATE_CTX = "state_ctx"
FAMILY_WARM_CTX = "warm_ctx"
# The warm-h families (round 19, ``ServeConfig.session_hidden``): the
# ``_h`` variants additionally RETURN the multi-level GRU hidden-state
# tree (cold frames) and CONSUME it as an extra traced input (warm
# frames) — eval/runner.make_forward ``hidden_init``/``return_hidden``.
# Same surface pattern as flow_init (r14) and ctx_init (r15): distinct
# executable families with their own compile-cost and persist keys.
FAMILY_STATE_H = "state_h"
FAMILY_WARM_H = "warm_h"
FAMILY_STATE_CTX_H = "state_ctx_h"
FAMILY_WARM_CTX_H = "warm_ctx_h"
# The xl family (round 17): a fixed-depth base-arity program SHARDED over
# a rows/corr device-group mesh (eval/runner.make_forward_mesh) — one
# full-resolution pair answered by several devices.  Only xl device-group
# workers pop these groups (BucketQueue.pop ``want`` filter); executables
# carry distinct ",mesh=rowsN" compile-cost and persist keys.
FAMILY_XL = "xl"

# Families that consume a flow_init input / reuse a context bundle.
_WARM_FAMILIES = (FAMILY_WARM, FAMILY_WARM_CTX, FAMILY_WARM_H,
                  FAMILY_WARM_CTX_H)
# Hidden-tree plumbing (round 19): _H_IN consume the previous frame's
# hidden tree as a traced input; _H_OUT return this frame's final tree.
_H_IN_FAMILIES = (FAMILY_WARM_H, FAMILY_WARM_CTX_H)
_H_OUT_FAMILIES = (FAMILY_STATE_H, FAMILY_WARM_H, FAMILY_STATE_CTX_H,
                   FAMILY_WARM_CTX_H)
# Context-bundle plumbing: _CTX_SAVE also return the bundle (cold ctx
# frames), _CTX_REUSE consume it and skip the context encoder.
_CTX_SAVE_FAMILIES = (FAMILY_STATE_CTX, FAMILY_STATE_CTX_H)
_CTX_REUSE_FAMILIES = (FAMILY_WARM_CTX, FAMILY_WARM_CTX_H)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving knobs (model architecture stays in RaftStereoConfig)."""

    max_batch: int = 8           # occupancy ceiling per device dispatch
    # Batch sizes compiled per shape bucket; capped at max_batch, must
    # include 1 (the solo-parity bucket).  The scheduler dispatches the
    # largest size the queue depth fills and decomposes remainders
    # (7 queued -> 4+2+1) — the batch axis never carries filler frames.
    batch_sizes: Tuple[int, ...] = (1, 2, 4, 8)
    # RETIRED (round 11): the engine's continuous batching dispatches the
    # moment a worker is free, so there is no timed flush to bound.  The
    # field is accepted for compatibility and ignored.
    max_wait_ms: float = 0.0
    max_queue: int = 64          # admission bound; beyond it -> Overloaded
    data_parallel: int = 1       # device workers (<= local device count)
    iters: int = 32              # GRU iterations per request (the depth
    #                              CAP for early-exit tiers)
    # Named latency tiers (config.py REQUEST_TIERS / inline
    # "name:threshold_px[:min_iters]" specs): each tier is an early-exit
    # knob setting the engine compiles a SEPARATE bucket-executable family
    # for, and requests select one by name (HTTP ?tier= / X-Tier).  A tier
    # whose threshold is <= 0 ("quality") runs the fixed-depth program and
    # shares the base executables — the bitwise-parity bucket.  Empty
    # (default): no tiers, exactly the pre-tier engine.
    tiers: Tuple[str, ...] = ()
    # Tier for requests that name none; None = "quality" when configured,
    # else the first tier.  Ignored without tiers.
    default_tier: Optional[str] = None
    shape_bucket: Optional[int] = None   # static coarser-than-/32 pad grid
    # Waste-driven spatial bucket selection: start shapes at the coarsest
    # grid in bucket_grids and refine a bucket toward the /32 floor once
    # its measured padding-waste fraction exceeds max_padding_waste.
    # Off by default: the static /32 rule is the reference's padding
    # semantics and the bitwise parity tests require it.
    adaptive_buckets: bool = False
    bucket_grids: Tuple[int, ...] = (128, 64, 32)
    max_padding_waste: float = 0.10
    # Raw (H, W) shapes whose bucket ladder (all batch sizes) is compiled
    # at boot — cold-start work moved out of the first requests' path.
    # Also the READINESS target: /readyz reports ready only once every
    # (worker, bucket, batch, tier-family) entry of this surface has
    # dispatched once.
    warmup_shapes: Tuple[Tuple[int, int], ...] = ()
    # False: declare the warm surface (readiness gates on it) but let the
    # caller drive ``prewarm`` itself — the CLI does this so the HTTP
    # server answers /readyz "warming" DURING the warm-up and so compile
    # events land in the run-event log wired after construction.
    prewarm_on_init: bool = True
    max_cached_shapes: int = 16  # per-worker (bucket, batch) executables
    fetch_dtype: Optional[str] = None    # "fp16" | "bf16" half fetch
    default_deadline_ms: Optional[float] = None  # per-request override wins
    # Donate the image buffers to every bucket executable (and declare the
    # same on the solo runner): the runtime may reclaim/alias them the
    # moment the program consumes them.  Numerics-neutral (tested).
    donate_buffers: bool = True
    # Fraction of requests whose span tree is recorded (telemetry/spans.py:
    # admission -> queue -> dispatch -> fetch -> respond, exported as
    # Chrome trace JSON via GET /debug/spans).  0.0 (default) disables
    # tracing entirely — every span site takes the constant-time None exit.
    trace_sample_rate: float = 0.0
    # Compile-cost telemetry (telemetry/costs.py): route every bucket
    # compile through the AOT path so GET /debug/compiles lists each
    # executable's flops/bytes/memory and the MFU gauges get their flops
    # numerator.  False (default) keeps the plain jax.jit dispatch.
    cost_telemetry: bool = False
    # MFU denominator override (TFLOP/s); None = the auto table keyed by
    # the local device kind (costs.DEVICE_PEAK_TFLOPS).
    device_peak_tflops: Optional[float] = None
    # ---- Resilience (round 13; docs/architecture.md §Resilience) -------
    # Deterministic fault injection (serving/chaos.py).  None (default):
    # chaos off, the dispatch path is a single attribute check away from
    # the round-12 program — bitwise-unchanged, tested.
    chaos: Optional[ChaosConfig] = None
    # Supervised recovery: a request whose dispatch crashes requeues
    # (ahead of fresh work) until it has been attempted this many times,
    # then fails with the typed RequestPoisoned.  1 = no retries.
    max_dispatch_attempts: int = 2
    # Backoff before a crashed batch's requests re-enter the queue:
    # retry_backoff_ms * 2^(attempt-1), so a flapping device is not
    # hammered by its own bounce-backs.
    retry_backoff_ms: float = 20.0
    # Per-device circuit breaker: this many CONSECUTIVE dispatch failures
    # quarantine the device; after breaker_cooldown_s one half-open probe
    # batch decides recovery (serving/resilience.py).
    breaker_failures: int = 3
    breaker_cooldown_s: float = 1.0
    # Brownout degradation: under sustained queue-saturation or
    # deadline-miss pressure, push eligible requests down the tier
    # ladder (cheapest tier = highest early-exit threshold) instead of
    # shedding; restore with hysteresis.  Requires tiers.
    brownout: bool = False
    brownout_engage_fraction: float = 0.75
    brownout_engage_s: float = 0.5
    brownout_restore_fraction: float = 0.25
    brownout_restore_s: float = 2.0
    brownout_poll_s: float = 0.1
    # Tiers that must NEVER be degraded (the per-tier opt-out; clients
    # additionally opt out per request via submit(degradable=False) /
    # the X-No-Degrade header).
    brownout_exempt_tiers: Tuple[str, ...] = ()
    # Persistent AOT executable cache directory (serving/persist.py):
    # compiled bucket executables serialize here keyed by (config, shape,
    # batch, tier, executable family — warm programs have a different
    # arity — and backend fingerprint) so a restarted process prewarm
    # loads from disk instead of recompiling.  None (default) = off.
    # The directory may be SHARED fleet-wide (an NFS mount / synced
    # object store tools/compile_farm.py populated): keys are pure
    # content hashes, so replicas coordinate for free.
    executable_cache_dir: Optional[str] = None
    # Store bound: beyond this many bytes the least-recently-USED
    # entries are evicted (atime LRU; config / jax-fingerprint churn
    # ages out instead of growing without bound).  None = unbounded.
    executable_cache_max_bytes: Optional[int] = None
    # Replica role against a SHARED store: fetch warm artifacts but
    # never write (a misconfigured replica cannot pollute the fleet's
    # cache; the compile farm is the only writer).
    executable_cache_read_only: bool = False
    # ---- Streaming sessions (round 14; serving/sessions.py) ------------
    # Stateful video serving: POST /v1/stream/<id> frames warm-start the
    # GRU from the session's previous low-res disparity, so with an
    # early-exit tier the convergence gate stalls after a fraction of the
    # cold iterations.  False (default): no session store, no warm
    # executable families — the engine is exactly the stateless round-13
    # build (bitwise-pinned by tests/test_sessions.py).
    sessions: bool = False
    # Idle seconds before a session's state expires (typed 410 on the
    # next frame; the client must open a fresh session).
    session_ttl_s: float = 30.0
    # Live-session ceiling; beyond it the least-recently-used session is
    # evicted (410 on its next frame).
    session_capacity: int = 256
    # Scene-cut fallback: a frame whose mean |Δintensity| vs the previous
    # frame's thumbnail exceeds this (0..255 units) cold-starts instead
    # of warm-starting from a disparity field the cut invalidated.
    # <= 0 disables the check (every in-session frame warm-starts).
    scene_cut_threshold: float = 40.0
    # Keyframe guard: a WARM frame on an early-exit tier that runs to the
    # iteration cap never satisfied the convergence gate — its output may
    # be drifting (warm-start chains accumulate error when the GRU is
    # not contracting; seen on synthetic sequences), so its state is not
    # trusted and the NEXT frame cold-starts, re-seeding the chain from
    # a clean zero-init (the video-codec I-frame move).  Cold frames at
    # the cap stay trusted: that is the stateless baseline by
    # definition.  No effect on fixed-depth tiers (every frame runs the
    # cap there by construction).
    session_reseed_on_cap: bool = True
    # Hidden-state warm start (round 19): carry the multi-level GRU
    # hidden-state tree frame to frame alongside the disparity, so a
    # warm frame resumes the GRU's own trajectory instead of re-deriving
    # it from the context encoder (the half of RAFT's temporal state the
    # r14 flow-only warm start left cold — tight convergence gates
    # DIVERGED from cold-h warm starts on the synthetic sequences).  Swaps
    # the state/warm executable families for their ``_h`` variants (distinct
    # compile-cost + persist keys); the scene-cut fallback, keyframe
    # guard, and crash demotion invalidate the h-tree in lockstep with
    # the flow state.  False (default): the r14 flow-only families,
    # byte-for-byte.  Requires ``sessions``.
    session_hidden: bool = False
    # Per-session CONTEXT-feature cache (round 15): for streams whose
    # inter-frame thumbnail delta stays tiny (static camera), reuse the
    # session's cnet context bundle instead of re-encoding it every
    # frame — cold frames run the "state_ctx" family (also returns the
    # bundle), coherent warm frames run "warm_ctx" (consumes it; the
    # context encoder never executes).  Invalidated by scene cuts, the
    # keyframe guard, and any frame whose delta exceeds the gate below
    # (the bundle re-establishes at the next cold frame).  Requires
    # ``sessions``; unsupported with shared_backbone (fnet is computed
    # FROM the cnet trunk there).  Responses carry X-Ctx-Cached and
    # hits count into serve_session_ctx_cache_hits_total.
    session_ctx_cache: bool = False
    # Mean inter-frame |Δintensity| (0..255) at or below which a warm
    # frame may reuse the cached context.  Far below the scene-cut
    # threshold by design: context reuse assumes the SCENE is static,
    # not merely continuous.
    ctx_cache_threshold: float = 2.0
    # ---- EDF cross-session frame scheduler (round 19) ------------------
    # Deadline-aware pop policy (serving/batcher.py): requests carrying
    # a per-frame deadline are ordered earliest-deadline-first, and an
    # idle worker whose chosen group cannot yet fill the largest
    # compiled batch size WAITS a bounded slack — never more than
    # edf_max_slack_ms past the head frame's arrival, never closer to
    # the nearest deadline than the bucket's measured dispatch latency —
    # to deliberately coalesce N concurrent streams' frames into one
    # batch-N dispatch.  Deadline-less requests keep the immediate-pop
    # behavior either way; False (default) leaves the scheduler the
    # exact r11 continuous-batching pop (pinned by tests/test_edf.py).
    edf_scheduler: bool = False
    edf_max_slack_ms: float = 50.0
    # ---- Int8 turbo tier (round 15; quant/) ----------------------------
    # Checkpoint-adjacent calibration scale file (quant/calibrate.py):
    # when set, tiers on the int8 path compile with the calibrated
    # percentile-clipped correlation-pyramid scales instead of dynamic
    # in-graph max-abs scales.  None = dynamic scales.
    quant_scales_path: Optional[str] = None
    # ---- XL tier: mesh-sharded big-image serving (round 17) ------------
    # Mesh topology one xl worker's bucket executables shard over, e.g.
    # "rows=4" (image-row context parallelism through the WHOLE forward —
    # the validated rows_gru loop) or "rows=2,corr=2" (rows-sharded
    # encoders x disparity-sharded correlation volume).  One xl worker
    # owns rows*corr devices (parallel.distributed.device_groups,
    # allocated AFTER the data_parallel solo workers) and answers one
    # request with all of them — per-device HBM drops ~1/N
    # (a compiler's memory analysis on an earlier runtime: 141 GiB at
    # rows=1 -> 13.8 GiB/device at 16 ways; not re-measured on the v5e).
    # None (default): no xl tier; a replica whose device
    # count cannot supply the mesh SKIPS the tier with a typed log line
    # instead of failing at boot (compile-farm/fleet contract).  XL
    # programs are fixed-depth, full-precision, and stateless (no
    # sessions) — the early-exit/quant/warm knobs do not compose with
    # the sharded executors (config.py).
    xl_mesh: Optional[str] = None
    # Independent xl device groups (each of rows*corr devices).
    xl_workers: int = 1
    # Requests whose padded BUCKET exceeds this many pixels route to the
    # xl family automatically (clients can force any compatible request
    # with ?tier=xl).  Default ~2 MP: about where a 32-iteration
    # full-resolution pair stops being a sensible single-device dispatch
    # (PERF.md section 5: 1.76 s a 5.7 MP pair on one v5e; ROADMAP R3).
    xl_threshold_pixels: int = 2_000_000
    # The mesh's own ceiling: buckets past this many pixels exceed what
    # the declared device group can hold (size it from the mesh's
    # measured per-device HBM at your largest warm bucket), so they fall
    # through to halo-overlap tiling — "beyond any mesh still runs
    # through the same bucket engine".  None = the mesh takes
    # everything above the threshold.
    xl_max_pixels: Optional[int] = None
    # Batch ladder compiled per xl bucket; (1,) by default — megapixel
    # pairs are latency-bound, and the mesh already uses the devices.
    xl_batch_sizes: Tuple[int, ...] = (1,)
    # ---- Halo-overlap tiling fallback (serving/tiles.py) ---------------
    # Requests whose padded bucket exceeds this many pixels (and did not
    # take the xl route) are split into equal-height overlapping row
    # tiles, dispatched as ORDINARY bucket requests (tiles of one image
    # share a bucket and batch together — no new scheduler), and
    # stitched by center-crop; the measured tile disagreement lands in
    # serve_tile_seam_epe and on the result (``ServeResult.seam_epe``).
    # None (default): never tile.
    tile_threshold_pixels: Optional[int] = None
    # Owned rows per tile; each tile additionally carries tile_halo
    # context rows on both sides (the per-iteration receptive-field
    # margin the rows_gru halo-exchange contract sizes — tiling cannot
    # refresh halos mid-loop, so it over-provisions 4x and measures the
    # residual as seam error).
    tile_rows: int = 512
    tile_halo: int = 64
    # ---- Model registry (round 21; serving/models.py) ------------------
    # Registered model versions to load at boot from the artifact
    # store's models/ namespace: "name@version" specs (bare "name" =
    # the newest complete version).  Requests pick one with ?model= /
    # X-Model; each registered model carries its OWN RaftStereoConfig
    # and compiles its own executable ladder (distinct compile-cost,
    # persist, and dispatch-group keys — models never share a batch).
    # Empty (default): exactly today's single implicit constructor
    # model — every key, program, and wire byte unchanged.
    models: Tuple[str, ...] = ()
    # Root of the model store; defaults to executable_cache_dir (the
    # weights live NEXT to the executables they compile into).  Required
    # when ``models`` is non-empty or hot registration is wanted.
    model_store_dir: Optional[str] = None
    # The registered model unnamed requests run (the default pointer a
    # hot swap flips); None = the implicit constructor model.
    default_model: Optional[str] = None
    # ---- Quality observability (round 24; telemetry/quality.py) --------
    # Compile the ``return_confidence`` program variants: every non-xl
    # executable additionally returns the per-pixel confidence element
    # derived from the refinement loop's own convergence signals
    # (models/raft_stereo.py), results carry the unpadded full-res map +
    # its mean, and each answered request lands in the
    # serve_confidence{tier,model} histograms, the quality good/bad SLO
    # counters, and the PSI drift watchdog.  False (default): no
    # tracker, no new series, and every program / cost key / persist
    # key / wire byte stays identical to the pre-confidence build
    # (pinned by tests).
    confidence: bool = False
    # Mean confidence below which a request counts AGAINST the quality
    # SLO budget (serve_quality_bad_total) — the split a quality
    # BurnRateTracker burns on.
    confidence_floor: float = 0.5
    # PSI drift watchdog knobs (telemetry/quality.QualityDriftWatchdog):
    # the index threshold that fires the typed quality_drift anomaly
    # (0.25 = the classic "act" band), the healthy-reference sample
    # count frozen at warm-up, and the rolling recent-window length.
    quality_drift_threshold: float = 0.25
    quality_drift_reference: int = 256
    quality_drift_window: int = 128
    # Quality SLO objective: the fraction of requests that may fall
    # below the confidence floor before the quality error budget burns
    # (0.99 = 1% of answers may be low-confidence).  Burns on the same
    # multi-window machinery as availability (telemetry/slo.py,
    # dimension="quality").
    quality_availability: float = 0.99
    # Brownout victim selection (serving/resilience.py): requests whose
    # tier's recent rolling mean confidence sits below this are SPARED
    # from degradation — they already need the expensive program.  0.0
    # (default) keeps the unconditional ladder.  Requires confidence.
    brownout_spare_below: float = 0.0
    # ---- Confidence-gated cascade: the "auto" pseudo-tier --------------
    # Requests naming ?tier=auto run the DRAFT tier first (default: the
    # cheapest rung of the cost ladder, e.g. turbo) and escalate to the
    # ESCALATE tier (default: the most expensive rung, e.g. quality)
    # only when the draft's mean confidence falls below
    # cascade_threshold — "turbo drafts, quality verifies" (ROADMAP
    # item 2).  Oversized requests cascade per halo tile: only the
    # low-confidence tiles re-run expensive.  Requires ``confidence``
    # and at least two configured tiers.
    cascade: bool = False
    cascade_draft: Optional[str] = None
    cascade_escalate: Optional[str] = None
    cascade_threshold: float = 0.5

    def __post_init__(self):
        if self.data_parallel < 1:
            raise ValueError(f"data_parallel={self.data_parallel} must be "
                             f">= 1")
        if not 0.0 <= self.trace_sample_rate <= 1.0:
            raise ValueError(f"trace_sample_rate={self.trace_sample_rate} "
                             f"must be in [0, 1]")
        sizes = tuple(sorted(set(int(s) for s in self.batch_sizes)))
        if not sizes or sizes[0] < 1:
            raise ValueError(
                f"batch_sizes={self.batch_sizes} must be positive ints")
        if 1 not in sizes:
            raise ValueError(
                f"batch_sizes={self.batch_sizes} must include 1 (the "
                f"solo-parity bucket every partial batch bottoms out at)")
        if self.shape_bucket is not None and self.shape_bucket % MODEL_DIVIS:
            raise ValueError(
                f"shape_bucket={self.shape_bucket} must be a multiple of "
                f"the model's /{MODEL_DIVIS} divisibility requirement")
        if not 0.0 < self.max_padding_waste < 1.0:
            raise ValueError(f"max_padding_waste={self.max_padding_waste} "
                             f"must be in (0, 1)")
        if self.fetch_dtype not in (None, "fp16", "bf16"):
            raise ValueError(f"fetch_dtype={self.fetch_dtype!r}: use "
                             f"'fp16', 'bf16', or None (full fp32 fetch)")
        for g in self.bucket_grids:
            if g < MODEL_DIVIS or g % MODEL_DIVIS:
                raise ValueError(
                    f"bucket_grids={self.bucket_grids}: every grid must be "
                    f"a multiple of /{MODEL_DIVIS}")
        parsed = tuple(parse_tier(s) for s in self.tiers)  # raises on bad
        names = [t.name for t in parsed]
        if len(set(names)) != len(names):
            raise ValueError(f"tiers={self.tiers}: duplicate tier names")
        if self.default_tier is not None and self.default_tier not in names:
            raise ValueError(
                f"default_tier={self.default_tier!r} is not one of the "
                f"configured tiers {names}")
        if self.max_dispatch_attempts < 1:
            raise ValueError(f"max_dispatch_attempts="
                             f"{self.max_dispatch_attempts} must be >= 1")
        if self.retry_backoff_ms < 0:
            raise ValueError(f"retry_backoff_ms={self.retry_backoff_ms} "
                             f"must be >= 0")
        if self.breaker_failures < 1:
            raise ValueError(f"breaker_failures={self.breaker_failures} "
                             f"must be >= 1")
        if self.breaker_cooldown_s <= 0:
            raise ValueError(f"breaker_cooldown_s="
                             f"{self.breaker_cooldown_s} must be > 0")
        if self.brownout:
            if len(names) < 2:
                raise ValueError(
                    "brownout=True needs at least two configured tiers — "
                    "the degradation ladder IS the tier ladder")
            if not (0 < self.brownout_restore_fraction
                    <= self.brownout_engage_fraction <= 1):
                raise ValueError(
                    f"need 0 < brownout_restore_fraction "
                    f"({self.brownout_restore_fraction}) <= "
                    f"brownout_engage_fraction "
                    f"({self.brownout_engage_fraction}) <= 1")
        for t in self.brownout_exempt_tiers:
            if t not in names:
                raise ValueError(
                    f"brownout_exempt_tiers={self.brownout_exempt_tiers}: "
                    f"{t!r} is not one of the configured tiers {names}")
        if self.sessions:
            if self.session_ttl_s <= 0:
                raise ValueError(f"session_ttl_s={self.session_ttl_s} "
                                 f"must be > 0")
            if self.session_capacity < 1:
                raise ValueError(f"session_capacity="
                                 f"{self.session_capacity} must be >= 1")
        if self.session_hidden and not self.sessions:
            raise ValueError(
                "session_hidden=True needs sessions=True — the hidden "
                "tree is per-stream state")
        if self.edf_max_slack_ms < 0:
            raise ValueError(f"edf_max_slack_ms={self.edf_max_slack_ms} "
                             f"must be >= 0")
        if self.session_ctx_cache:
            if not self.sessions:
                raise ValueError(
                    "session_ctx_cache=True needs sessions=True — the "
                    "context bundle is per-stream state")
            if self.ctx_cache_threshold <= 0:
                raise ValueError(
                    f"ctx_cache_threshold={self.ctx_cache_threshold} "
                    f"must be > 0 (the static-scene gate)")
        if self.xl_mesh is not None:
            # Spec validity is a CONFIG error (fatal at construction);
            # insufficient devices is a REPLICA condition (typed skip at
            # engine boot) — the split the fleet contract needs.
            from raft_stereo_tpu.parallel.mesh import parse_mesh_spec
            parse_mesh_spec(self.xl_mesh)
            if self.xl_workers < 1:
                raise ValueError(f"xl_workers={self.xl_workers} must be "
                                 f">= 1")
            if self.xl_threshold_pixels < 1:
                raise ValueError(f"xl_threshold_pixels="
                                 f"{self.xl_threshold_pixels} must be "
                                 f">= 1")
            xl_sizes = tuple(sorted(set(int(s)
                                        for s in self.xl_batch_sizes)))
            if not xl_sizes or xl_sizes[0] != 1:
                raise ValueError(
                    f"xl_batch_sizes={self.xl_batch_sizes} must be "
                    f"positive ints including 1 (the partial-batch "
                    f"floor)")
            if (self.xl_max_pixels is not None
                    and self.xl_max_pixels <= self.xl_threshold_pixels):
                raise ValueError(
                    f"xl_max_pixels={self.xl_max_pixels} must exceed "
                    f"xl_threshold_pixels={self.xl_threshold_pixels} "
                    f"(the xl routing band would be empty)")
        if self.tile_threshold_pixels is not None \
                and self.tile_threshold_pixels < 1:
            raise ValueError(f"tile_threshold_pixels="
                             f"{self.tile_threshold_pixels} must be >= 1")
        if self.tile_rows < MODEL_DIVIS:
            raise ValueError(
                f"tile_rows={self.tile_rows} must be >= {MODEL_DIVIS} "
                f"(a tile is an ordinary /{MODEL_DIVIS}-padded bucket "
                f"dispatch)")
        if self.tile_halo < 0:
            raise ValueError(f"tile_halo={self.tile_halo} must be >= 0")
        model_names = [parse_model_spec(s)[0] for s in self.models]
        if len(set(model_names)) != len(model_names):
            raise ValueError(f"models={self.models}: duplicate model "
                             f"names (one served version per name)")
        if self.models and not (self.model_store_dir
                                or self.executable_cache_dir):
            raise ValueError(
                "ServeConfig.models needs a store to load from: set "
                "model_store_dir (or executable_cache_dir — the shared "
                "artifact store holds the models/ namespace)")
        if (self.default_model is not None
                and self.default_model not in model_names):
            raise ValueError(
                f"default_model={self.default_model!r} is not one of the "
                f"registered model names {model_names}")
        if not 0.0 <= self.confidence_floor <= 1.0:
            raise ValueError(f"confidence_floor={self.confidence_floor} "
                             f"must be in [0, 1]")
        if self.quality_drift_threshold <= 0:
            raise ValueError(
                f"quality_drift_threshold={self.quality_drift_threshold} "
                f"must be > 0")
        if not 0.0 < self.quality_availability < 1.0:
            raise ValueError(
                f"quality_availability={self.quality_availability} must "
                f"be in (0, 1) — 1.0 leaves no quality budget to burn")
        if self.brownout_spare_below and not self.confidence:
            raise ValueError(
                "brownout_spare_below needs confidence=True — the spare "
                "signal IS the rolling confidence telemetry")
        if not 0.0 <= self.brownout_spare_below <= 1.0:
            raise ValueError(
                f"brownout_spare_below={self.brownout_spare_below} must "
                f"be in [0, 1]")
        if self.cascade:
            if not self.confidence:
                raise ValueError("cascade=True needs confidence=True — "
                                 "the escalation gate IS the confidence "
                                 "signal")
            if len(names) < 2:
                raise ValueError(
                    "cascade=True needs at least two configured tiers "
                    "(a draft and an escalation target)")
            for field_name, value in (("cascade_draft",
                                       self.cascade_draft),
                                      ("cascade_escalate",
                                       self.cascade_escalate)):
                if value is not None and value not in names:
                    raise ValueError(
                        f"{field_name}={value!r} is not one of the "
                        f"configured tiers {names}")
            if (self.cascade_draft is not None
                    and self.cascade_draft == self.cascade_escalate):
                raise ValueError(
                    f"cascade_draft and cascade_escalate are both "
                    f"{self.cascade_draft!r} — the cascade would never "
                    f"change programs")
            if not 0.0 <= self.cascade_threshold <= 1.0:
                raise ValueError(
                    f"cascade_threshold={self.cascade_threshold} must "
                    f"be in [0, 1]")
        elif self.cascade_draft is not None \
                or self.cascade_escalate is not None:
            raise ValueError("cascade_draft/cascade_escalate need "
                             "cascade=True")

    def parsed_tiers(self) -> Tuple[RequestTier, ...]:
        return tuple(parse_tier(s) for s in self.tiers)


@dataclasses.dataclass
class ServeResult:
    """One answered request: the flow plus its latency decomposition."""

    flow: np.ndarray             # (H, W) x-flow (= -disparity), float32
    queue_wait_s: float          # admission -> worker pickup
    device_s: float              # dispatch -> outputs ready
    fetch_s: float               # device->host result transfer
    total_s: float               # admission -> result ready
    batch_size: int              # occupancy of the dispatch it rode in
    iters_used: Optional[int] = None  # GRU trip count of the dispatch
    #                              (the worst batch member's depth; the
    #                              configured depth on fixed-iters paths)
    tier: Optional[str] = None   # latency tier the request RAN at
    # Brownout provenance: the tier the client asked for when it differs
    # from ``tier`` (None = served as requested).  The HTTP layer renders
    # this as the X-Degraded header.
    requested_tier: Optional[str] = None
    attempts: int = 1            # dispatch attempts including the one
    #                              that succeeded (> 1 = recovered crash)
    # Streaming-session provenance (engine.submit_session): the session
    # this frame belonged to, its index in the stream, whether the GRU
    # warm-started from the previous frame's disparity, whether the
    # scene-cut gate forced a cold start, and the measured inter-frame
    # delta.  ``flow_low`` is the PADDED low-res x-flow the session
    # carries forward — surfaced so benches/tests can chain manually.
    session_id: Optional[str] = None
    frame_index: Optional[int] = None
    warm: bool = False
    scene_cut: bool = False
    frame_delta: Optional[float] = None
    flow_low: Optional[np.ndarray] = None
    # Context-cache provenance (session_ctx_cache): ``ctx_cached`` — this
    # frame REUSED the session's context bundle (the context encoder
    # never ran; X-Ctx-Cached header); ``ctx`` — the bundle a cold
    # state_ctx frame computed, folded back into the session.
    ctx_cached: bool = False
    ctx: Optional[object] = None
    # Hidden-state provenance (round 19, ``ServeConfig.session_hidden``):
    # the frame's FINAL per-level GRU hidden tree (batch-axis-free host
    # arrays) the session chains into the next frame's warm-h dispatch,
    # and whether THIS frame consumed one (``warm_hidden`` — the warm-h
    # families).
    hidden: Optional[object] = None
    warm_hidden: bool = False
    # XL/tiling provenance (round 17): ``mesh`` — the compact mesh label
    # ("rows4") when this request ran as a mesh-sharded xl dispatch
    # (``tier`` reads "xl" then); ``tiles`` — how many halo-overlap tile
    # dispatches a stitched answer rode (X-Tiles header); ``seam_epe`` —
    # the tiles' measured mean overlap disagreement in px (None for
    # untiled requests and single-overlap-free stitches).
    mesh: Optional[str] = None
    tiles: Optional[int] = None
    seam_epe: Optional[float] = None
    # Model provenance (round 21, serving/models.py): which registered
    # model answered — None/None for the implicit constructor model
    # (wire bytes unchanged); the HTTP layer renders these as
    # X-Model / X-Model-Version.
    model: Optional[str] = None
    model_version: Optional[str] = None
    # Trace provenance (round 23 fleet observability): the sampled trace
    # this request recorded spans under — None when unsampled (the
    # common case).  The HTTP layer surfaces it as X-Trace-Id so a
    # client can quote the exact id that finds the request's timeline in
    # /debug/spans (and, across the router hop, the federated view).
    trace_id: Optional[str] = None
    # Quality provenance (round 24, ``ServeConfig.confidence``): the
    # unpadded full-resolution (H, W) float32 confidence map in (0, 1]
    # (None with confidence off and on xl/mesh dispatches), its mean
    # (the scalar the telemetry, SLO, and cascade gate consume), and —
    # cascade requests only — whether this answer came from the
    # escalation tier, which tier drafted it, and the draft's mean
    # confidence that triggered (or cleared) the escalation.
    confidence: Optional[np.ndarray] = None
    confidence_mean: Optional[float] = None
    escalated: bool = False
    draft_tier: Optional[str] = None
    draft_confidence: Optional[float] = None

    @property
    def degraded(self) -> bool:
        return self.requested_tier is not None

    @property
    def disparity(self) -> np.ndarray:
        """Positive disparity (the user-facing convention, cli/demo.py)."""
        return -self.flow


@dataclasses.dataclass
class _Payload:
    """What the engine parks in Request.payload: padded inputs + unpadder,
    plus (session frames only) the warm-start init and the state the
    completion callback folds back into the session."""

    left: np.ndarray             # (Hp, Wp, 3) host-padded
    right: np.ndarray
    padder: InputPadder
    flow_init: Optional[np.ndarray] = None   # (Hp/f, Wp/f) f32, warm only
    hidden_init: Optional[object] = None     # warm-h: per-level hidden tree
    session: Optional[object] = None         # sessions.StereoSession
    thumb: Optional[np.ndarray] = None       # THIS frame's thumbnail
    raw_shape: Optional[Tuple[int, int]] = None
    frame_index: Optional[int] = None
    scene_cut: bool = False
    frame_delta: Optional[float] = None
    ctx_init: Optional[object] = None        # warm_ctx: the session's
    #                                          cached context bundle


@dataclasses.dataclass
class _XlGroup:
    """One xl worker's device group: the mesh its bucket executables
    shard over, the variables replicated onto it, and the replicated
    NamedSharding the dispatch path uploads image buffers with."""

    devices: Tuple
    mesh: object          # jax.sharding.Mesh (1, corr, rows)
    variables: object     # params replicated over the group
    sharding: object      # NamedSharding(mesh, P()) for uploads

    @property
    def label(self) -> str:
        return "+".join(str(getattr(d, "id", i))
                        for i, d in enumerate(self.devices))


@dataclasses.dataclass
class _EngineModel:
    """One served model's engine-side state: the identity coordinate
    plus everything the dispatch path reads per model — the effective
    config, the per-tier model objects, the per-worker resident fp32
    trees, and the lazily quantized int8 trees.  The implicit
    constructor model is the ``name=None`` bundle; its fields are
    exactly the attributes the pre-registry engine kept flat on
    ``self`` (which stay as aliases — same objects, zero behavior
    drift)."""

    name: Optional[str]          # None = the implicit constructor model
    version: Optional[str]
    config: RaftStereoConfig
    effective_config: RaftStereoConfig
    model: RAFTStereo
    tier_models: Dict[Optional[str], RAFTStereo]
    host_variables: object
    worker_vars: List
    qvars_host: object = None
    qvars: Dict[int, object] = dataclasses.field(default_factory=dict)
    # Retirement latch: resolve_model refuses a retiring model (typed
    # 404) while its in-flight dispatches drain.
    retiring: bool = False

    @property
    def coord(self) -> Optional[str]:
        """``name@version``, or None for the implicit model — the tag
        compile-cost keys, persist keys, and metric labels carry."""
        if self.name is None:
            return None
        return model_coord(self.name, self.version or "0")


@dataclasses.dataclass
class _XlTier:
    """Engine-side state of the xl serving tier (``ServeConfig.xl_mesh``):
    the parsed topology, the model whose config carries the sharding
    knobs (rows_shards / corr_w2_shards / rows_gru — same parameter tree
    as the base model, different compiled programs), and the device
    groups that serve it."""

    spec: Dict[str, int]       # {"rows": r, "corr": c}
    label: str                 # compact key/metric tag, e.g. "rows4"
    size: int                  # devices per group (rows * corr)
    model: RAFTStereo          # the xl-config model (shared params)
    groups: List[_XlGroup]


def _host_bytes(tree) -> int:
    """Bytes of the NumPy leaves of ``tree`` (``None`` leaves count 0)."""
    import jax

    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))


class BucketPolicy:
    """Maps a raw image (H, W) to its padded dispatch bucket (Hp, Wp).

    Static mode (``grids`` of length 1): the fixed grid — /32 by default,
    or ``ServeConfig.shape_bucket`` — exactly the reference's padding
    semantics.

    Adaptive mode: a shape starts at the COARSEST grid (coarse buckets
    collapse more raw shapes into one compiled ladder, so compiles and
    cold starts are fewest), and ``note`` — fed the same per-dispatch
    real/padding pixel counts as the ``serve_bucket_*_pixels_total``
    counters — refines a bucket to the next finer grid once its measured
    cumulative waste fraction exceeds ``max_waste``.  Refinement is
    monotonic and bottoms out at the /32 floor, which the model's
    divisibility constraint makes irreducible.
    """

    def __init__(self, grids: Sequence[int] = (MODEL_DIVIS,),
                 max_waste: float = 0.10, min_observe_px: int = 0,
                 refinements_counter=None):
        grids = sorted(set(int(g) for g in grids), reverse=True)
        if not grids or any(g % MODEL_DIVIS or g < MODEL_DIVIS
                            for g in grids):
            raise ValueError(f"grids={grids} must be multiples of "
                             f"/{MODEL_DIVIS}")
        self.grids = tuple(grids)         # coarsest first
        self.max_waste = max_waste
        self.min_observe_px = min_observe_px
        self._lock = threading.Lock()
        self._px: Dict[Tuple[int, int], List[int]] = {}  # bucket -> [real,
        #                                                   dispatched]
        self._refined: set = set()        # buckets past the waste bound
        self._refinements = refinements_counter
        self.adaptive = len(self.grids) > 1

    @staticmethod
    def _pad_to(h: int, w: int, grid: int) -> Tuple[int, int]:
        return (-(-h // grid) * grid, -(-w // grid) * grid)

    def bucket_for(self, h: int, w: int) -> Tuple[int, int, int]:
        """The (Hp, Wp, grid) this raw shape dispatches at: the coarsest
        grid whose bucket has not been refined away (the finest grid is
        always accepted)."""
        with self._lock:
            for g in self.grids[:-1]:
                bucket = self._pad_to(h, w, g)
                if bucket not in self._refined:
                    return bucket + (g,)
            g = self.grids[-1]
            return self._pad_to(h, w, g) + (g,)

    def note(self, bucket: Tuple[int, int], real_px: int,
             dispatched_px: int) -> None:
        """Per-dispatch waste feedback (the engine calls this alongside
        ``ServingMetrics.observe_padding``).  Crossing ``max_waste``
        refines the bucket: subsequent shapes that would have used it route
        to the next finer grid instead."""
        if not self.adaptive or dispatched_px <= 0:
            return
        with self._lock:
            if bucket in self._refined:
                return
            acc = self._px.setdefault(tuple(bucket), [0, 0])
            acc[0] += real_px
            acc[1] += dispatched_px
            if acc[1] < max(self.min_observe_px, 1):
                return
            waste = 1.0 - acc[0] / acc[1]
            if waste > self.max_waste:
                self._refined.add(tuple(bucket))
                log.info(
                    "bucket %sx%s refined: measured padding waste %.1f%% "
                    "> %.1f%% over %d dispatched pixels — shapes re-route "
                    "to the next finer pad grid",
                    bucket[0], bucket[1], waste * 100,
                    self.max_waste * 100, acc[1])
                if self._refinements is not None:
                    self._refinements.inc()

    @property
    def refined_buckets(self) -> Tuple[Tuple[int, int], ...]:
        with self._lock:
            return tuple(sorted(self._refined))


class _SinkRef:
    """Late-bound anomaly-sink handle: the brownout controller (and any
    other long-lived component) holds this instead of the sink itself,
    because the CLI attaches the sink after the engine is constructed."""

    def __init__(self, engine: "ServingEngine"):
        self._engine = engine

    def fire(self, kind: str, **detail):
        sink = self._engine.sink
        if sink is not None:
            return sink.fire(kind, **detail)
        return None


class ServingEngine:
    """The unified serving engine: one object owning the batch-N compile
    cache, the continuous-batching scheduler, the device worker pool, and
    the cost/waste telemetry loop.

    ``devices`` defaults to the first ``serve_cfg.data_parallel`` local JAX
    devices; each gets a worker thread with the variables resident on that
    device.  The public surface is unchanged from the round-6
    ``StereoService`` (``submit``/``infer``/``drain``/``close``), which
    remains as an alias.
    """

    def __init__(self, config: RaftStereoConfig, variables,
                 serve_cfg: ServeConfig = ServeConfig(),
                 devices: Optional[Sequence] = None,
                 registry: Optional[MetricsRegistry] = None,
                 tracer=None):
        import jax

        self.serve_cfg = serve_cfg
        # Request-path span tracer (telemetry/spans.py).  At the default
        # sample rate 0.0 every start_trace returns None and the span
        # plumbing below is a handful of no-op attribute checks per
        # request — serving numerics and dispatch behavior are untouched.
        self.tracer = (tracer if tracer is not None
                       else SpanTracer(serve_cfg.trace_sample_rate))
        if devices is None:
            # The ONE device-discovery helper the engine and the parallel
            # runtime share (parallel/distributed.py): a stable id-sorted
            # order, so the solo worker pool and the xl mesh groups below
            # partition the same list instead of each trusting
            # jax.local_devices() ordering independently.
            from raft_stereo_tpu.parallel.distributed import device_groups
            solo = device_groups(1, serve_cfg.data_parallel)
            if not solo:
                raise ValueError(
                    f"data_parallel={serve_cfg.data_parallel} exceeds the "
                    f"{len(jax.local_devices())} local devices")
            devices = [g[0] for g in solo]
        self.devices = list(devices)
        self.metrics = ServingMetrics(registry,
                                      max_batch=serve_cfg.max_batch)
        # Host phases (telemetry/spans.py): every boundary of a dispatch on
        # the worker thread and of a request on its HTTP thread, as a span
        # on the profiler's clock, ``serve_phase_seconds{phase=}`` and the
        # sampled request's trace, from one pair of clock reads each.
        self.phases = Phases("serve.", WORKER_PHASES + HTTP_PHASES,
                             self.metrics.registry, "serve_phase_seconds",
                             self.tracer)
        self._dispatch_seq = itertools.count(1)
        # Compile-cost registry (telemetry/costs.py): one per engine,
        # shared by all workers — same bucket => same program => one cost
        # record per (shape, batch) key.  None (default) leaves the jit
        # dispatch untouched.
        self.costs = None
        self._mfu = None
        if serve_cfg.cost_telemetry:
            from raft_stereo_tpu.telemetry.costs import (CompileRegistry,
                                                         MfuMeter)
            self.costs = CompileRegistry(
                registry=self.metrics.registry,
                device_peak_tflops=serve_cfg.device_peak_tflops)
            self._mfu = MfuMeter(
                self.metrics.mfu, self.costs.peak_flops,
                achieved_gauge=self.metrics.achieved_flops_per_s)
        # The spatial padding policy: static /32 (or shape_bucket) unless
        # adaptive_buckets turns on the waste feedback loop.
        if serve_cfg.adaptive_buckets:
            grids = tuple(serve_cfg.bucket_grids) + (
                serve_cfg.shape_bucket or MODEL_DIVIS,)
        else:
            grids = (serve_cfg.shape_bucket or MODEL_DIVIS,)
        self.policy = BucketPolicy(
            grids=grids, max_waste=serve_cfg.max_padding_waste,
            refinements_counter=self.metrics.bucket_refinements)
        # The model, with the same deep-iteration corr_fp32 guard the solo
        # runner applies — both paths compile the identical program.
        self.config = config
        # Calibrated correlation scales for int8 tiers (quant/calibrate):
        # loaded once and swapped into every quant tier's effective
        # config, so the compiled programs carry the percentile-clipped
        # constants instead of dynamic in-graph reductions.
        self._quant_corr_scales = None
        # Calibrated per-conv activation scales for int8_mxu tiers
        # (quant/calibrate.conv_input_scales): baked into the packs the
        # lazy host quantization builds (_vars_for); None (no scale
        # file, or a pre-r22 record without qin sites) leaves the
        # int8_mxu convs on the dynamic in-graph max-abs fallback.
        self._quant_act_scales = None
        if serve_cfg.quant_scales_path:
            from raft_stereo_tpu.quant import (conv_input_scales,
                                               corr_scales, load_scales)
            _scale_record = load_scales(serve_cfg.quant_scales_path)
            self._quant_corr_scales = corr_scales(_scale_record)
            self._quant_act_scales = (conv_input_scales(_scale_record)
                                      or None)

        # Latency tiers: one effective config / model per tier (the
        # early-exit + quant knobs swapped into the SAME architecture —
        # the parameter tree is shared, only the compiled program
        # differs).  A tier whose effective config equals the base one
        # (threshold <= 0, e.g. "quality") maps to the base model so its
        # requests share the base executables — the bitwise-parity
        # bucket stays one program.  Int8 tiers ("turbo") get their own
        # model AND their own quantized variable tree (_vars_for).
        self.tiers: Dict[str, RequestTier] = {
            t.name: t for t in serve_cfg.parsed_tiers()}
        self.default_tier: Optional[str] = None
        if self.tiers:
            self.default_tier = serve_cfg.default_tier or (
                "quality" if "quality" in self.tiers
                else next(iter(self.tiers)))
        if serve_cfg.session_ctx_cache and config.shared_backbone:
            raise ValueError(
                "session_ctx_cache is unsupported with shared_backbone: "
                "fnet is computed from the cnet trunk, so the context "
                "encoder cannot be skipped (models/raft_stereo.py)")
        # Model registry (round 21, serving/models.py): every served
        # model — the implicit constructor one under key None, plus any
        # registered "name@version" — keeps its per-model state in one
        # _EngineModel bundle.  The int8 quantization lock is shared
        # (host quantization runs at most once per bundle).
        self._qvars_lock = threading.Lock()
        self._models_lock = threading.Lock()
        self._pending_lock = threading.Lock()
        self._model_pending: Dict[Optional[str], int] = {}
        base_bundle = self._build_bundle(None, None, config, variables)
        self._models: Dict[Optional[str], _EngineModel] = {
            None: base_bundle}
        # Flat aliases of the implicit bundle — the pre-registry
        # attribute surface every existing call site (HTTP, CLIs,
        # tests) keeps reading.  Same objects, zero drift.
        self.config = base_bundle.config
        self.effective_config = base_bundle.effective_config
        self.model = base_bundle.model
        self._tier_models = base_bundle.tier_models
        self._worker_vars = base_bundle.worker_vars
        self._host_variables = variables
        # The model store + boot-time registrations (ServeConfig.models).
        self.model_store: Optional[ModelStore] = None
        store_dir = (serve_cfg.model_store_dir
                     or serve_cfg.executable_cache_dir)
        if store_dir and (serve_cfg.models
                          or serve_cfg.model_store_dir):
            self.model_store = ModelStore(store_dir)
        self.default_model: Optional[str] = None
        for spec in serve_cfg.models:
            reg = self.model_store.resolve(spec)   # deep-verified load
            self._models[reg.name] = self._build_bundle(
                reg.name, reg.version, reg.config, reg.variables)
            log.info("model %s registered at boot", reg.coord)
        if serve_cfg.default_model is not None:
            self.default_model = serve_cfg.default_model
        self._cache_lock = threading.Lock()
        self._compiled: "collections.OrderedDict[Tuple, object]" = (
            collections.OrderedDict())
        # Per-group dispatch-latency EWMA (seconds, device + fetch):
        # what the EDF bounded-slack derivation subtracts from the
        # nearest deadline so coalescing can delay a frame but never be
        # the reason it misses.  Updated after every dispatch
        # (_note_dispatch_latency); a group with no measurement yet
        # estimates 0 — the slack then bounds only on edf_max_slack_ms.
        self._latency_lock = threading.Lock()
        self._dispatch_latency_s: Dict[Tuple, float] = {}
        self.queue = BucketQueue(
            max_batch=serve_cfg.max_batch,
            batch_sizes=serve_cfg.batch_sizes,
            max_queue=serve_cfg.max_queue, metrics=self.metrics,
            edf=serve_cfg.edf_scheduler,
            edf_max_slack_s=serve_cfg.edf_max_slack_ms / 1e3,
            latency_fn=self._dispatch_latency_estimate, clock=clock)
        # ---- XL tier: mesh-sharded device groups (round 17) ------------
        # ``self.xl`` is an _XlTier (mesh spec + per-group meshes +
        # replicated variables) or None — None either because no xl_mesh
        # was configured or because THIS replica cannot supply the
        # devices (typed skip; the fleet contract for heterogeneous
        # replicas).  xl workers are extra entries at the END of the
        # unified worker table: indices [len(devices), len(devices) +
        # xl_workers) with their own breakers/threads, popping only
        # FAMILY_XL groups from the one shared queue.
        self.xl: Optional[_XlTier] = None
        self._xl_sizes: Tuple[int, ...] = ()
        if serve_cfg.xl_mesh is not None:
            self._init_xl(variables)
        # ---- Resilience layer (round 13) -------------------------------
        # Anomaly sink (telemetry/watchdog.AnomalySink | None): fires
        # worker_crash / circuit / brownout / poisoned events into the
        # run-event log + flight recorder.  The CLI attaches it after
        # construction (attach_anomaly_sink) because the event log is
        # wired after the engine exists; every fire site reads the
        # attribute at fire time.
        self.sink = None
        # Chaos injector: None unless configured AND enabled — the
        # dispatch path then carries exactly one attribute check.
        self.chaos: Optional[ChaosInjector] = None
        if serve_cfg.chaos is not None and serve_cfg.chaos.enabled:
            self.chaos = ChaosInjector(
                serve_cfg.chaos,
                observe=self.metrics.observe_injected_fault)
            log.warning("CHAOS ENABLED: %s — injected faults are ON for "
                        "this engine", serve_cfg.chaos)
        # Per-worker circuit breakers (solo devices AND xl device
        # groups); gauges start in the closed state so /metrics shows
        # every worker's circuit from boot.
        self.breakers = [
            CircuitBreaker(
                failure_threshold=serve_cfg.breaker_failures,
                cooldown_s=serve_cfg.breaker_cooldown_s,
                on_state=self._make_circuit_callback(i))
            for i in range(self._worker_count())]
        for i in range(self._worker_count()):
            self.metrics.circuit_gauge(i).set(CIRCUIT_CLOSED)
        # Brownout controller over the tier cost ladder (cheapest-first).
        self.brownout: Optional[BrownoutController] = None
        if serve_cfg.brownout:
            self.brownout = BrownoutController(
                self.metrics, serve_cfg.max_queue,
                ladder=cost_ladder(serve_cfg.parsed_tiers()),
                engage_fraction=serve_cfg.brownout_engage_fraction,
                engage_s=serve_cfg.brownout_engage_s,
                restore_fraction=serve_cfg.brownout_restore_fraction,
                restore_s=serve_cfg.brownout_restore_s,
                poll_s=serve_cfg.brownout_poll_s,
                gauge=self.metrics.brownout_level,
                sink=_SinkRef(self)).start()
            # Confidence-aware victim selection (round 24): requests at
            # tiers whose recent answers were already low-confidence are
            # spared from degradation (_admit_tier feeds the rolling
            # mean).  0.0 (default) disables the check.
            self.brownout.spare_below = serve_cfg.brownout_spare_below
        # ---- Quality observability (round 24) --------------------------
        # Per-request confidence telemetry + PSI drift watchdog
        # (telemetry/quality.py); None with confidence off — no tracker,
        # no series, the exposition stays byte-identical.  The drift
        # watchdog fires through _SinkRef, so a sink attached after
        # construction (the CLI order) is still reached.
        self.quality = None
        self._cascade_drafts = None
        self._cascade_escalations = None
        if serve_cfg.confidence:
            from raft_stereo_tpu.telemetry.quality import QualityTracker
            from raft_stereo_tpu.telemetry.slo import BurnRateTracker
            # The quality error budget: the fraction of requests allowed
            # below the confidence floor, burned on the same multi-window
            # machinery as the fleet's availability budget — one more
            # dimension label on the burn-rate gauge family.
            quality_slo = BurnRateTracker(
                availability=serve_cfg.quality_availability,
                registry=self.metrics.registry,
                gauge_name="serve_slo_burn_rate",
                dimension="quality")
            self.quality = QualityTracker(
                registry=self.metrics.registry,
                sink=_SinkRef(self),
                floor=serve_cfg.confidence_floor,
                drift_threshold=serve_cfg.quality_drift_threshold,
                drift_reference_size=serve_cfg.quality_drift_reference,
                drift_window=serve_cfg.quality_drift_window,
                slo=quality_slo)
        # Cascade tier resolution ("auto"): draft on the cheapest rung
        # of the cost ladder, escalate to the most expensive, unless the
        # config names either explicitly.
        self._cascade_draft: Optional[str] = None
        self._cascade_escalate: Optional[str] = None
        if serve_cfg.cascade:
            ladder = cost_ladder(serve_cfg.parsed_tiers())
            self._cascade_draft = serve_cfg.cascade_draft or ladder[0]
            self._cascade_escalate = (serve_cfg.cascade_escalate
                                      or ladder[-1])
            if self._cascade_draft == self._cascade_escalate:
                raise ValueError(
                    f"cascade draft and escalation tiers both resolve "
                    f"to {self._cascade_draft!r} — configure "
                    f"cascade_draft/cascade_escalate explicitly")
            self._cascade_drafts = self.metrics.registry.counter(
                "serve_cascade_draft_total",
                "Cascade (tier=auto) requests answered by the draft "
                "tier alone")
            self._cascade_escalations = self.metrics.registry.counter(
                "serve_cascade_escalated_total",
                "Cascade (tier=auto) requests escalated to the "
                "expensive tier on low draft confidence")
        # Persistent executable cache / shared artifact store
        # (serving/persist.py).
        self.disk_cache = None
        if serve_cfg.executable_cache_dir:
            from raft_stereo_tpu.serving.persist import ExecutableDiskCache
            self.disk_cache = ExecutableDiskCache(
                serve_cfg.executable_cache_dir,
                max_bytes=serve_cfg.executable_cache_max_bytes,
                read_only=serve_cfg.executable_cache_read_only,
                bytes_gauge=self.metrics.persist_cache_bytes)
        # Streaming-session store (serving/sessions.py): the per-stream
        # warm-start state behind submit_session / POST /v1/stream.  None
        # (default) keeps the engine stateless — no warm executable
        # families compile, prewarm, or join the readiness target.
        self.sessions: Optional[SessionStore] = None
        if serve_cfg.sessions:
            self.sessions = SessionStore(
                capacity=serve_cfg.session_capacity,
                ttl_s=serve_cfg.session_ttl_s,
                active_gauge=self.metrics.sessions_active,
                created_counter=self.metrics.sessions_created,
                expired_counter=self.metrics.sessions_expired,
                evicted_counter=self.metrics.sessions_evicted)
        # Session handoff (round 18): the artifact store's sessions/
        # namespace a draining engine publishes its live streams into,
        # and a receiving engine lazily adopts them from
        # (submit_session handoff_key=).  Needs BOTH the session store
        # and a shared artifact directory; absent either, drains keep
        # the r16 typed-loss behavior.
        self.handoff_store = None
        self._handoff_manifest: Optional[Dict[str, object]] = None
        self._handoff_fetched = threading.Event()
        self._handoff_lock = threading.Lock()
        self._handoff_blobs: Dict[str, Dict] = {}
        if serve_cfg.sessions and serve_cfg.executable_cache_dir:
            from raft_stereo_tpu.serving.persist import SessionHandoffStore
            self.handoff_store = SessionHandoffStore(
                serve_cfg.executable_cache_dir,
                ttl_s=max(serve_cfg.session_ttl_s, 60.0) * 4)
        # Retry bookkeeping: requests bounced by a crashed dispatch sit in
        # backoff timers between dequeue and requeue — drain() must wait
        # for them and close() must fail them, so they are accounted here.
        self._retry_lock = threading.Lock()
        self._pending_retries = 0
        self._retry_timers: set = set()   # (Timer, reqs) pairs
        # Readiness (the /readyz gate): the configured warm surface is
        # warmup_shapes x distinct executable families x batch sizes x
        # workers; ready once every entry has dispatched once (prewarm or
        # traffic).  No configured warmup -> ready at boot.
        self._warm_lock = threading.Lock()
        self._warmed: set = set()
        self._warm_target: set = set()
        for hw in serve_cfg.warmup_shapes:
            hp, wp, _ = self.policy.bucket_for(int(hw[0]), int(hw[1]))
            if self._xl_routes((hp, wp)):
                # This bucket's traffic runs on the xl mesh groups —
                # warming the solo ladder for it would pay megapixel
                # single-device compiles no request will ever dispatch.
                # (Named models never route xl, so the entry is
                # implicit-model only.)
                for widx in self._xl_worker_indices():
                    for n in self._xl_sizes:
                        self._warm_target.add(
                            (widx, (hp, wp), n, None, FAMILY_XL, None))
                continue
            for mname in self._registered_names():
                for widx in range(len(self.devices)):
                    for tier in self._distinct_cache_tiers(mname):
                        for n in self.queue.sizes:
                            for family in self._families():
                                self._warm_target.add(
                                    (widx, (hp, wp), n, tier, family,
                                     mname))
        self._closed = False
        self._shutting_down = False
        self._workers_lock = threading.Lock()
        self._workers = [
            threading.Thread(target=self._worker_loop, args=(i,),
                             daemon=True, name=f"stereo-worker-{i}")
            for i in range(self._worker_count())]
        for t in self._workers:
            t.start()
        if serve_cfg.prewarm_on_init:
            for hw in serve_cfg.warmup_shapes:
                self.prewarm(hw)

    def _make_circuit_callback(self, widx: int):
        """Breaker transition hook for one device: gauge + anomaly event.
        Opening the circuit is the page-worthy event (a device is
        quarantined); closing is the all-clear."""
        def on_state(old: int, new: int, failures: int) -> None:
            self.metrics.circuit_gauge(widx).set(new)
            log.warning("device %d circuit %s -> %s (%d consecutive "
                        "failures)", widx, circuit_state_name(old),
                        circuit_state_name(new), failures)
            sink = self.sink
            if sink is not None:
                sink.fire(f"circuit_{circuit_state_name(new)}",
                          device=widx,
                          previous=circuit_state_name(old),
                          consecutive_failures=failures)
        return on_state

    def attach_anomaly_sink(self, sink) -> None:
        """Wire an AnomalySink (telemetry/watchdog.py): resilience
        transitions emit anomaly run events + flight-recorder bundles
        through the same path the watchdogs use."""
        self.sink = sink

    # -------------------------------------------------------- model registry
    def _effective(self, cfg_in: RaftStereoConfig) -> RaftStereoConfig:
        """One model config's effective inference form: the solo runner's
        deep-iteration guard plus the calibrated int8 correlation scales
        swapped into quantized configs (quant/calibrate.py)."""
        eff = effective_inference_config(cfg_in, self.serve_cfg.iters)
        if (eff.quant != "off" and self._quant_corr_scales is not None
                and eff.quant_corr_scales is None):
            eff = dataclasses.replace(
                eff, quant_corr_scales=self._quant_corr_scales)
        return eff

    def _build_bundle(self, name: Optional[str], version: Optional[str],
                      config: RaftStereoConfig, variables) -> _EngineModel:
        """Build one model's engine-side state: effective config, the
        per-tier model objects (fixed-depth tiers share the bundle's
        base model — one program per DISTINCT effective config), and the
        per-worker resident fp32 trees.  Same construction for the
        implicit model and every registered one."""
        import jax

        eff = self._effective(config)
        model = RAFTStereo(eff)
        tier_models: Dict[Optional[str], RAFTStereo] = {None: model}
        for tname, tier in self.tiers.items():
            teff = self._effective(tier.apply(config))
            tier_models[tname] = (model if teff == eff
                                  else RAFTStereo(teff))
        worker_vars = [jax.device_put(variables, d)
                       for d in self.devices]
        return _EngineModel(name=name, version=version, config=config,
                            effective_config=eff, model=model,
                            tier_models=tier_models,
                            host_variables=variables,
                            worker_vars=worker_vars)

    def _registered_names(self, include_implicit: bool = True
                          ) -> List[Optional[str]]:
        """Model names this engine serves, implicit first — what the
        warm target and prewarm iterate."""
        with self._models_lock:
            names = sorted(n for n in self._models if n is not None)
        return ([None] + names) if include_implicit else names

    def resolve_model(self, model: Optional[str]) -> Optional[str]:
        """The model a request actually runs: the named one (validated
        against the registry), or the default-model pointer, or None
        (the implicit constructor model).  Raises the typed
        ``ModelUnknown`` (HTTP 404 ``model_unknown``) on an
        unregistered or retiring name."""
        if model is None:
            model = self.default_model
        if model is None:
            return None
        bundle = self._models.get(model)
        if bundle is None or bundle.retiring:
            with self._models_lock:
                known = [n for n, b in self._models.items()
                         if n is not None and not b.retiring]
            raise ModelUnknown(model, known)
        return model

    def _note_pending(self, model: Optional[str], delta: int) -> None:
        """Per-model in-flight admission count — ``retire_model``'s
        drain signal (a model with pending admissions must not have its
        pytree evicted under a dispatch that will still read it)."""
        with self._pending_lock:
            self._model_pending[model] = (
                self._model_pending.get(model, 0) + delta)

    def _model_pending_count(self, model: Optional[str]) -> int:
        with self._pending_lock:
            return self._model_pending.get(model, 0)

    def _extend_warm_target(self, name: str) -> None:
        """Grow the /readyz warm surface by one registered model's
        ladder: ``ready`` flips False until the new model's prewarm
        completes — a hot swap can never report ready ahead of a warm
        ladder (acceptance: model_smoke asserts this)."""
        with self._warm_lock:
            for hw in self.serve_cfg.warmup_shapes:
                hp, wp, _ = self.policy.bucket_for(int(hw[0]),
                                                   int(hw[1]))
                if self._xl_routes((hp, wp)):
                    continue    # named models never route xl
                for widx in range(len(self.devices)):
                    for tier in self._distinct_cache_tiers(name):
                        for n in self.queue.sizes:
                            for family in self._families():
                                self._warm_target.add(
                                    (widx, (hp, wp), n, tier, family,
                                     name))

    def _purge_model_cache(self, name: str,
                           drop_target: bool = False) -> None:
        """Drop one model's in-memory compiled executables and warm
        entries (same-name version replace / retirement).  Disk-cache
        entries stay — their content keys carry the version, so they
        can never serve the wrong weights."""
        with self._cache_lock:
            for k in [k for k in self._compiled if k[5] == name]:
                self._compiled.pop(k)
        with self._warm_lock:
            self._warmed = {e for e in self._warmed if e[5] != name}
            if drop_target:
                self._warm_target = {e for e in self._warm_target
                                     if e[5] != name}

    def register_model(self, spec: str, set_default: bool = False,
                       prewarm: bool = True) -> Dict[str, object]:
        """Hot-register a model version on this LIVE engine (``POST
        /admin/models``): deep-verified store load, bundle build
        (device placement; the turbo tier quantizes lazily at first
        dispatch), warm-target extension, prewarm of the declared
        ladder through the warm artifact-store path, and — only then,
        when asked — the atomic default-pointer flip.  Re-registering
        the SAME name@version is idempotent; a new version under a
        live name replaces it (its in-memory executables purge; the
        old pytree is released once in-flight dispatches drain)."""
        if self.model_store is None:
            store_dir = (self.serve_cfg.model_store_dir
                         or self.serve_cfg.executable_cache_dir)
            if not store_dir:
                raise RuntimeError(
                    "no model store: construct the engine with "
                    "ServeConfig.model_store_dir (or "
                    "executable_cache_dir) to register models")
            self.model_store = ModelStore(store_dir)
        reg = self.model_store.resolve(spec)   # deep SHA-256 verify
        with self._models_lock:
            existing = self._models.get(reg.name)
            fresh = not (existing is not None
                         and existing.version == reg.version
                         and not existing.retiring)
        if fresh:
            bundle = self._build_bundle(reg.name, reg.version,
                                        reg.config, reg.variables)
            if existing is not None:
                # Same-name version replace: the old version's
                # executables must never answer the new version's
                # requests (the in-memory cache keys by NAME).
                self._purge_model_cache(reg.name)
            with self._models_lock:
                self._models[reg.name] = bundle
            self._extend_warm_target(reg.name)
            log.info("model %s registered%s", reg.coord,
                     " (replacing a live version)" if existing else "")
            if prewarm:
                for hw in self.serve_cfg.warmup_shapes:
                    self.prewarm(hw, models=[reg.name])
        if set_default:
            self.set_default_model(reg.name)
        return {"model": reg.name, "version": reg.version,
                "registered": bool(fresh),
                "default": self.default_model,
                "ready": self.ready}

    def set_default_model(self, name: Optional[str]) -> Optional[str]:
        """Atomically flip the default-model pointer (what unnamed
        requests run); None restores the implicit constructor model.
        The flip is the LAST step of a rollout — ``register_model``
        prewarms before it, so the first post-flip request hits warm
        executables."""
        with self._models_lock:
            if name is not None:
                b = self._models.get(name)
                if b is None or b.retiring:
                    raise ModelUnknown(
                        name, [n for n, bb in self._models.items()
                               if n is not None and not bb.retiring])
            previous, self.default_model = self.default_model, name
        log.info("default model: %s -> %s", previous, name)
        return name

    def retire_model(self, name: str, timeout: float = 30.0
                     ) -> Dict[str, object]:
        """Retire a registered model from this live engine: latch it
        retiring (new requests get the typed 404), DRAIN its in-flight
        admissions, then evict the pytree and purge its executables.
        Refuses the current default (RuntimeError — flip the pointer
        first; HTTP 409) and raises ``TimeoutError`` (retiring latch
        released) if in-flight work does not drain in ``timeout``."""
        with self._models_lock:
            bundle = self._models.get(name) if name is not None else None
            if bundle is None:
                raise ModelUnknown(
                    name, [n for n in self._models if n is not None])
            if self.default_model == name:
                raise RuntimeError(
                    f"model {name!r} is the default — set_default_model "
                    f"to another version before retiring it")
            bundle.retiring = True
        deadline = time.monotonic() + max(0.0, timeout)
        while self._model_pending_count(name) > 0:
            if time.monotonic() > deadline:
                with self._models_lock:
                    bundle.retiring = False
                raise TimeoutError(
                    f"model {name!r}: {self._model_pending_count(name)} "
                    f"admission(s) still in flight after {timeout}s — "
                    f"retirement rolled back")
            time.sleep(0.005)
        with self._models_lock:
            self._models.pop(name, None)
        self._purge_model_cache(name, drop_target=True)
        with self._pending_lock:
            self._model_pending.pop(name, None)
        log.info("model %s retired (drained, pytree evicted)",
                 bundle.coord)
        return {"model": name, "version": bundle.version,
                "retired": True}

    def models_status(self) -> Dict[str, object]:
        """The registry's JSON line (/healthz, /admin/models GET):
        registered versions, the default pointer, per-model in-flight
        admissions."""
        with self._models_lock:
            registered = [
                {"name": b.name, "version": b.version,
                 "coord": b.coord, "retiring": b.retiring}
                for n, b in sorted(self._models.items(),
                                   key=lambda kv: kv[0] or "")
                if n is not None]
        with self._pending_lock:
            pending = {(k if k is not None else "(implicit)"): v
                       for k, v in self._model_pending.items() if v > 0}
        return {"default": self.default_model,
                "registered": registered, "pending": pending}

    # -------------------------------------------------------------- xl tier
    def _xl_model_config(self, spec: Dict[str, int]) -> RaftStereoConfig:
        """The model config xl bucket executables compile: the engine's
        effective config with the mesh sharding knobs swapped in.
        Raises typed ``ValueError`` at BOOT for architecture/mesh
        combinations the sharded executors do not support — a
        misdeclared xl tier must fail loudly at construction, not at the
        first megapixel request."""
        base = self.effective_config
        rows, corr = spec["rows"], spec["corr"]
        if corr > 1 and base.corr_backend == "alt":
            raise ValueError(
                "xl_mesh corr sharding shards the 'reg' correlation "
                "volume and is incompatible with corr_backend='alt' "
                "(which builds no volume) — use 'reg'/'reg_fused' or a "
                "rows-only mesh")
        if rows > 1:
            from raft_stereo_tpu.models.banded import banded_supported
            norms = (base.context_norm,) + (
                () if base.shared_backbone else (base.fnet_norm,))
            for norm in norms:
                if not banded_supported(norm, base.n_downsample):
                    raise ValueError(
                        f"xl_mesh rows sharding is unsupported for this "
                        f"architecture: norm {norm!r} with n_downsample="
                        f"{base.n_downsample} (parallel/rows_sharded.py "
                        f"supports the published n_downsample=2 trunks)")
        # Fixed-depth, full-precision, unbanded: the sharded executors
        # run their own paths and the early-exit / int8 / banded knobs
        # do not compose with them (config.py validation); rows_gru
        # (full-loop context parallelism) needs the volume unsharded,
        # so a combined rows x corr mesh shards encoders + volume and
        # leaves the GRU loop replicated (__graft_entry__'s dryrun
        # topology).
        return dataclasses.replace(
            base, rows_shards=rows, corr_w2_shards=corr,
            rows_gru=(rows > 1 and corr == 1), banded_encoder=False,
            exit_threshold_px=0.0, exit_max_iters=None,
            quant="off", quant_corr_scales=None)

    def _init_xl(self, variables) -> None:
        """Build the xl tier: parse the mesh spec, carve device groups
        from the stable local-device order (after the solo workers),
        and replicate the variables onto each group's mesh.  A replica
        whose devices cannot supply the mesh logs the typed skip line
        and serves WITHOUT the tier (xl-routed requests fall through to
        tiling / solo dispatch) — fleet replicas are allowed to be
        heterogeneous."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from raft_stereo_tpu.parallel.distributed import device_groups
        from raft_stereo_tpu.parallel.mesh import (make_mesh,
                                                   mesh_spec_label,
                                                   parse_mesh_spec)

        serve_cfg = self.serve_cfg
        spec = parse_mesh_spec(serve_cfg.xl_mesh)
        size = spec["rows"] * spec["corr"]
        xl_cfg = self._xl_model_config(spec)   # raises typed on bad combos
        groups_devs = device_groups(size, serve_cfg.xl_workers,
                                    skip=len(self.devices))
        if not groups_devs:
            # Not enough devices past the solo workers: overlap with them
            # rather than refuse — dispatches contend on the shared
            # devices but stay correct (the CPU test backend and small
            # dev hosts hit this; production sizes data_parallel +
            # xl_workers*size <= local devices).
            groups_devs = device_groups(size, serve_cfg.xl_workers)
            if groups_devs:
                log.warning(
                    "xl_mesh=%s: not enough devices after the %d solo "
                    "worker(s) — xl group(s) share their devices "
                    "(dispatches contend; size data_parallel + "
                    "xl_workers*%d <= %d local devices to avoid this)",
                    serve_cfg.xl_mesh, len(self.devices), size,
                    len(jax.local_devices()))
        if not groups_devs:
            log.warning(
                "xl_mesh=%s skipped: this replica has %d local "
                "device(s) but the mesh needs %d x %d worker group(s) "
                "— serving WITHOUT the xl tier (big requests fall back "
                "to tiling / solo dispatch)", serve_cfg.xl_mesh,
                len(jax.local_devices()), size, serve_cfg.xl_workers)
            return
        label = mesh_spec_label(spec)
        model = (self.model if xl_cfg == self.effective_config
                 else RAFTStereo(xl_cfg))
        groups = []
        for devs in groups_devs:
            mesh = make_mesh(n_data=1, n_corr=spec["corr"],
                             n_rows=spec["rows"], devices=devs)
            repl = NamedSharding(mesh, P())
            groups.append(_XlGroup(
                devices=tuple(devs), mesh=mesh,
                variables=jax.device_put(self._host_variables, repl),
                sharding=repl))
        self.xl = _XlTier(spec=spec, label=label, size=size, model=model,
                          groups=groups)
        self._xl_sizes = tuple(sorted(set(
            int(s) for s in serve_cfg.xl_batch_sizes)))
        log.info("xl tier up: mesh %s (%s), %d group(s) of %d device(s), "
                 "routing buckets > %d px (and ?tier=xl)",
                 serve_cfg.xl_mesh, label, len(groups), size,
                 serve_cfg.xl_threshold_pixels)

    @property
    def xl_enabled(self) -> bool:
        return self.xl is not None

    def _worker_count(self) -> int:
        return len(self.devices) + (len(self.xl.groups)
                                    if self.xl is not None else 0)

    def _is_xl_worker(self, widx: int) -> bool:
        return widx >= len(self.devices)

    def _xl_group(self, widx: int) -> _XlGroup:
        return self.xl.groups[widx - len(self.devices)]

    def _xl_worker_indices(self) -> List[int]:
        if self.xl is None:
            return []
        return list(range(len(self.devices),
                          len(self.devices) + len(self.xl.groups)))

    def _xl_compatible(self, bucket: Tuple[int, int]
                       ) -> Tuple[bool, str]:
        """Whether this padded bucket satisfies the xl mesh's geometry
        (trunk row divisibility, rows_gru window constraints).  The /32
        pad guarantees most production shapes pass; the ones that don't
        fall through to tiling with the reason logged."""
        if self.xl is None:
            return False, "no xl mesh on this engine"
        cfg = self.xl.model.config
        rows = cfg.rows_shards
        h = int(bucket[0])
        if rows > 1:
            if h % (4 * rows):
                return False, (f"padded H={h} not divisible by 4*rows="
                               f"{4 * rows} (stride-2 trunk stages)")
            from raft_stereo_tpu.parallel.rows_sharded import DEFAULT_HALO
            if h // rows < DEFAULT_HALO:
                return False, (f"per-shard rows H/rows={h // rows} < "
                               f"trunk halo {DEFAULT_HALO}")
            if cfg.rows_gru:
                from raft_stereo_tpu.parallel.rows_gru import \
                    validate_rows_gru
                try:
                    validate_rows_gru(cfg, h // cfg.downsample_factor,
                                      rows)
                except ValueError as e:
                    return False, str(e)
        return True, ""

    def _xl_routes(self, bucket: Tuple[int, int]) -> bool:
        """Whether a stateless request at this bucket routes to the xl
        family automatically (the prewarm/readiness surface uses the
        same predicate, so the warm target matches real routing)."""
        px = bucket[0] * bucket[1]
        return (self.xl is not None
                and px > self.serve_cfg.xl_threshold_pixels
                and (self.serve_cfg.xl_max_pixels is None
                     or px <= self.serve_cfg.xl_max_pixels)
                and self._xl_compatible(bucket)[0])

    def xl_status(self) -> Optional[Dict[str, object]]:
        """One JSON-able line for /healthz: the tier's topology and
        routing threshold, or None when this engine serves without it."""
        if self.xl is None:
            return None
        return {"mesh": self.serve_cfg.xl_mesh, "label": self.xl.label,
                "groups": len(self.xl.groups),
                "devices_per_group": self.xl.size,
                "threshold_pixels": self.serve_cfg.xl_threshold_pixels,
                "batch_sizes": list(self._xl_sizes)}

    # ----------------------------------------------------------- back-compat
    @property
    def batcher(self) -> BucketQueue:
        """Round-6 name for the request queue (healthz / CLI used
        ``service.batcher.depth``)."""
        return self.queue

    def quality_status(self) -> Optional[Dict[str, object]]:
        """Online quality posture (``GET /quality``): rolling per-tier
        mean confidence, good/bad totals vs the floor, drift-watchdog
        state, the quality SLO burn, and — with the cascade on — the
        draft/escalation split.  None when confidence telemetry is off
        (the endpoint 404s, keeping the off wire surface unchanged)."""
        if self.quality is None:
            return None
        out = self.quality.status()
        if self._cascade_draft is not None:
            out["cascade"] = {
                "draft": self._cascade_draft,
                "escalate": self._cascade_escalate,
                "threshold": self.serve_cfg.cascade_threshold,
                "drafts": self._cascade_drafts.value,
                "escalated": self._cascade_escalations.value,
            }
        return out

    # ------------------------------------------------------------ front door
    def bucket_for(self, shape: Tuple[int, int, int]) -> Tuple[int, int]:
        """The padded (Hp, Wp) this image shape dispatches at."""
        return self.policy.bucket_for(shape[0], shape[1])[:2]

    def _dispatch_latency_estimate(self, group_key: Tuple,
                                   batch_size: int) -> Optional[float]:
        """The measured per-dispatch wall (device + fetch EWMA) of one
        queue group — the EDF scheduler's slack subtrahend.  None before
        the group's first dispatch."""
        with self._latency_lock:
            return self._dispatch_latency_s.get(group_key)

    def _note_dispatch_latency(self, group_key: Tuple,
                               seconds: float) -> None:
        with self._latency_lock:
            prev = self._dispatch_latency_s.get(group_key)
            self._dispatch_latency_s[group_key] = (
                seconds if prev is None else 0.7 * prev + 0.3 * seconds)

    def resolve_tier(self, tier: Optional[str]) -> Optional[str]:
        """The tier a request actually runs at: the named one (validated),
        or the default tier when tiers are configured, or None (the base
        fixed-depth path) when they are not."""
        if tier is None:
            return self.default_tier
        if tier not in self.tiers:
            raise ValueError(
                f"unknown tier {tier!r}: this engine serves "
                f"{sorted(self.tiers) or '(no tiers configured)'}")
        return tier

    def submit(self, left: np.ndarray, right: np.ndarray,
               deadline_ms: Optional[float] = None,
               tier: Optional[str] = None,
               degradable: bool = True,
               model: Optional[str] = None,
               trace_context=None) -> Future:
        """Admit one stereo pair; returns a Future of ``ServeResult``.

        ``tier`` selects a configured latency tier (``ServeConfig.tiers``)
        — requests of different tiers run different compiled programs and
        never share a dispatch; None runs the default tier (or the base
        fixed-depth path when no tiers are configured).  Raises
        ``Overloaded`` at the door when the queue is full or the engine is
        draining; the Future fails with ``DeadlineExceeded`` if the
        request's deadline passes before a device picks it up, or with
        ``RequestPoisoned`` if its dispatch crashes on every bounded
        retry.  Under active brownout (``ServeConfig.brownout``) an
        eligible request is rerouted down the tier ladder —
        ``degradable=False`` opts this request out (the HTTP layer maps
        the X-No-Degrade header here), and ``brownout_exempt_tiers``
        opts a whole tier out; a degraded result carries
        ``requested_tier`` / ``degraded``.

        Big-image routing (round 17): with an xl tier configured
        (``ServeConfig.xl_mesh``), a request whose padded bucket exceeds
        ``xl_threshold_pixels`` — or that names ``tier="xl"`` explicitly
        — dispatches ONE mesh-sharded executable on an xl device group
        (result ``tier`` reads "xl", ``mesh`` carries the topology
        label).  Past ``tile_threshold_pixels`` (or when the bucket does
        not fit the mesh geometry) the request is answered by
        halo-overlap tiling instead: equal-height row tiles ride the
        ordinary batcher and the stitched result carries ``tiles`` /
        ``seam_epe``.  Naming ``tier="xl"`` without an xl tier, or for
        a mesh-incompatible bucket, raises ``ValueError`` (HTTP 400).

        ``model`` (round 21) selects a REGISTERED model version
        (``?model=`` / X-Model); None runs the default-model pointer
        (the implicit constructor model unless a hot swap flipped it).
        Unknown/retiring names raise the typed ``ModelUnknown``
        (HTTP 404).  Requests of different models never share a
        dispatch (the queue groups by model) and named models never
        route to the xl mesh (its replicated weights are the implicit
        model's).

        ``tier="auto"`` (round 24) is the confidence-gated cascade
        pseudo-tier (requires ``ServeConfig.cascade``): the request runs
        on the cheap draft tier first and re-runs on the quality tier
        ONLY when the draft's mean confidence falls below
        ``cascade_threshold``.  The result's ``tier`` is whichever tier
        produced the answer, with ``escalated`` / ``draft_tier`` /
        ``draft_confidence`` provenance; beyond the tiling threshold
        the gate applies per halo tile.

        ``trace_context`` (round 23) is an upstream ``TraceContext``
        decoded from an inbound ``traceparent`` header: the request's
        ``serve.request`` span ADOPTS that trace id and parents to the
        caller's span (the fleet router's ``route.forward``), bypassing
        this engine's local sample rate — the upstream sampling decision
        already happened.  None (the default) keeps the local-sampling
        behavior byte-for-byte.
        """
        t_admit = clock()
        model = self.resolve_model(model)
        left, right = np.asarray(left), np.asarray(right)
        if left.ndim != 3 or left.shape != right.shape:
            raise ValueError(
                f"need two same-shape (H, W, 3) images, got {left.shape} "
                f"vs {right.shape}")
        bucket = self.policy.bucket_for(left.shape[0], left.shape[1])[:2]
        if tier == "auto":
            # Confidence-gated cascade (round 24): draft cheap, escalate
            # only low-confidence answers.  A pseudo-tier like "xl" —
            # resolved here, never a queue coordinate of its own.
            if self._cascade_draft is None:
                raise ValueError(
                    "tier 'auto' requested but this engine has no "
                    "cascade (configure ServeConfig.cascade / --cascade "
                    "with confidence telemetry on)")
            return self._submit_cascade(left, right, deadline_ms,
                                        degradable, t_admit, model,
                                        trace_context=trace_context)
        want_xl = tier == "xl"
        if want_xl and self.xl is None:
            raise ValueError(
                "tier 'xl' requested but this engine has no xl tier "
                "(configure ServeConfig.xl_mesh / --xl_mesh, and enough "
                "devices for the mesh)")
        if want_xl and model is not None:
            raise ValueError(
                f"tier 'xl' serves only the implicit constructor model "
                f"(the mesh groups replicate its weights); model "
                f"{model!r} cannot ride it")
        if (model is None and self.xl is not None
                and (want_xl or self._xl_routes(bucket))):
            ok, reason = self._xl_compatible(bucket)
            if ok:
                # Fixed-depth full-precision program: no tier ladder, no
                # brownout rung below it — the request IS the expensive
                # kind brownout protects the rest of the fleet from.
                return self._enqueue(left, right, deadline_ms, None,
                                     None, t_admit,
                                     family=FAMILY_XL,
                                     trace_context=trace_context).future
            if want_xl:
                raise ValueError(
                    f"tier 'xl': bucket {bucket[0]}x{bucket[1]} does "
                    f"not fit mesh {self.serve_cfg.xl_mesh}: {reason}")
            log.info("bucket %sx%s exceeds xl_threshold_pixels but does "
                     "not fit mesh %s (%s) — falling through to "
                     "tiling/solo dispatch", bucket[0], bucket[1],
                     self.serve_cfg.xl_mesh, reason)
        tier, requested_tier = self._admit_tier(tier, degradable)
        tt = self.serve_cfg.tile_threshold_pixels
        if tt is not None and bucket[0] * bucket[1] > tt:
            return self._submit_tiled(left, right, deadline_ms, tier,
                                      requested_tier, t_admit, model,
                                      trace_context=trace_context)
        return self._enqueue(left, right, deadline_ms, tier,
                             requested_tier, t_admit,
                             model=model,
                             trace_context=trace_context).future

    def _admit_tier(self, tier: Optional[str], degradable: bool
                    ) -> Tuple[Optional[str], Optional[str]]:
        """Resolve the requested tier and apply brownout degradation:
        ``(effective_tier, requested_tier_if_degraded)``."""
        tier = self.resolve_tier(tier)
        requested_tier = None
        if (self.brownout is not None and degradable
                and tier not in self.serve_cfg.brownout_exempt_tiers):
            # Victim selection (round 24): the tier's recent rolling
            # mean confidence, when tracked, spares already-struggling
            # streams from degradation (resilience.degrade).  None
            # (confidence off) keeps the unconditional ladder.
            conf = (self.quality.mean_confidence(tier)
                    if self.quality is not None else None)
            effective = self.brownout.degrade(tier, confidence=conf)
            if effective != tier:
                requested_tier, tier = tier, effective
        return tier, requested_tier

    def _enqueue(self, left: np.ndarray, right: np.ndarray,
                 deadline_ms: Optional[float], tier: Optional[str],
                 requested_tier: Optional[str], t_admit: float,
                 family: Optional[str] = FAMILY_BASE,
                 session=None, session_id: Optional[str] = None,
                 flow_init: Optional[np.ndarray] = None,
                 thumb: Optional[np.ndarray] = None,
                 frame_index: Optional[int] = None,
                 scene_cut: bool = False,
                 frame_delta_v: Optional[float] = None,
                 ctx_init=None, hidden_init=None,
                 model: Optional[str] = None,
                 trace_context=None) -> Request:
        """Pad, build, trace, and queue one request — shared by the
        stateless ``submit`` (base family, no session fields) and the
        streaming ``submit_session``.  ``model`` is the RESOLVED
        registered-model name (None = implicit) — it joins the queue
        group key, so models never share a dispatch."""
        hp, wp, grid = self.policy.bucket_for(left.shape[0], left.shape[1])
        padder = InputPadder((1,) + left.shape, divis_by=grid)
        l, r, t, b = padder.pads
        spec = ((t, b), (l, r), (0, 0))
        payload = _Payload(left=np.pad(left, spec, mode="edge"),
                           right=np.pad(right, spec, mode="edge"),
                           padder=padder, flow_init=flow_init,
                           hidden_init=hidden_init,
                           session=session, thumb=thumb,
                           raw_shape=tuple(left.shape[:2]),
                           frame_index=frame_index, scene_cut=scene_cut,
                           frame_delta=frame_delta_v, ctx_init=ctx_init)
        now = clock()
        deadline_ms = (deadline_ms if deadline_ms is not None
                       else self.serve_cfg.default_deadline_ms)
        req = Request(bucket=(hp, wp), payload=payload,
                      future=Future(), t_enqueue=now, tier=tier,
                      requested_tier=requested_tier,
                      family=family, session_id=session_id,
                      model=model,
                      deadline=(None if deadline_ms is None
                                else now + deadline_ms / 1e3))
        # Per-model in-flight accounting (retire_model's drain signal):
        # incremented before the queue sees the request, decremented by
        # the future resolving — admission-to-resolution coverage, so a
        # retiring model's pytree is never evicted under a live
        # dispatch.  The Overloaded path below decrements explicitly
        # (a refused request's future never resolves).
        self._note_pending(model, +1)
        req.future.add_done_callback(
            lambda f, m=model: self._note_pending(m, -1))
        # Sampled request: root span + admission (validate/pad) span; the
        # queue span opens here and closes at worker pickup (_run_chunk)
        # or in the done-callback for requests dropped in the queue.  An
        # upstream trace context (the router's traceparent) ADOPTS the
        # caller's trace id — serve.request parents to the router's
        # route.forward span and the local sample rate is bypassed (the
        # sampling decision already happened one hop up).
        trace_attrs = dict(
            bucket=str(req.bucket), deadline_ms=deadline_ms,
            **({"tier": tier} if tier is not None else {}),
            **({"session": session_id} if session_id is not None else {}))
        if trace_context is not None:
            trace = self.tracer.adopt_trace(trace_context,
                                            "serve.request",
                                            **trace_attrs)
        else:
            trace = self.tracer.start_trace("serve.request",
                                            **trace_attrs)
        # (admission starts in whichever submit took the request and ends
        # here: no scope covers it, so it has no event on the profiler)
        self.phases.record("admission", t_admit, now,
                           () if trace is None else (trace,),
                           bucket=str(req.bucket))
        if trace is not None:
            req.trace = trace
            req.queue_span = self.tracer.start_span("serve.queue", trace)
            req.future.add_done_callback(
                lambda f, r=req: self._finish_request_trace(r, f))
        try:
            self.queue.submit(req)     # raises Overloaded at the door
        except Overloaded:
            self._note_pending(model, -1)   # refused: future never resolves
            if trace is not None and trace.root is not None:
                trace.root.set_attr("status", "overloaded")
                self._finish_request_trace(req, None)
            raise
        if requested_tier is not None:
            self.metrics.degraded.inc()
            if trace is not None and trace.root is not None:
                trace.root.set_attr("degraded_from", requested_tier)
        return req

    def _finish_request_trace(self, req: Request, future) -> None:
        """Close the queue span (if no worker picked the request up) and
        the root span; idempotence guards the two close paths (worker
        pickup vs future resolution)."""
        qs = req.queue_span
        if qs is not None and qs.t_end is None:
            self.tracer.finish(qs)
        root = req.trace.root if req.trace is not None else None
        if root is not None and root.t_end is None:
            if future is not None:
                exc = future.exception()
                root.set_attr("status",
                              "ok" if exc is None else type(exc).__name__)
            self.tracer.finish(root)

    def infer(self, left: np.ndarray, right: np.ndarray,
              deadline_ms: Optional[float] = None,
              timeout: Optional[float] = None,
              tier: Optional[str] = None,
              degradable: bool = True,
              model: Optional[str] = None,
              trace_context=None) -> ServeResult:
        """Blocking convenience: submit + wait (the in-process client)."""
        return self.submit(left, right, deadline_ms, tier=tier,
                           degradable=degradable, model=model,
                           trace_context=trace_context
                           ).result(timeout=timeout)

    # ------------------------------------------------------ tiled dispatch
    def _submit_tiled(self, left: np.ndarray, right: np.ndarray,
                      deadline_ms: Optional[float], tier: Optional[str],
                      requested_tier: Optional[str],
                      t_admit: float,
                      model: Optional[str] = None,
                      trace_context=None) -> Future:
        """Answer one beyond-threshold pair as N halo-overlap row tiles
        through the ORDINARY bucket path (serving/tiles.py): every tile
        is an equal-height `_enqueue` at the same bucket/tier/family, so
        the continuous batcher coalesces them into batch-N dispatches —
        no new scheduler.  The returned Future resolves once every tile
        did, with the center-crop-stitched disparity and the measured
        seam error.  A tile failing (deadline, poisoning, shutdown)
        fails the whole request with that tile's typed error.  An
        ``Overloaded`` mid-tiling propagates to the caller; tiles
        admitted before the bound hit still run and are discarded (their
        futures resolve into a dead aggregate) — admission stays a
        single bounded door, unreserved."""
        from raft_stereo_tpu.serving import tiles as tiles_mod

        specs = tiles_mod.plan_tiles(left.shape[0],
                                     self.serve_cfg.tile_rows,
                                     self.serve_cfg.tile_halo)
        if len(specs) < 2:
            # Shorter than one tile extent: nothing to split.
            return self._enqueue(left, right, deadline_ms, tier,
                                 requested_tier, t_admit,
                                 model=model,
                                 trace_context=trace_context).future
        # Every tile adopts the same upstream context: N serve.request
        # subtrees under one trace id, all parented to the caller's span
        # — the tiled answer reads as one fan-out in the timeline.
        reqs = [self._enqueue(
                    np.ascontiguousarray(left[s.src0:s.src1]),
                    np.ascontiguousarray(right[s.src0:s.src1]),
                    deadline_ms, tier, requested_tier, t_admit,
                    model=model, trace_context=trace_context)
                for s in specs]
        agg: Future = Future()
        state = {"remaining": len(reqs), "done": False}
        lock = threading.Lock()

        def on_done(future):
            # One-shot resolution decided INSIDE the lock: the first
            # failing tile owns the aggregate; later tiles (including
            # other failures) are no-ops.
            action = None
            with lock:
                if state["done"]:
                    return
                if future.exception() is not None:
                    state["done"], action = True, "fail"
                else:
                    state["remaining"] -= 1
                    if state["remaining"] == 0:
                        state["done"], action = True, "finish"
            if action == "fail":
                agg.set_exception(future.exception())
            elif action == "finish":
                try:
                    self._finish_tiled(agg, reqs, specs, tier,
                                       requested_tier, t_admit, model)
                except BaseException as e:  # noqa: BLE001 — typed to caller
                    agg.set_exception(e)

        for req in reqs:
            req.future.add_done_callback(on_done)
        return agg

    def _finish_tiled(self, agg: Future, reqs: List[Request],
                      specs, tier: Optional[str],
                      requested_tier: Optional[str],
                      t_admit: float,
                      model: Optional[str] = None) -> None:
        """All tiles answered: stitch, measure the seam, resolve the
        aggregate.  Latency legs report the worst tile (the tiles ran
        concurrently); ``total_s`` is admission -> stitched."""
        from raft_stereo_tpu.serving import tiles as tiles_mod

        results = [r.future.result() for r in reqs]
        flow = tiles_mod.stitch([res.flow for res in results], specs)
        seam = tiles_mod.seam_epe([res.flow for res in results], specs)
        self.metrics.tiled_requests.inc()
        if seam is not None:
            self.metrics.tile_seam_epe.observe(seam)
        iters = [res.iters_used for res in results
                 if res.iters_used is not None]
        conf_map, conf_mean = self._stitch_confidence(results, specs)
        agg.set_result(ServeResult(
            flow=np.ascontiguousarray(flow),
            queue_wait_s=max(res.queue_wait_s for res in results),
            device_s=max(res.device_s for res in results),
            fetch_s=max(res.fetch_s for res in results),
            total_s=clock() - t_admit,
            batch_size=max(res.batch_size for res in results),
            iters_used=max(iters) if iters else None,
            tier=tier, requested_tier=requested_tier,
            attempts=max(res.attempts for res in results),
            tiles=len(reqs), seam_epe=seam,
            model=results[0].model,
            model_version=results[0].model_version,
            confidence=conf_map, confidence_mean=conf_mean,
            trace_id=results[0].trace_id))

    @staticmethod
    def _stitch_confidence(results: List["ServeResult"], specs
                           ) -> Tuple[Optional[np.ndarray],
                                      Optional[float]]:
        """Stitch per-tile confidence maps with the same halo-crop
        geometry as the disparity (confidence and disparity are both
        (H, W) row fields); (None, None) when confidence is off."""
        from raft_stereo_tpu.serving import tiles as tiles_mod

        if any(res.confidence is None for res in results):
            return None, None
        conf = np.ascontiguousarray(tiles_mod.stitch(
            [res.confidence for res in results], specs))
        return conf, float(conf.mean())

    # ------------------------------------------- confidence-gated cascade
    def _submit_cascade(self, left: np.ndarray, right: np.ndarray,
                        deadline_ms: Optional[float], degradable: bool,
                        t_admit: float, model: Optional[str] = None,
                        trace_context=None) -> Future:
        """The ``auto`` pseudo-tier: answer on the cheap draft tier
        first and escalate to the quality tier ONLY when the draft's own
        confidence map says the answer is doubtful.  Well-textured
        frames pay draft cost; the hard ones pay draft + quality — mean
        fleet cost tracks the EASY fraction of traffic instead of the
        worst case.  Beyond the tiling threshold the gate is per tile:
        only the doubtful rows of a large frame re-run at quality.

        The draft runs at the ADMITTED draft tier (brownout may degrade
        it further); escalation re-admits at escalation time so a
        brownout that deepened mid-request still applies."""
        tt = self.serve_cfg.tile_threshold_pixels
        bucket = self.policy.bucket_for(left.shape[0], left.shape[1])[:2]
        if tt is not None and bucket[0] * bucket[1] > tt:
            return self._submit_cascade_tiled(
                left, right, deadline_ms, degradable, t_admit, model,
                trace_context=trace_context)
        return self._cascade_one(left, right, deadline_ms, degradable,
                                 t_admit, model,
                                 trace_context=trace_context)

    def _cascade_one(self, left: np.ndarray, right: np.ndarray,
                     deadline_ms: Optional[float], degradable: bool,
                     t_admit: float, model: Optional[str] = None,
                     trace_context=None) -> Future:
        """One draft -> (maybe) escalate chain for a single pair; the
        returned Future resolves with whichever answer survived, carrying
        full provenance (``draft_tier``, ``draft_confidence``,
        ``escalated``)."""
        draft = self._cascade_draft
        threshold = self.serve_cfg.cascade_threshold
        agg: Future = Future()
        draft_tier, draft_requested = self._admit_tier(draft, degradable)
        dreq = self._enqueue(left, right, deadline_ms, draft_tier,
                             draft_requested, t_admit, model=model,
                             trace_context=trace_context)

        def on_draft(future):
            exc = future.exception()
            if exc is not None:
                agg.set_exception(exc)
                return
            res = future.result()
            conf = res.confidence_mean
            if conf is None or conf >= threshold:
                # Confident (or confidence unavailable — fail open to
                # the draft rather than double every request's cost).
                res.draft_tier = draft_tier
                res.draft_confidence = conf
                res.total_s = clock() - t_admit
                if self._cascade_drafts is not None:
                    self._cascade_drafts.inc()
                agg.set_result(res)
                return
            if self._cascade_escalations is not None:
                self._cascade_escalations.inc()
            try:
                esc_tier, esc_requested = self._admit_tier(
                    self._cascade_escalate, degradable)
                ereq = self._enqueue(left, right, deadline_ms, esc_tier,
                                     esc_requested, t_admit, model=model,
                                     trace_context=trace_context)
            except BaseException as e:  # noqa: BLE001 — typed to caller
                agg.set_exception(e)
                return

            def on_escalated(f2):
                exc2 = f2.exception()
                if exc2 is not None:
                    agg.set_exception(exc2)
                    return
                res2 = f2.result()
                res2.escalated = True
                res2.draft_tier = draft_tier
                res2.draft_confidence = conf
                res2.total_s = clock() - t_admit
                agg.set_result(res2)

            ereq.future.add_done_callback(on_escalated)

        dreq.future.add_done_callback(on_draft)
        return agg

    def _submit_cascade_tiled(self, left: np.ndarray, right: np.ndarray,
                              deadline_ms: Optional[float],
                              degradable: bool, t_admit: float,
                              model: Optional[str] = None,
                              trace_context=None) -> Future:
        """Per-tile cascade for beyond-threshold pairs: every halo tile
        runs its own draft -> escalate chain (``_cascade_one``), so only
        the low-confidence ROWS of a large frame pay quality-tier cost.
        Stitching and seam measurement mirror ``_finish_tiled``."""
        from raft_stereo_tpu.serving import tiles as tiles_mod

        specs = tiles_mod.plan_tiles(left.shape[0],
                                     self.serve_cfg.tile_rows,
                                     self.serve_cfg.tile_halo)
        if len(specs) < 2:
            return self._cascade_one(left, right, deadline_ms,
                                     degradable, t_admit, model,
                                     trace_context=trace_context)
        futs = [self._cascade_one(
                    np.ascontiguousarray(left[s.src0:s.src1]),
                    np.ascontiguousarray(right[s.src0:s.src1]),
                    deadline_ms, degradable, t_admit, model,
                    trace_context=trace_context)
                for s in specs]
        agg: Future = Future()
        state = {"remaining": len(futs), "done": False}
        lock = threading.Lock()

        def on_done(future):
            action = None
            with lock:
                if state["done"]:
                    return
                if future.exception() is not None:
                    state["done"], action = True, "fail"
                else:
                    state["remaining"] -= 1
                    if state["remaining"] == 0:
                        state["done"], action = True, "finish"
            if action == "fail":
                agg.set_exception(future.exception())
            elif action == "finish":
                try:
                    self._finish_cascade_tiled(agg, futs, specs, t_admit)
                except BaseException as e:  # noqa: BLE001 — typed to caller
                    agg.set_exception(e)

        for fut in futs:
            fut.add_done_callback(on_done)
        return agg

    def _finish_cascade_tiled(self, agg: Future, futs: List[Future],
                              specs, t_admit: float) -> None:
        """All per-tile cascades answered: stitch (disparity AND
        confidence), report the ESCALATED tier when any tile escalated
        (the cost actually paid), keep per-tile draft provenance in the
        aggregate's ``draft_confidence`` (worst tile — the gate that
        mattered)."""
        from raft_stereo_tpu.serving import tiles as tiles_mod

        results = [f.result() for f in futs]
        flow = tiles_mod.stitch([res.flow for res in results], specs)
        seam = tiles_mod.seam_epe([res.flow for res in results], specs)
        self.metrics.tiled_requests.inc()
        if seam is not None:
            self.metrics.tile_seam_epe.observe(seam)
        iters = [res.iters_used for res in results
                 if res.iters_used is not None]
        conf_map, conf_mean = self._stitch_confidence(results, specs)
        escalated = any(res.escalated for res in results)
        final = next((res for res in results if res.escalated),
                     results[0])
        draft_confs = [res.draft_confidence for res in results
                       if res.draft_confidence is not None]
        agg.set_result(ServeResult(
            flow=np.ascontiguousarray(flow),
            queue_wait_s=max(res.queue_wait_s for res in results),
            device_s=max(res.device_s for res in results),
            fetch_s=max(res.fetch_s for res in results),
            total_s=clock() - t_admit,
            batch_size=max(res.batch_size for res in results),
            iters_used=max(iters) if iters else None,
            tier=final.tier, requested_tier=final.requested_tier,
            attempts=max(res.attempts for res in results),
            tiles=len(futs), seam_epe=seam,
            model=results[0].model,
            model_version=results[0].model_version,
            confidence=conf_map, confidence_mean=conf_mean,
            escalated=escalated,
            draft_tier=results[0].draft_tier,
            draft_confidence=min(draft_confs) if draft_confs else None,
            trace_id=results[0].trace_id))

    # ---------------------------------------------------- streaming sessions
    def submit_session(self, session_id: str, left: np.ndarray,
                       right: np.ndarray,
                       deadline_ms: Optional[float] = None,
                       tier: Optional[str] = None,
                       degradable: bool = True,
                       handoff_key: Optional[str] = None,
                       model: Optional[str] = None,
                       trace_context=None) -> Future:
        """Admit one frame of a streaming session (the engine behind
        ``POST /v1/stream/<session>``).  Returns a Future of
        ``ServeResult`` whose session fields say what happened:
        ``warm`` (the GRU was seeded from the previous frame's
        disparity), ``scene_cut`` (the inter-frame delta check failed and
        the frame cold-started), ``frame_index``, ``frame_delta``.

        First frame of a new id creates the session and cold-starts;
        every subsequent frame warm-starts unless the resolution changed,
        the previous frame failed, or the scene-cut gate fired.  Raises
        the typed ``SessionExpired`` (HTTP 410) on a TTL-expired /
        LRU-evicted / closed id and ``SessionsDisabled`` when the engine
        has no session store.

        **Ordering:** the session's ordering lock is held from here until
        the frame's future resolves, so a session never has two frames
        in flight and a dispatch cycle can never reorder its frames —
        the call blocks while the previous frame of the SAME session is
        still pending (distinct sessions proceed concurrently and batch
        together freely).  Every admitted frame terminates (success or
        typed error; round-13 guarantee), so the lock cannot be held
        forever.

        **Model pinning (round 21):** a session PINS the model its
        first frame resolved (the explicit ``model`` or the
        then-current default) — later frames run that model even if a
        hot swap flips the default mid-stream, so no session ever
        receives frames from two different versions.  A later frame
        naming a DIFFERENT model than the pin raises ``ValueError``
        (HTTP 400); a frame whose pinned model was retired raises the
        typed ``ModelUnknown`` (404 — open a fresh session)."""
        if self.sessions is None:
            raise SessionsDisabled(
                "this engine runs without a session store — construct it "
                "with ServeConfig(sessions=True) to stream")
        t_admit = clock()
        tier, requested_tier = self._admit_tier(tier, degradable)
        left, right = np.asarray(left), np.asarray(right)
        if left.ndim != 3 or left.shape != right.shape:
            raise ValueError(
                f"need two same-shape (H, W, 3) images, got {left.shape} "
                f"vs {right.shape}")
        sess, created = self.sessions.get_or_create(session_id)
        # One frame per session in the pipeline: block until the previous
        # frame's future resolved (its done-callback releases the lock).
        sess.order_lock.acquire()
        try:
            if created and handoff_key is not None:
                # Lazy handoff adoption (round 18): the router tagged
                # this id's first frame here with the draining replica's
                # published blob — import THAT session's state so this
                # frame warm-starts exactly where the old replica left
                # off.  Any failure (missing blob, corrupt entry,
                # unregistered pinned model) just leaves ``created``
                # true: the frame cold-starts, which is the pre-handoff
                # baseline.
                created = not self._adopt_handoff(sess, session_id,
                                                  handoff_key)
            if created:
                # Pin the model at session birth: the explicit name or
                # the CURRENT default — frames of this stream run it
                # for the session's whole life, hot swaps
                # notwithstanding.
                sess.model = self.resolve_model(model)
            else:
                pinned = sess.model
                if model is not None and model != pinned:
                    raise ValueError(
                        f"session {session_id!r} is pinned to model "
                        f"{pinned or '(implicit)'} — a mid-stream "
                        f"switch to {model!r} would mix versions; open "
                        f"a new session")
                if pinned is not None:
                    # Retired mid-stream -> typed 404 on the next frame.
                    self.resolve_model(pinned)
            req_model = sess.model
            thumb = frame_thumbnail(left)
            hp, wp, _grid = self.policy.bucket_for(left.shape[0],
                                                   left.shape[1])
            hidden_on = self.serve_cfg.session_hidden
            warm = (not created and sess.flow_low is not None
                    and sess.bucket == (hp, wp)
                    and sess.raw_shape == tuple(left.shape[:2])
                    # warm-h programs consume BOTH state halves: a
                    # session missing its hidden tree (dropped at
                    # export, invalidated by a crash) cold-starts
                    # rather than feeding the warm-h executable a
                    # fabricated trajectory.
                    and (not hidden_on or sess.hidden is not None))
            scene_cut = False
            delta = None
            if warm:
                delta = frame_delta(thumb, sess.thumb)
                if delta is not None:
                    self.metrics.frame_delta.observe(delta)
                    if (self.serve_cfg.scene_cut_threshold > 0
                            and delta > self.serve_cfg.scene_cut_threshold):
                        # The previous disparity field belongs to a scene
                        # this frame is not in: a warm start would anchor
                        # the GRU to garbage, so fall back to cold (the
                        # session survives — state re-seeds from this
                        # frame's result).
                        warm, scene_cut = False, True
                        sess.scene_cuts += 1
                        self.metrics.scene_cuts.inc()
            # Family routing with the ctx cache on: cold frames SAVE the
            # context bundle (state_ctx); a warm frame whose measured
            # delta proves the scene static REUSES it (warm_ctx — the
            # context encoder never runs); a warm frame past the gate
            # runs plain warm AND the bundle is dropped at completion
            # (the scene moved; a stale context is a silent accuracy
            # leak, so it re-establishes at the next cold frame).
            ctx_on = self.serve_cfg.session_ctx_cache
            ctx_init = None
            if warm:
                family = FAMILY_WARM_H if hidden_on else FAMILY_WARM
                if (ctx_on and sess.ctx is not None and delta is not None
                        and delta <= self.serve_cfg.ctx_cache_threshold):
                    family = (FAMILY_WARM_CTX_H if hidden_on
                              else FAMILY_WARM_CTX)
                    ctx_init = sess.ctx
            elif ctx_on:
                family = (FAMILY_STATE_CTX_H if hidden_on
                          else FAMILY_STATE_CTX)
            else:
                family = FAMILY_STATE_H if hidden_on else FAMILY_STATE
            req = self._enqueue(
                left, right, deadline_ms, tier, requested_tier, t_admit,
                family=family,
                session=sess, session_id=session_id,
                flow_init=sess.flow_low if warm else None,
                hidden_init=(sess.hidden if warm and hidden_on
                             else None),
                ctx_init=ctx_init,
                thumb=thumb, frame_index=sess.frame_index,
                scene_cut=scene_cut, frame_delta_v=delta,
                model=req_model, trace_context=trace_context)
        except BaseException:
            sess.order_lock.release()
            raise
        req.future.add_done_callback(
            lambda f, r=req: self._finish_session_frame(r, f))
        return req.future

    def infer_session(self, session_id: str, left: np.ndarray,
                      right: np.ndarray,
                      deadline_ms: Optional[float] = None,
                      timeout: Optional[float] = None,
                      tier: Optional[str] = None,
                      degradable: bool = True,
                      handoff_key: Optional[str] = None,
                      model: Optional[str] = None,
                      trace_context=None) -> ServeResult:
        """Blocking convenience: submit_session + wait."""
        return self.submit_session(
            session_id, left, right, deadline_ms, tier=tier,
            degradable=degradable, handoff_key=handoff_key,
            model=model,
            trace_context=trace_context).result(timeout=timeout)

    # ------------------------------------------------------ session handoff
    def exec_config_fingerprint(self) -> str:
        """SHA-256 identity of the compiled surface a handed-off session
        would re-enter here: the effective model config (architecture,
        precision, quant — array geometry and dtypes of every state
        tree) plus the serving knobs that pick the session executable
        families (``session_hidden`` / ``session_ctx_cache``), the GRU
        depth cap, and the fetch dtype.  Stamped onto every published
        handoff blob; an importer whose fingerprint differs refuses the
        blob TYPED (``serve_handoff_import_skipped_total{reason=
        "config_mismatch"}``) instead of silently installing state its
        programs cannot consume — deliberately coarse: any drift costs
        one cold start per stream, which is the cheap failure."""
        import hashlib

        payload = {
            "model": self.effective_config.to_json(),
            "session_hidden": self.serve_cfg.session_hidden,
            "session_ctx_cache": self.serve_cfg.session_ctx_cache,
            "iters": self.serve_cfg.iters,
            "fetch_dtype": self.serve_cfg.fetch_dtype,
        }
        if self.default_model is not None:
            # The default-model coordinate joins the fingerprint ONLY
            # when a registered model holds the pointer (the implicit
            # default keeps the pre-registry fingerprint byte-stable):
            # a handoff exported under one default version is refused
            # typed-cold by an importer whose default moved — never a
            # wrong-weights warm frame.
            bundle = self._models[self.default_model]
            payload["default_model"] = bundle.coord
        import json as json_mod
        return hashlib.sha256(
            json_mod.dumps(payload, sort_keys=True).encode()).hexdigest()

    def _handoff_records(self, key: str) -> Dict:
        """Parsed ``{sid: (meta, arrays)}`` of one published handoff
        blob, fetched and decoded at most once per key (N inherited
        sessions share one artifact read).  A blob stamped with a
        DIFFERENT exec-config fingerprint than this engine's is refused
        wholesale — every session it carries counts into
        ``serve_handoff_import_skipped_total{reason="config_mismatch"}``
        and cold-starts (the r18 follow-up: mismatch is typed, never a
        silent wrong-geometry import)."""
        with self._handoff_lock:
            cached = self._handoff_blobs.get(key)
        if cached is not None:
            return cached
        records: Dict = {}
        if self.handoff_store is not None:
            blob = self.handoff_store.fetch(key)
            if blob is not None:
                from raft_stereo_tpu.serving.sessions import (
                    handoff_fingerprint, handoff_session_ids)
                stamped = handoff_fingerprint(blob)
                mine = self.exec_config_fingerprint()
                if stamped is not None and stamped != mine:
                    n = len(handoff_session_ids(blob))
                    self.metrics.observe_handoff_skip("config_mismatch",
                                                      n)
                    log.warning(
                        "handoff artifact %s was exported under exec-"
                        "config %.12s but this engine compiles %.12s; "
                        "refusing %d session(s) — they cold-start "
                        "(config_mismatch)", key[:12], stamped, mine, n)
                else:
                    records, skipped = parse_handoff_blob(blob)
                    if skipped:
                        self.metrics.observe_handoff_skip("corrupt",
                                                          skipped)
            else:
                log.warning("handoff artifact %s not in the store; its "
                            "sessions cold-start", key)
        with self._handoff_lock:
            self._handoff_blobs[key] = records
            # A replica inherits from at most a handful of concurrent
            # drains; keep the parse cache from growing across weeks of
            # rolling restarts.
            while len(self._handoff_blobs) > 8:
                self._handoff_blobs.pop(next(iter(self._handoff_blobs)))
        return records

    def _adopt_handoff(self, sess, sid: str, key: str) -> bool:
        """Install the handed-off state for ``sid`` from blob ``key``
        into the freshly created session; True when adopted (the frame
        may warm-start).  A session pinned to a model THIS engine does
        not serve is refused typed (it cold-starts on whatever this
        engine's default is — never a wrong-weights warm frame)."""
        rec = self._handoff_records(key).get(sid)
        if rec is None:
            return False
        meta, arrays = rec
        pinned = meta.get("model") if isinstance(meta, dict) else None
        if pinned is not None:
            bundle = self._models.get(pinned)
            if bundle is None or bundle.retiring:
                self.metrics.observe_handoff_skip("model_unknown", 1)
                log.warning(
                    "session %s was pinned to model %r which this "
                    "engine does not serve — refusing its handed-off "
                    "state (cold start)", sid, pinned)
                return False
        self.sessions.adopt(sess, meta, arrays)
        sess.model = pinned
        self.metrics.sessions_adopted.inc()
        log.info("session %s adopted from handoff %s at frame %s "
                 "(imported warm-start state)", sid, key[:12],
                 sess.frame_index)
        return True

    def publish_handoff(self) -> Optional[Dict[str, object]]:
        """Serialize every live session into the artifact store's
        ``sessions/`` namespace and remember the manifest ``GET
        /admin/handoff`` serves (cli/serve.py calls this at SIGTERM,
        after ``begin_shutdown``).  Returns the manifest — with
        ``artifact=None`` when there was nothing to export (an empty
        manifest is still an ANSWER: the router learns definitively
        that no sessions need remapping).  None only when this engine
        cannot hand off at all (no session store, or no shared artifact
        directory) — the router then falls back to the r16 typed-loss
        path when the process exits."""
        if self.sessions is None or self.handoff_store is None:
            return None
        blob = self.sessions.export(
            config_fingerprint=self.exec_config_fingerprint())
        sids = handoff_session_ids(blob)
        key = None
        if sids:
            key = self.handoff_store.publish(blob)
            if key is None:
                log.warning("session handoff publish failed; %d "
                            "session(s) will fail typed on exit instead",
                            len(sids))
                sids = []
            else:
                self.metrics.sessions_exported.inc(len(sids))
        manifest = {"artifact": key, "sessions": sids,
                    "count": len(sids), "published_unix": time.time(),
                    "config_fingerprint": self.exec_config_fingerprint()}
        self._handoff_manifest = manifest
        log.info("session handoff published: %d session(s) -> %s",
                 len(sids), key and key[:12])
        return manifest

    @property
    def handoff_manifest(self) -> Optional[Dict[str, object]]:
        """The drain handoff manifest (None until ``publish_handoff``
        ran) — what ``GET /admin/handoff`` serves."""
        return self._handoff_manifest

    def note_handoff_fetched(self) -> None:
        """The HTTP layer records that a router fetched the manifest —
        the CLI's post-drain linger can stop waiting."""
        self._handoff_fetched.set()

    def wait_handoff_fetched(self, timeout: float) -> bool:
        return self._handoff_fetched.wait(timeout)

    def close_session(self, session_id: str) -> Dict[str, object]:
        """End one session deliberately (``DELETE /v1/stream/<id>``);
        returns its lifetime stats.  Raises ``SessionsDisabled`` /
        ``SessionExpired`` / ``KeyError`` like the store."""
        if self.sessions is None:
            raise SessionsDisabled("this engine runs without a session "
                                   "store")
        return self.sessions.close(session_id)

    def _finish_session_frame(self, req: Request, future) -> None:
        """Completion hook of one session frame: fold the result's state
        back into the session (under the ordering lock, so the next
        frame — possibly already blocked in ``submit_session`` — reads a
        consistent snapshot), then release the lock.  A failed frame
        releases without touching state: the session's previous state
        stays the warm-start source, and the scene-cut delta check
        guards against it having gone stale."""
        sess = req.payload.session
        try:
            if future.exception() is None:
                res = future.result()
                flow_low = res.flow_low
                reseed = False
                if (self.serve_cfg.session_reseed_on_cap and res.warm
                        and res.iters_used is not None
                        and res.iters_used >= self.serve_cfg.iters
                        and early_exit_enabled(
                            self._models[req.model].tier_models[
                                self._cache_tier(req.tier, req.model)
                            ].config)):
                    # Keyframe guard (ServeConfig.session_reseed_on_cap):
                    # the gate never fired, so this warm output is not a
                    # trusted init — drop the state and let the next
                    # frame cold-start.
                    flow_low = None
                    reseed = True
                    self.metrics.session_reseeds.inc()
                if self.serve_cfg.session_ctx_cache:
                    if res.ctx is not None:
                        # Cold state_ctx frame: (re-)establish the bundle.
                        sess.ctx = res.ctx
                    elif reseed or (res.warm and not res.ctx_cached):
                        # Invalidated: the keyframe guard fired, or a
                        # warm frame ran past the static-scene gate —
                        # either way the cached context no longer
                        # describes the scene; it re-establishes at the
                        # next cold frame.
                        sess.ctx = None
                    if res.ctx_cached:
                        sess.ctx_hits += 1
                        self.metrics.ctx_cache_hits.inc()
                sess.note_result(
                    flow_low=flow_low, thumb=req.payload.thumb,
                    bucket=req.bucket, raw_shape=req.payload.raw_shape,
                    warm=res.warm, iters_used=res.iters_used,
                    # The hidden tree rides (and drops) with the flow
                    # state: the keyframe guard's flow_low=None above
                    # zeroes both halves inside note_result.
                    hidden=res.hidden,
                    confidence=res.confidence_mean)
                self.metrics.observe_session_frame(
                    "warm" if res.warm else "cold")
        finally:
            # The dispatch counts as session activity: a first-frame
            # compile longer than the TTL must not expire the stream.
            self.sessions.touch(req.session_id)
            sess.order_lock.release()

    # ------------------------------------------------------------ readiness
    @property
    def ready(self) -> bool:
        """The /readyz gate: every configured (worker, bucket, batch,
        tier-family) warm entry has dispatched at least once.  True at
        boot when no ``warmup_shapes`` are configured — an engine with no
        declared warm surface is ready by definition (it just pays
        first-request compiles, as before).  False the moment a graceful
        shutdown begins (``begin_shutdown``): the fleet router reads
        this as "stop routing here" while queued work still drains.
        Chaos slow-start (``ChaosConfig.slow_start_s``) also holds the
        gate closed — the replica a failover test brings up slowly."""
        if self._shutting_down or self._closed:
            return False
        if self.chaos is not None and self.chaos.ready_blocked():
            return False
        with self._warm_lock:
            return self._warm_target <= self._warmed

    def warm_status(self) -> Dict[str, object]:
        """Readiness detail for /readyz: progress through the configured
        bucket x batch x tier ladder, plus the disk-cache counters that
        say whether warmness came from disk or from XLA."""
        with self._warm_lock:
            done = len(self._warm_target & self._warmed)
            total = len(self._warm_target)
            ready = self._warm_target <= self._warmed
        out: Dict[str, object] = {"ready": ready and self.ready,
                                  "warm_done": done,
                                  "warm_target": total,
                                  "draining": self._shutting_down}
        out["compiles_cold"] = self.metrics.compiles_cold.value
        out["compiles_warm"] = self.metrics.compiles_warm.value
        if self.disk_cache is not None:
            out["executable_cache"] = self.disk_cache.stats()
        # The registry joins the readiness detail ONLY when named
        # models exist — a single-model engine's payload stays
        # byte-identical to the pre-registry build.
        if len(self._models) > 1 or self.default_model is not None:
            out["models"] = self.models_status()
        return out

    def _note_warm(self, widx: int, bucket: Tuple[int, int], batch: int,
                   cache_tier: Optional[str],
                   family: Optional[str] = FAMILY_BASE,
                   model: Optional[str] = None) -> None:
        with self._warm_lock:
            self._warmed.add((widx, tuple(bucket), batch, cache_tier,
                              family, model))

    def _families(self) -> Tuple[Optional[str], ...]:
        """The executable families this engine serves: the base program
        always; the session state/warm variants only when the session
        store exists (so a stateless engine's compile surface, prewarm
        cost, and readiness target are exactly the round-13 ones); the
        ctx-cache variants replace state/warm when the per-session
        context cache is on (cold frames must SAVE the bundle for warm
        frames to reuse, so plain "state" never runs there); with
        ``session_hidden`` every session family swaps for its ``_h``
        variant (all session programs must carry the hidden tree —
        otherwise one un-carried frame would silently break the warm-h
        chain)."""
        if self.sessions is None:
            return (FAMILY_BASE,)
        hidden = self.serve_cfg.session_hidden
        if self.serve_cfg.session_ctx_cache:
            if hidden:
                return (FAMILY_BASE, FAMILY_STATE_CTX_H, FAMILY_WARM_H,
                        FAMILY_WARM_CTX_H)
            return (FAMILY_BASE, FAMILY_STATE_CTX, FAMILY_WARM,
                    FAMILY_WARM_CTX)
        if hidden:
            return (FAMILY_BASE, FAMILY_STATE_H, FAMILY_WARM_H)
        return (FAMILY_BASE, FAMILY_STATE, FAMILY_WARM)

    # ------------------------------------------------------- tier variables
    def _vars_for(self, widx: int, cache_tier: Optional[str],
                  model: Optional[str] = None):
        """The variable tree a tier's executables consume on one worker:
        the bundle's resident fp32 tree for full-precision tiers, the
        bundle's per-worker int8 tree for quant tiers (built lazily,
        host-quantized once per bundle — disk checkpoints stay fp32).
        Two models with identical shapes NEVER share a variables slot:
        each bundle owns its own device placements."""
        if self._is_xl_worker(widx):
            # xl workers consume the tree replicated over their group's
            # mesh (one host->devices placement per group at boot);
            # tiers never apply there — xl is fixed-depth fp, implicit
            # model only.
            return self._xl_group(widx).variables
        bundle = self._models[model]
        if bundle.tier_models[cache_tier].config.quant == "off":
            return bundle.worker_vars[widx]
        import jax

        with self._qvars_lock:
            dev = bundle.qvars.get(widx)
            if dev is None:
                if bundle.qvars_host is None:
                    from raft_stereo_tpu.quant import quantize_variables
                    # One int8 tree serves every quant tier of the
                    # bundle: the calibrated activation scales ride the
                    # packs as an extra member that the weights-only
                    # "int8" mode's in-program dequant simply ignores,
                    # while "int8_mxu" executables read them as their
                    # static input-quantization constants.
                    bundle.qvars_host = quantize_variables(
                        bundle.host_variables,
                        act_scales=self._quant_act_scales)
                dev = jax.device_put(bundle.qvars_host,
                                     self.devices[widx])
                bundle.qvars[widx] = dev
        return dev

    def _ctx_avals(self, cfg, bucket: Tuple[int, int], batch: int):
        """Abstract shapes of one context bundle at ``bucket`` — what the
        AOT persistent-cache path lowers the ctx families with and what
        prewarm feeds as zeros (models/raft_stereo.py: per-level initial
        hidden states + (cz, cr, cq) biases at 1/2^(downsample+l))."""
        import jax
        import jax.numpy as jnp

        dt = jnp.bfloat16 if cfg.mixed_precision else jnp.float32
        f = cfg.downsample_factor
        nets, ctxs = [], []
        for l in range(cfg.n_gru_layers):
            h = bucket[0] // (f * 2 ** l)
            w = bucket[1] // (f * 2 ** l)
            c = cfg.hidden_dims[l]
            nets.append(jax.ShapeDtypeStruct((batch, h, w, c), dt))
            ctxs.append(tuple(jax.ShapeDtypeStruct((batch, h, w, c), dt)
                              for _ in range(3)))
        return (tuple(nets), tuple(ctxs))

    def _hidden_avals(self, cfg, bucket: Tuple[int, int], batch: int):
        """Abstract shapes of one hidden-state tree at ``bucket`` — the
        per-level evolved GRU states the warm-h families consume
        (identical geometry to the ctx bundle's net half)."""
        return self._ctx_avals(cfg, bucket, batch)[0]

    # --------------------------------------------------------- compile cache
    def _cache_tier(self, tier: Optional[str],
                    model: Optional[str] = None) -> Optional[str]:
        """The executable-cache key a tier compiles under: None when the
        tier's model IS the bundle's base model (fixed-depth tiers share
        the base executables — one program, one cost record, bitwise
        parity)."""
        bundle = self._models[model]
        if tier is None or bundle.tier_models.get(tier) is bundle.model:
            return None
        return tier

    def _distinct_cache_tiers(self, model: Optional[str] = None
                              ) -> List[Optional[str]]:
        """The DISTINCT executable families the configured tiers compile
        to ("quality" and the base path normalize to one cache key) —
        what prewarm and the readiness target iterate, per model."""
        tiers = tuple(self.tiers) if self.tiers else (None,)
        return sorted({self._cache_tier(t, model) for t in tiers},
                      key=lambda t: (t is not None, t or ""))

    def _cost_key(self, bucket: Tuple[int, int], batch: int,
                  tier: Optional[str] = None,
                  family: Optional[str] = FAMILY_BASE,
                  model: Optional[str] = None) -> str:
        """Stable label of one compile point in the cost registry — what
        GET /debug/compiles lists and the MFU path looks up.  The quant
        mode joins the key exactly like the family tag (the r14
        warm/state split): an int8 tier's executable must never share a
        cost record with the full-precision program of the same
        (bucket, batch).  A registered model's coordinate joins LAST
        (",model=name@version") — the implicit model's keys stay
        byte-identical to the pre-registry build."""
        if family == FAMILY_XL:
            # The mesh label IS the family coordinate for xl (the
            # ISSUE's ",mesh=rows4" contract): an xl executable must
            # never share a cost record with the solo program of the
            # same (bucket, batch).
            label = self.xl.label if self.xl is not None else "none"
            return (f"serving.forward({bucket[0]}x{bucket[1]},b{batch}"
                    f",mesh={label})")
        bundle = self._models[model]
        cache_tier = self._cache_tier(tier, model)
        tail = "" if cache_tier is None else f",tier={tier}"
        qmode = bundle.tier_models[cache_tier].config.quant
        if qmode != "off":
            tail += f",quant={qmode}"
        if self.serve_cfg.confidence:
            # The confidence variant returns two extra outputs — a
            # different program, so a different cost record.  Off keeps
            # every key byte-identical to the round-23 build.
            tail += ",conf"
        if family is not None:
            tail += f",{family}"
        if bundle.name is not None:
            tail += f",model={bundle.coord}"
        return f"serving.forward({bucket[0]}x{bucket[1]},b{batch}{tail})"

    def compiled_cost(self, bucket: Tuple[int, int], batch: int = 1,
                      tier: Optional[str] = None,
                      family: Optional[str] = FAMILY_BASE,
                      model: Optional[str] = None):
        """The cost record for a compiled (bucket, batch) executable, or
        None (no registry / not compiled yet / analysis degraded)."""
        if self.costs is None:
            return None
        return self.costs.get(self._cost_key(bucket, batch, tier, family,
                                             model))

    def _forward_for(self, bucket: Tuple[int, int], batch: int = 1,
                     worker: int = 0, tier: Optional[str] = None,
                     family: Optional[str] = FAMILY_BASE,
                     model: Optional[str] = None):
        """The compiled batch-``batch`` executable for ``bucket`` on
        ``worker``'s device — the engine-owned cache the round-6 design
        spread across per-worker InferenceRunners.  Bounded per worker at
        ``max_cached_shapes`` (bucket, batch, tier, family, model)
        entries, oldest evicted."""
        tier = self._cache_tier(tier, model)
        bundle = self._models[model]
        key = (worker, tuple(bucket), batch, tier, family, model)
        with self._cache_lock:
            if key in self._compiled:
                self._compiled[key] = self._compiled.pop(key)  # LRU refresh
                return self._compiled[key]
        # Build + (with cost telemetry) AOT-instrument outside the lock —
        # distinct keys may compile concurrently on different workers.
        if family == FAMILY_XL:
            # The mesh-sharded program over this worker's device group
            # (eval/runner.make_forward_mesh); base arity, fixed depth.
            fwd = make_forward_mesh(
                self.xl.model, self.serve_cfg.iters,
                self._xl_group(worker).mesh,
                self._fetch_jax_dtype(),
                donate_images=self.serve_cfg.donate_buffers)
        else:
            fwd = make_forward(
                bundle.tier_models[tier], self.serve_cfg.iters,
                self._fetch_jax_dtype(),
                donate_images=self.serve_cfg.donate_buffers,
                warm_start=(family in _WARM_FAMILIES),
                return_state=(family is not FAMILY_BASE
                              and family != FAMILY_XL),
                ctx=("save" if family in _CTX_SAVE_FAMILIES
                     else "reuse" if family in _CTX_REUSE_FAMILIES
                     else None),
                hidden_init=(family in _H_IN_FAMILIES),
                return_hidden=(family in _H_OUT_FAMILIES),
                return_confidence=self.serve_cfg.confidence)
        if self.disk_cache is not None:
            fwd = self._load_or_compile(fwd, bucket, batch, worker, tier,
                                        family, model)
        else:
            # No persistent cache: the executable is built by XLA (at
            # first dispatch on the plain-jit path, inside instrument on
            # the cost path) — a cold compile either way.
            self.metrics.compiles_cold.inc()
            if self.costs is not None:
                fwd = self.costs.instrument(
                    fwd, key=self._cost_key(bucket, batch, tier, family,
                                            model),
                    site="serving", model=bundle.coord)
        with self._cache_lock:
            mine = [k for k in self._compiled if k[0] == worker]
            while len(mine) >= self.serve_cfg.max_cached_shapes:
                evicted = mine.pop(0)
                self._compiled.pop(evicted)
                log.info(
                    "engine compile cache full (max_cached_shapes=%d): "
                    "evicting oldest executable for bucket %s batch %d "
                    "tier %s family %s model %s on worker %d — its next "
                    "use re-pays XLA compile time",
                    self.serve_cfg.max_cached_shapes, evicted[1],
                    evicted[2], evicted[3], evicted[4], evicted[5],
                    evicted[0])
                if self.costs is not None:
                    self.costs.note_runner_eviction(
                        self._cost_key(*evicted[1:]), len(mine))
            self._compiled[key] = fwd
            if self.costs is not None:
                self.costs.note_runner_cache_size(len(self._compiled))
        return fwd

    def _disk_key(self, bucket: Tuple[int, int], batch: int,
                  worker: int, cache_tier: Optional[str],
                  family: Optional[str] = FAMILY_BASE,
                  model: Optional[str] = None) -> str:
        """The persistent-cache content key of one compile point: every
        coordinate that selects a distinct program, plus the device the
        serialized executable is bound to (persist.py mixes in the
        jax/backend fingerprint).  ``family`` / ``flow_init`` encode the
        streaming-program arity — a warm executable takes an extra
        traced input and returns the low-res state, so it must NEVER
        share a disk entry with the sessionless program of the same
        (config, bucket, batch, tier)."""
        from raft_stereo_tpu.serving.persist import executable_cache_key

        if family == FAMILY_XL:
            # The xl coordinates: the sharded config JSON (rows_shards /
            # corr_w2_shards / rows_gru live inside it), the explicit
            # mesh label (belt and braces, like quant below), and the
            # WHOLE device group — a serialized sharded executable is
            # bound to its device assignment, so groups never share an
            # entry.
            group = self._xl_group(worker)
            return executable_cache_key(
                config=self.xl.model.config.to_json(),
                bucket=tuple(bucket), batch=int(batch),
                tier=None, iters=self.serve_cfg.iters,
                fetch_dtype=self.serve_cfg.fetch_dtype,
                donate=self.serve_cfg.donate_buffers,
                family=FAMILY_XL, flow_init=False,
                mesh=self.xl.label, device=group.label)
        bundle = self._models[model]
        # Registered models join the key ONLY as extra kwargs (the
        # content hash is over sorted kwargs JSON), so the implicit
        # model's keys — no model kwargs at all — stay byte-identical
        # to the pre-registry build (the bitwise single-model pin).
        extra = {}
        if bundle.name is not None:
            extra = {"model": bundle.name,
                     "model_version": bundle.version}
        if self.serve_cfg.confidence:
            # Confidence variants return two extra outputs — a distinct
            # program, so a distinct disk entry.  Joins as an extra
            # kwarg ONLY when on, so confidence-off keys stay
            # byte-identical to the round-23 build (the bitwise pin).
            extra["confidence"] = True
        return executable_cache_key(
            config=bundle.tier_models[cache_tier].config.to_json(),
            bucket=tuple(bucket), batch=int(batch),
            tier=cache_tier, iters=self.serve_cfg.iters,
            fetch_dtype=self.serve_cfg.fetch_dtype,
            donate=self.serve_cfg.donate_buffers,
            family=family, flow_init=(family in _WARM_FAMILIES),
            # The hidden-tree arity (round 19): warm-h programs take an
            # extra traced input tree and every _h program returns one —
            # the family string above already separates them, but the
            # explicit coordinate keeps the key self-describing.
            hidden=(family in _H_IN_FAMILIES),
            # Belt and braces for the int8 tier: the quant mode is
            # already inside the config JSON above, but it also keys
            # explicitly — a quantized and a base executable consume
            # DIFFERENT input trees (int8 packs vs fp32 kernels) and
            # must never collide on one disk entry (tests/test_quant.py).
            quant=bundle.tier_models[cache_tier].config.quant,
            device=str(getattr(self.devices[worker], "id", worker)),
            **extra)

    def _load_or_compile(self, fwd, bucket: Tuple[int, int], batch: int,
                         worker: int, cache_tier: Optional[str],
                         family: Optional[str] = FAMILY_BASE,
                         model: Optional[str] = None):
        """The persistent-cache build path: deserialize the executable
        from disk (warm — no XLA compile paid) or AOT-compile it now and
        store it for the next boot (cold).  Either way the cost registry
        (when attached) gets its record, so /debug/compiles stays the
        complete executable inventory.  Falls back to the plain callable
        when the AOT machinery is unavailable — the cache can never take
        the dispatch path down."""
        import jax

        bundle = self._models[model]
        disk_key = self._disk_key(bucket, batch, worker, cache_tier,
                                  family, model)
        t0 = time.perf_counter()
        exe = self.disk_cache.load(
            disk_key, devices=(self._xl_group(worker).devices
                               if family == FAMILY_XL
                               else [self.devices[worker]]))
        if exe is not None:
            self.metrics.compiles_warm.inc()
            log.info("bucket %s batch %d tier %s family %s model %s "
                     "worker %d: executable restored from persistent "
                     "cache in %.3fs",
                     bucket, batch, cache_tier, family, bundle.coord,
                     worker, time.perf_counter() - t0)
            if self.costs is not None:
                self.costs.record(
                    self._cost_key(bucket, batch, cache_tier, family,
                                   model),
                    "serving", time.perf_counter() - t0, compiled=exe,
                    model=bundle.coord)
            return exe
        aval = jax.ShapeDtypeStruct((batch, bucket[0], bucket[1], 3),
                                    np.uint8)
        avals = [aval, aval]
        tier_cfg = (self.xl.model.config if family == FAMILY_XL
                    else bundle.tier_models[cache_tier].config)
        if family in _WARM_FAMILIES:
            f = tier_cfg.downsample_factor
            avals.append(jax.ShapeDtypeStruct(
                (batch, bucket[0] // f, bucket[1] // f), np.float32))
        if family in _H_IN_FAMILIES:
            avals.append(self._hidden_avals(tier_cfg, bucket, batch))
        if family in _CTX_REUSE_FAMILIES:
            avals.append(self._ctx_avals(tier_cfg, bucket, batch))
        try:
            compiled = fwd.lower(self._vars_for(worker, cache_tier,
                                                model),
                                 *avals).compile()
        except Exception:
            log.warning("AOT compile for the persistent cache failed; "
                        "falling back to plain jit dispatch (this "
                        "executable will not be cached)", exc_info=True)
            self.metrics.aot_compile_failures.inc()
            self.metrics.compiles_cold.inc()
            if self.costs is not None:
                return self.costs.instrument(
                    fwd, key=self._cost_key(bucket, batch, cache_tier,
                                            family, model),
                    site="serving", model=bundle.coord)
            return fwd
        compile_s = time.perf_counter() - t0
        self.metrics.compiles_cold.inc()
        if self.costs is not None:
            self.costs.record(
                self._cost_key(bucket, batch, cache_tier, family, model),
                "serving", compile_s, compiled=compiled,
                model=bundle.coord)
        self.disk_cache.store(
            disk_key, compiled,
            meta={"bucket": list(bucket), "batch": int(batch),
                  "tier": cache_tier, "family": family,
                  "iters": self.serve_cfg.iters,
                  "quant": tier_cfg.quant,
                  "model": bundle.coord,
                  "mesh": (self.xl.label if family == FAMILY_XL
                           else None),
                  "fetch_dtype": self.serve_cfg.fetch_dtype,
                  "compile_s": round(compile_s, 3)})
        return compiled

    def _fetch_jax_dtype(self):
        import jax.numpy as jnp

        fetch = self.serve_cfg.fetch_dtype
        if fetch not in (None, "fp16", "bf16"):
            raise ValueError(f"fetch_dtype={fetch!r}: use 'fp16', 'bf16', "
                             f"or None (full fp32 fetch)")
        return {None: None, "fp16": jnp.float16,
                "bf16": jnp.bfloat16}[fetch]

    def prewarm(self, raw_hw: Tuple[int, int],
                batch_sizes: Optional[Sequence[int]] = None,
                tiers: Optional[Sequence[Optional[str]]] = None,
                models: Optional[Sequence[Optional[str]]] = None) -> None:
        """Compile + warm the whole bucket ladder for one raw shape on
        every worker: each configured batch size dispatches once with
        zero images, so the first real requests at this shape hit warm
        executables (and, with cost telemetry, the registry holds every
        ladder rung's cost record at boot).  With latency tiers
        configured, every tier's executable family is warmed (fixed-depth
        tiers share the base executables, so the ladder compiles once per
        DISTINCT program, not once per tier name).  ``models`` limits
        the pass to specific registered models (None = every served
        model, implicit first) — the hot-swap path warms just the new
        arrival."""
        import jax

        h, w = int(raw_hw[0]), int(raw_hw[1])
        hp, wp, _ = self.policy.bucket_for(h, w)
        if self._xl_routes((hp, wp)):
            # This bucket's traffic dispatches on the xl mesh groups —
            # warm THAT surface (and only it; the solo ladder at this
            # size would compile programs no request runs).  Implicit
            # model only: named models never route xl.
            if models is None or None in models:
                self._prewarm_xl((hp, wp), batch_sizes)
            return
        sizes = tuple(batch_sizes) if batch_sizes else self.queue.sizes
        model_names = (list(models) if models is not None
                       else self._registered_names())
        for mname in model_names:
            if tiers is None:
                cache_tiers = self._distinct_cache_tiers(mname)
            else:
                # Distinct executable families only: "quality" and the
                # base path normalize to the same cache key.
                cache_tiers = sorted(
                    {self._cache_tier(t, mname) for t in tiers},
                    key=lambda t: (t is not None, t or ""))
            bundle = self._models[mname]
            for widx, dev in enumerate(self.devices):
                for tier in cache_tiers:
                    for n in sizes:
                        for family in self._families():
                            fwd = self._forward_for(
                                (hp, wp), n, worker=widx,
                                tier=tier, family=family, model=mname)
                            zeros = np.zeros((n, hp, wp, 3), np.uint8)
                            args = [self._vars_for(widx, tier, mname),
                                    jax.device_put(zeros, dev),
                                    jax.device_put(zeros.copy(), dev)]
                            tier_cfg = bundle.tier_models[tier].config
                            if family in _WARM_FAMILIES:
                                f = tier_cfg.downsample_factor
                                args.append(jax.device_put(
                                    np.zeros((n, hp // f, wp // f),
                                             np.float32), dev))
                            if family in _H_IN_FAMILIES:
                                import jax.tree_util as jtu
                                args.append(jtu.tree_map(
                                    lambda s: jax.device_put(
                                        np.zeros(s.shape, s.dtype), dev),
                                    self._hidden_avals(tier_cfg, (hp, wp),
                                                       n)))
                            if family in _CTX_REUSE_FAMILIES:
                                import jax.tree_util as jtu
                                ctx_zeros = jtu.tree_map(
                                    lambda s: jax.device_put(
                                        np.zeros(s.shape, s.dtype), dev),
                                    self._ctx_avals(tier_cfg, (hp, wp), n))
                                args.append(ctx_zeros)
                            out = fwd(*args)
                            jax.block_until_ready(out)
                            self._note_warm(widx, (hp, wp), n, tier,
                                            family, mname)
        log.info("prewarmed bucket %dx%d batch sizes %s (%d model(s) x "
                 "tier families x %d program variant(s)) on %d "
                 "worker(s)",
                 hp, wp, sizes, len(model_names),
                 len(self._families()), len(self.devices))

    def _prewarm_xl(self, bucket: Tuple[int, int],
                    batch_sizes: Optional[Sequence[int]] = None) -> None:
        """Compile + warm the xl bucket ladder on every xl device group:
        each batch size dispatches once with zero images through the
        mesh-sharded program, the warm entries open /readyz, and (with
        cost telemetry) the per-device HBM gauge goes live."""
        import jax

        sizes = tuple(batch_sizes) if batch_sizes else self._xl_sizes
        for widx in self._xl_worker_indices():
            group = self._xl_group(widx)
            for n in sizes:
                fwd = self._forward_for(bucket, n, worker=widx,
                                        tier=None, family=FAMILY_XL)
                zeros = np.zeros((n, bucket[0], bucket[1], 3), np.uint8)
                out = fwd(group.variables,
                          jax.device_put(zeros, group.sharding),
                          jax.device_put(zeros.copy(), group.sharding))
                jax.block_until_ready(out)
                self._note_warm(widx, bucket, n, None, FAMILY_XL)
                self._note_xl_hbm(bucket, n)
        log.info("prewarmed XL bucket %dx%d batch sizes %s (mesh %s) on "
                 "%d device group(s)", bucket[0], bucket[1], sizes,
                 self.xl.label, len(self.xl.groups))

    def _note_xl_hbm(self, bucket: Tuple[int, int], batch: int) -> None:
        """Surface the xl executable's per-device HBM (CompileRecord
        memory_analysis) as serve_xl_hbm_bytes{mesh=,bucket=} — the
        sharding win as a live gauge.  No-op without cost telemetry or
        when the backend's analysis degraded."""
        rec = self.compiled_cost(bucket, batch=batch, family=FAMILY_XL)
        if rec is not None and rec.hbm_bytes:
            self.metrics.xl_hbm_gauge(
                self.xl.label, f"{bucket[0]}x{bucket[1]}"
            ).set(rec.hbm_bytes)

    # --------------------------------------------------------------- workers
    def _worker_loop(self, widx: int) -> None:
        """One device worker under supervision.  The circuit breaker
        gates the pop (an open circuit = this device takes no work); a
        dispatch crash hands the batch to the recovery path and then
        RESTARTS the worker thread — a crashed dispatch must never kill
        the server, and a fresh thread is the cheapest guarantee that no
        corrupted per-thread state survives the crash."""
        breaker = self.breakers[widx]
        # Worker-class pop filter: xl device-group workers take ONLY the
        # mesh-sharded xl groups (their own batch ladder); solo workers
        # take everything else.  One queue, one admission bound, one
        # drain — the filter is the whole scheduler change.
        want, sizes = None, None
        if self.xl is not None:
            if self._is_xl_worker(widx):
                want = lambda key: key[2] == FAMILY_XL  # noqa: E731
                sizes = self._xl_sizes
            else:
                want = lambda key: key[2] != FAMILY_XL  # noqa: E731
        while True:
            delay = breaker.until_allowed()
            if delay > 0:
                if self._closed:
                    return
                time.sleep(min(delay, 0.05))
                continue
            # The worker waits for work: an empty queue, or requests the
            # batcher holds back for a fuller batch (``queued_at_start``
            # tells them apart).
            with self.phases.phase("wait_work",
                                   queued_at_start=self.queue.depth) as wait:
                batch = self.queue.pop(want=want, sizes=sizes)
                wait.set(popped=len(batch or ()))
                wait.traces = [r.trace for r in batch or ()
                               if r.trace is not None]
            if batch is None:       # queue closed: worker shutdown
                return
            try:
                self._run_batch(widx, batch)
                breaker.record_success()
            except BaseException as e:  # noqa: BLE001 — recover, restart
                self._on_dispatch_failure(widx, batch, e)
                self.metrics.inflight.dec(len(batch))
                self._restart_worker(widx)
                return              # this thread exits; successor took over
            self.metrics.inflight.dec(len(batch))

    # ---------------------------------------------------- supervised recovery
    def _on_dispatch_failure(self, widx: int, batch: List[Request],
                             exc: BaseException) -> None:
        """The recovery path for one crashed dispatch: record the breaker
        failure, requeue the batch's unresolved requests with backoff, and
        poison the ones that exhausted their attempts.  Chunks of the
        batch that already completed (futures done) are untouched."""
        pending = [r for r in batch if not r.future.done()]
        log.exception("dispatch of %d request(s) crashed on worker %d "
                      "(%d unresolved)", len(batch), widx, len(pending))
        self.breakers[widx].record_failure()
        sink = self.sink
        if sink is not None:
            sink.fire("worker_crash", device=widx, batch_size=len(batch),
                      unresolved=len(pending),
                      error=f"{type(exc).__name__}: {exc}")
        retry: List[Request] = []
        now_pc = clock()
        for r in pending:
            r.attempts += 1
            if getattr(r.payload, "session", None) is not None:
                self._invalidate_crashed_session_frame(r)
            if r.attempts >= self.serve_cfg.max_dispatch_attempts:
                self.metrics.poisoned.inc()
                self.metrics.failed.inc()
                if r.trace is not None and r.trace.root is not None:
                    r.trace.root.set_attr("attempts", r.attempts)
                r.future.set_exception(RequestPoisoned(
                    f"dispatch crashed on all {r.attempts} attempts "
                    f"(last: {type(exc).__name__}: {exc})",
                    attempts=r.attempts, last_error=exc))
            else:
                retry.append(r)
        if not retry:
            return
        self.metrics.retries.inc(len(retry))
        attempt = max(r.attempts for r in retry)
        backoff_s = (self.serve_cfg.retry_backoff_ms / 1e3
                     * 2 ** (attempt - 1))
        for r in retry:
            if r.trace is not None:
                self.tracer.add_span(
                    "serve.retry", r.trace, now_pc, clock(),
                    attempt=r.attempts, device=widx,
                    backoff_ms=round(backoff_s * 1e3, 3),
                    error=type(exc).__name__)
        self._schedule_requeue(retry, backoff_s)

    def _invalidate_crashed_session_frame(self, req: Request) -> None:
        """A crashed dispatch carried this SESSION frame (r13 requeue x
        r14 submit_session cross): the flow this frame was supposed to
        produce never existed, so (a) a requeued WARM frame must not
        re-run the warm program against state the crash voided — a
        crash *caused by* that state (NaN init, poisoned buffer) would
        deterministically burn every retry attempt — and (b) the
        session's stored state must not seed any LATER frame across the
        gap.  Demote the requeued frame to the cold family (it
        cold-starts and, on success, re-seeds the chain exactly like a
        scene cut) and drop the session's warm-start state.  Mutating
        the session here is safe: its ordering lock is held by THIS
        frame from submit to resolution, so no other frame of the
        session can observe a torn state.  The ordering lock itself is
        released by the frame's future resolving (retry success or
        typed poisoning) — never leaked.  Regression:
        tests/test_sessions.py."""
        sess = req.payload.session
        if req.family in _WARM_FAMILIES:
            ctx_on = self.serve_cfg.session_ctx_cache
            if self.serve_cfg.session_hidden:
                req.family = (FAMILY_STATE_CTX_H if ctx_on
                              else FAMILY_STATE_H)
            else:
                req.family = FAMILY_STATE_CTX if ctx_on else FAMILY_STATE
            req.payload.flow_init = None
            req.payload.hidden_init = None
            req.payload.ctx_init = None
            log.warning("session %s frame %s: crashed warm dispatch "
                        "demoted to a cold start for its retry",
                        req.session_id, req.payload.frame_index)
        sess.flow_low = None
        sess.hidden = None
        sess.ctx = None

    def _schedule_requeue(self, reqs: List[Request],
                          delay_s: float) -> None:
        """Requeue ``reqs`` after ``delay_s`` on a backoff timer.  The
        pending-retry count keeps ``drain`` honest (requests in backoff
        are neither queued nor inflight) and ``close`` fails the timers'
        requests instead of stranding them."""
        with self._retry_lock:
            self._pending_retries += len(reqs)

        entry = None

        def _requeue():
            try:
                self.queue.requeue(reqs)   # closed queue -> typed failure
            finally:
                with self._retry_lock:
                    self._pending_retries -= len(reqs)
                    self._retry_timers.discard(entry)

        timer = threading.Timer(max(0.0, delay_s), _requeue)
        timer.daemon = True
        entry = (timer, tuple(reqs))
        with self._retry_lock:
            self._retry_timers.add(entry)
        timer.start()

    def _pending_retry_count(self) -> int:
        with self._retry_lock:
            return self._pending_retries

    def _restart_worker(self, widx: int) -> None:
        """Supervisor: replace a crashed worker thread with a fresh one
        on the same device (unless the engine is closing)."""
        with self._workers_lock:
            if self._closed:
                return
            t = threading.Thread(target=self._worker_loop, args=(widx,),
                                 daemon=True, name=f"stereo-worker-{widx}")
            # Start inside the lock so close() can never snapshot (and
            # try to join) a thread that was not started yet.
            self._workers[widx] = t
            t.start()
        self.metrics.worker_restarts.inc()
        log.warning("worker %d restarted after dispatch crash "
                    "(restart #%d)", widx,
                    self.metrics.worker_restarts.value)

    def _run_batch(self, widx: int, batch: List[Request]) -> None:
        """One popped batch.  The scheduler pops exact bucket sizes, but
        deadline triage can shrink a batch below the size it picked —
        decompose so every device dispatch still runs a compiled
        batch-size bucket."""
        sizes = (self._xl_sizes if batch[0].family == FAMILY_XL
                 else self.queue.sizes)
        i = 0
        for k in decompose_batch(len(batch), sizes):
            self._run_chunk(widx, batch[i:i + k])
            i += k

    def _run_chunk(self, widx: int, batch: List[Request]) -> None:
        import jax
        import jax.tree_util as jtu

        bucket = batch[0].bucket
        # The queue groups by (bucket, tier, family, model): every
        # member of this chunk shares all four coordinates.
        tier = batch[0].tier
        family = batch[0].family
        model = batch[0].model
        n = len(batch)
        xl = family == FAMILY_XL
        # Sampled requests: the phases below share the chunk's time window
        # but land in each request's own trace (a trace stays
        # self-contained).
        traces = [r.trace for r in batch if r.trace is not None]

        def phase(name: str, **attrs):
            return self.phases.phase(name, traces, batch_size=n, **attrs)

        with phase("assemble", bucket=str(bucket),
                   seq=next(self._dispatch_seq)) as assemble:
            # The queue leg ends at worker pickup: this phase's start.
            pickup = assemble.t_start
            for r in batch:
                if r.queue_span is not None and r.queue_span.t_end is None:
                    r.queue_span.set_attr("batch_size", n)
                    r.queue_span.t_end = pickup
                    self.tracer.finish(r.queue_span)
            bundle = self._models[model]
            cache_tier = self._cache_tier(tier, model)
            if xl:
                group = self._xl_group(widx)
                device = group.sharding   # replicated upload over the mesh
                device_label = f"xl:{group.label}"
            else:
                device = self.devices[widx]
                device_label = str(device)

            # Fault injection (serving/chaos.py): one attribute check when
            # chaos is off — the no-chaos dispatch path is the round-12
            # program, bitwise-unchanged (tests/test_resilience.py).  The
            # injected exceptions propagate into the worker loop's recovery
            # path exactly like organic faults.
            if self.chaos is not None:
                self.chaos.on_compile(widx)
                self.chaos.on_dispatch(widx)

            # ONE batch-n dispatch through the (bucket, n) executable.
            # n == 1 is the identical program the solo InferenceRunner
            # compiles (make_forward), so that bucket stays bitwise-equal
            # to solo inference; n > 1 amortizes the fixed per-dispatch
            # work across a real batch axis with zero filler frames.
            fwd = self._forward_for(bucket, n, worker=widx, tier=tier,
                                    family=family, model=model)
            adaptive = False if xl else early_exit_enabled(
                bundle.tier_models[cache_tier].config)
            variables = self._vars_for(widx, cache_tier, model)
            host_args = [np.stack([r.payload.left for r in batch]),
                         np.stack([r.payload.right for r in batch])]
            if family in _WARM_FAMILIES:
                # Warm session frames: the batch's previous-frame states
                # stack into the program's flow_init input.
                host_args.append(
                    np.stack([r.payload.flow_init for r in batch]
                             ).astype(np.float32))
            if family in _H_IN_FAMILIES:
                # Hidden warm start: the batch members' per-level hidden
                # trees stack leaf-wise (frames of DIFFERENT sessions
                # batch together; each leaf is per-image along axis 0).
                host_args.append(jtu.tree_map(
                    lambda *xs: np.stack(xs),
                    *[r.payload.hidden_init for r in batch]))
            if family in _CTX_REUSE_FAMILIES:
                # Context reuse: the batch members' cached bundles stack
                # leaf-wise (frames of DIFFERENT static-scene sessions
                # batch together; each leaf is per-image along axis 0).
                host_args.append(jtu.tree_map(
                    lambda *xs: np.stack(xs),
                    *[r.payload.ctx_init for r in batch]))

        with phase("upload", bytes=_host_bytes(host_args)) as upload:
            # ``device_put`` returns at once: the runtime lays the arrays
            # out and copies them behind it, and the launch waits for that
            # inside ``execute``.
            args = [variables] + [jax.device_put(a, device)
                                  for a in host_args]

        with phase("execute") as execute:
            out = fwd(*args)
            # The device leg's stop clock.
            jax.block_until_ready(out)

        with phase("fetch") as fetch:
            flow_low_padded = None
            ctx_out = None
            hidden_out = None
            if family in _CTX_SAVE_FAMILIES:
                # The ctx-saving cold program appends the context bundle
                # LAST (eval/runner.make_forward): peel it off, fetch it
                # to host leaves (numpy; bf16 leaves ride as ml_dtypes).
                out, ctx_dev = out[:-1], out[-1]
                ctx_out = jtu.tree_map(lambda x: np.asarray(x), ctx_dev)
            if family in _H_OUT_FAMILIES:
                # The hidden tree rides just before the ctx bundle
                # (return order: flow_up, flow_low[, iters][, conf]
                # [, hidden][, ctx]) — now the LAST remaining element.
                out, hidden_dev = out[:-1], out[-1]
                hidden_out = jtu.tree_map(lambda x: np.asarray(x),
                                          hidden_dev)
            conf_padded = None
            confidence_on = self.serve_cfg.confidence and not xl
            if confidence_on:
                # The confidence element — the model's (conf_low,
                # conf_up) pair — rides just before hidden/ctx, so after
                # those peels it is the last remaining element.  Only
                # the full-res map is served.
                out, conf_dev = out[:-1], out[-1]
                conf_padded = np.asarray(conf_dev[1])   # (n, Hp, Wp)
                if family is FAMILY_BASE and not adaptive:
                    # The base fixed-depth program returns a bare array
                    # without confidence; restore that arity for the
                    # shared unpack below.
                    out = out[0]
            if family is FAMILY_BASE or xl:
                if adaptive:
                    flows, iters_used_dev = out
                    iters_used = int(iters_used_dev)  # extra scalar fetch
                else:
                    flows, iters_used = out, self.serve_cfg.iters
            else:
                # Session families also return the padded low-res state
                # (and, adaptive, the trip count): (flow_up, flow_low[,
                # iters_used]) — eval/runner.make_forward.
                if adaptive:
                    flows, flow_low, iters_used_dev = out
                    iters_used = int(iters_used_dev)
                else:
                    (flows, flow_low), iters_used = out, self.serve_cfg.iters
                flow_low_padded = np.asarray(flow_low)  # (n, Hp/f, Wp/f)
            flows_padded = np.asarray(flows)      # (n, Hp, Wp)
            fetch.set(bytes=_host_bytes(
                [flows_padded, flow_low_padded, conf_padded, hidden_out,
                 ctx_out]))

        with phase("account"):
            # The legs the histograms, the response headers and the sampled
            # trace have always reported, from the phases' own readings:
            # device = pickup -> outputs ready (stack and upload included).
            device_s = assemble.seconds + upload.seconds + execute.seconds
            fetch_s = fetch.seconds
            for r in batch:
                if r.trace is not None:
                    self.tracer.add_span(
                        "serve.dispatch", r.trace, pickup, execute.t_end,
                        bucket=str(bucket), batch_size=n,
                        device=device_label, iters_used=iters_used,
                        attempt=r.attempts + 1,
                        **({"tier": tier} if tier is not None else {}))
            # Per-group dispatch-latency EWMA: the EDF scheduler's bounded
            # slack subtracts this from the nearest deadline.
            self._note_dispatch_latency(batch[0].group_key,
                                        device_s + fetch_s)
            self.metrics.observe_dispatch(n)
            if xl:
                self.metrics.xl_dispatches.inc()
                self._note_xl_hbm(bucket, n)
            # Trip-count telemetry: every dispatch lands in the per-tier
            # infer_gru_iters_used histogram (fixed-depth paths report the
            # configured depth, so tier histograms are directly comparable)
            # and early-exit dispatches accumulate the iterations they
            # saved.
            self.metrics.observe_iters_used(
                "xl" if xl else (tier or "default"), iters_used,
                self.serve_cfg.iters, n_requests=n)
            self.metrics.device_time.observe(device_s)
            self.metrics.fetch_time.observe(fetch_s)
            # Padding-waste accounting + the policy feedback loop: every
            # dispatched pixel beyond the requests' real image pixels is
            # pure waste at fixed GRU depth.  With the engine's
            # exact-occupancy batch axis the only waste left is spatial
            # padding — which is exactly what BucketPolicy.note adapts on.
            real_px = sum(r.payload.padder.ht * r.payload.padder.wd
                          for r in batch)
            dispatched_px = n * bucket[0] * bucket[1]
            self.metrics.observe_padding(bucket, real_px, dispatched_px)
            self.policy.note(bucket, real_px, dispatched_px)
            # MFU numerator: the batch-n executable's model flops, once per
            # dispatch.  NOTE XLA's cost_analysis counts a loop body ONCE
            # regardless of trip count (scan and while alike), so this
            # numerator never overstates under early exit and under-reads
            # a looped program; benchmark/flops.py counts from shapes
            # instead, and its step_mfu_pct is what a PR is judged on.
            if self._mfu is not None:
                rec = self.compiled_cost(bucket, batch=n, tier=tier,
                                         family=family, model=model)
                if rec is not None and rec.flops:
                    self.metrics.dispatched_flops.inc(rec.flops)
                    self._mfu.note(rec.flops)
            self.metrics.note_batch_done()
            if model is not None:
                # Per-model request accounting (named models only: the
                # implicit model's /metrics stay byte-identical to pre-
                # registry builds).
                self.metrics.observe_model_request(
                    bundle.name, bundle.version, n_requests=n)
            self._note_warm(widx, bucket, n, cache_tier, family, model)

        # One span a dispatch on the profiler and in the histogram; a
        # sampled request gets its own, around its own answer.
        with self.phases.phase("respond", batch_size=n):
            for i, (r, fp) in enumerate(zip(batch, flows_padded)):
                exemplar = r.trace.trace_id if r.trace is not None else None
                t_respond = clock() if exemplar is not None else 0.0
                flow = r.payload.padder.unpad(fp[None])[0]
                if flow.dtype != np.float32:         # half-precision fetch
                    flow = flow.astype(np.float32)
                wait = pickup - r.t_enqueue
                total = fetch.t_end - r.t_enqueue
                self.metrics.queue_wait.observe(wait, exemplar=exemplar)
                self.metrics.total_latency.observe(total, exemplar=exemplar)
                self.metrics.completed.inc()
                ctx_i = None
                if ctx_out is not None:
                    # Per-member slice of the batch's returned bundle: the
                    # session stores a batch-axis-free copy it can stack
                    # into any later dispatch.
                    ctx_i = jtu.tree_map(lambda leaf, j=i: leaf[j], ctx_out)
                hidden_i = None
                if hidden_out is not None:
                    hidden_i = jtu.tree_map(lambda leaf, j=i: leaf[j],
                                            hidden_out)
                conf_i = None
                conf_mean = None
                if conf_padded is not None:
                    conf_i = r.payload.padder.unpad(
                        conf_padded[i][None])[0]
                    if conf_i.dtype != np.float32:
                        conf_i = conf_i.astype(np.float32)
                    conf_i = np.ascontiguousarray(conf_i)
                    conf_mean = float(conf_i.mean())
                    if self.quality is not None:
                        self.quality.observe(tier or "default",
                                             bundle.coord, conf_mean,
                                             exemplar=exemplar)
                r.future.set_result(ServeResult(
                    flow=np.ascontiguousarray(flow), queue_wait_s=wait,
                    device_s=device_s, fetch_s=fetch_s, total_s=total,
                    batch_size=n, iters_used=iters_used,
                    tier="xl" if xl else tier,
                    mesh=self.xl.label if xl else None,
                    requested_tier=r.requested_tier,
                    attempts=r.attempts + 1,
                    session_id=r.session_id,
                    frame_index=r.payload.frame_index,
                    warm=(family in _WARM_FAMILIES),
                    scene_cut=r.payload.scene_cut,
                    frame_delta=r.payload.frame_delta,
                    flow_low=(np.ascontiguousarray(flow_low_padded[i])
                              if flow_low_padded is not None else None),
                    ctx_cached=(family in _CTX_REUSE_FAMILIES),
                    ctx=ctx_i,
                    hidden=hidden_i,
                    warm_hidden=(family in _H_IN_FAMILIES),
                    model=bundle.name,
                    model_version=bundle.version,
                    confidence=conf_i, confidence_mean=conf_mean,
                    trace_id=exemplar))
                if exemplar is not None:
                    self.tracer.add_span("serve.respond", r.trace,
                                         t_respond, clock())

    # ---------------------------------------------------------- fleet hooks
    def set_brownout_floor(self, level: int) -> int:
        """Fleet-wide degradation floor (``POST /admin/brownout``, pushed
        by the fleet router): the engine degrades at least this many
        rungs regardless of its local pressure signals, so the whole
        fleet steps down in lockstep instead of each replica flapping on
        its own queue.  Returns the effective level.  Raises
        ``RuntimeError`` when this engine runs without a brownout
        controller (``ServeConfig.brownout=False``)."""
        if self.brownout is None:
            raise RuntimeError(
                "this engine runs without a brownout controller "
                "(ServeConfig.brownout=False) — no ladder to degrade on")
        return self.brownout.set_floor(level)

    def begin_shutdown(self) -> None:
        """Phase one of graceful SIGTERM (cli/serve.py): flip ``ready``
        to False — /readyz answers 503 and the fleet router pulls this
        replica out of rotation within one health poll — and stop
        admitting (new submits shed with the typed draining
        ``Overloaded``), while queued + in-flight + backoff work keeps
        flowing and the HTTP server stays up to answer it.  ``drain()``
        then waits that work out and ``close()``s."""
        self._shutting_down = True
        self.queue.stop_admitting()

    # -------------------------------------------------------------- shutdown
    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful SIGTERM story: refuse new work (``Overloaded``), let
        the workers finish the queue, in-flight batches, AND any crashed
        requests sitting in retry backoff, then stop them.  Returns False
        if ``timeout`` elapsed first (workers are still stopped; any
        stranded requests fail rather than hang)."""
        self.queue.stop_admitting()
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        ok = True
        while (self.queue.depth > 0 or self.metrics.inflight.value > 0
               or self._pending_retry_count() > 0):
            if deadline is not None and time.monotonic() > deadline:
                ok = False
                break
            time.sleep(0.002)
        self.close()
        return ok

    def close(self) -> None:
        """Hard stop: closes the queue (queued requests fail with
        ``Overloaded``; blocked worker pops return None), cancels retry
        backoff timers (their requests fail the same typed way instead of
        hanging), stops the brownout controller, and joins the worker
        threads.  ``drain`` first for the graceful version."""
        if self._closed:
            return
        self._closed = True
        if self.brownout is not None:
            self.brownout.stop()
        self.queue.close()
        # Retry timers: cancel, then run each timer's requeue through the
        # now-closed queue so its requests get the typed shutdown failure
        # (requeue dedups, so racing an already-fired timer is safe).
        with self._retry_lock:
            entries = list(self._retry_timers)
        for timer, reqs in entries:
            timer.cancel()
            self.queue.requeue(list(reqs))
        with self._workers_lock:
            workers = list(self._workers)
        for t in workers:
            t.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# The engine IS the service: the round-6 class name stays importable for
# every existing call site (serving/http.py, cli/serve.py, tests).
StereoService = ServingEngine
