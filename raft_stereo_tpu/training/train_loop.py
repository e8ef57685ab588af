"""The full training runtime (reference: train_stereo.py:132-211 ``train``).

TPU-native structure: one jitted SPMD train step over a device mesh (batch
sharded along ``data``, state replicated, XLA derives the gradient psum);
host-side threaded data loading overlaps with device compute through jax's
async dispatch.  Improvements over the reference, by design:

* full train-state checkpoints (params + opt state + step) → exact resume
  (the reference saves weights only — train_stereo.py:184-186);
* periodic validation runs FlyingThings TEST like the reference
  (train_stereo.py:183-190) but is optional when datasets are absent.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import logging
import os
import signal
import threading
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
from raft_stereo_tpu.data.datasets import build_training_mixture
from raft_stereo_tpu.data.loader import StereoLoader
from raft_stereo_tpu.parallel import distributed
from raft_stereo_tpu.parallel.corr_sharded import corr_sharding
from raft_stereo_tpu.parallel.mesh import make_mesh, replicate, shard_batch
from raft_stereo_tpu.training import checkpoint as ckpt
from raft_stereo_tpu.training.anomaly import (AnomalyPolicy, AnomalyTracker,
                                              TrainingDiverged)
from raft_stereo_tpu.training.logger import Logger, SUM_FREQ
from raft_stereo_tpu.training.optimizer import make_optimizer
from raft_stereo_tpu.training.state import TrainState, create_train_state
from raft_stereo_tpu.training.step import make_train_step

log = logging.getLogger(__name__)

# Config fields that choose HOW the graph executes — backends, precision,
# sharding, remat, memory gates — not WHAT the weights are.  A weights-only
# warm start must take these from the CALLER's config: train() has already
# built the mesh and the corr/rows sharding contexts from it, and the .pth
# warm-start branch honors it the same way (import_torch_checkpoint's
# config= argument).  The checkpoint stays authoritative for the
# weight-shaping architecture fields (hidden_dims, n_gru_layers,
# corr_levels, ...), which is the point of a warm start.
_EXEC_CONFIG_FIELDS = (
    "corr_backend", "fused_gru", "slow_fast_gru", "mixed_precision",
    "corr_fp32", "banded_encoder", "corr_w2_shards", "rows_shards",
    "rows_gru", "rows_gru_halo", "remat_gru", "remat_save",
    "sequential_fnet_pixels", "band_rows",
    # round 15: the int8 inference-tier knobs are pure execution choices
    # (params on disk stay fp32), so the caller's setting wins over
    # whatever the checkpoint was saved with.
    "quant", "quant_corr", "quant_corr_scales")


def merge_warm_start_config(caller_cfg: RaftStereoConfig,
                            ckpt_cfg: RaftStereoConfig) -> RaftStereoConfig:
    """Checkpoint architecture + caller execution-level overrides.

    Fixes the ADVICE.md round-5 finding: the orbax warm-start branch used to
    adopt the checkpoint's config wholesale, silently discarding CLI
    --rows_shards/--rows_gru/--corr_w2_shards/--mixed_precision passed
    alongside --warm_start — and conversely demanding mesh axes the
    already-built mesh lacks when the checkpoint was saved sharded."""
    return dataclasses.replace(
        ckpt_cfg,
        **{f: getattr(caller_cfg, f) for f in _EXEC_CONFIG_FIELDS})


# Batches uploaded to the device ahead of the step dispatch (per-step HBM
# cost: depth x batch bytes): the upload of batch N+1 overlaps the device
# compute of step N instead of sitting between two dispatches.
_DEVICE_PREFETCH_DEPTH = 2


def _no_phase(name: str, **attrs):
    """``telemetry.phases.phase`` with no telemetry: a scope that reads no
    clock and makes no event."""
    return contextlib.nullcontext()


class _DevicePrefetcher:
    """Iterator wrapper that applies ``put`` (host->device upload / global
    shard assembly) on a worker thread, ``depth`` batches ahead.

    The wrapped iterator's exceptions re-raise in the consumer; exhaustion
    yields the usual StopIteration so ``next(it, None)`` keeps feeding the
    train loop's global stop collective.  The producer's terminal state
    (exhausted or crashed) is REMEMBERED: the queue sentinel is delivered
    exactly once, so a consumer that keeps calling ``__next__`` after the
    worker thread died re-raises the same terminal condition immediately
    instead of blocking forever on a queue nothing will ever feed again
    (the pre-round-20 hang: one crashed upload wedged the loop's next
    ``next(batches, None)``)."""

    _DONE = object()

    def __init__(self, it, put, depth: int = _DEVICE_PREFETCH_DEPTH):
        import queue

        self._it = it
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._terminal: Optional[object] = None   # _DONE or BaseException

        def run():
            try:
                for item in it:
                    if self._stop.is_set():
                        return
                    self._q.put(put(item))
            except BaseException as e:  # surface in the consumer
                self._q.put(e)
            else:
                self._q.put(self._DONE)

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._terminal is not None:
            # The producer is gone; its sentinel was already consumed.
            # Blocking on the queue here would hang forever.
            if self._terminal is self._DONE:
                raise StopIteration
            raise self._terminal  # type: ignore[misc]
        item = self._q.get()
        if item is self._DONE:
            self._terminal = item
            raise StopIteration
        if isinstance(item, BaseException):
            self._terminal = item
            raise item
        return item

    def close(self, timeout: float = 5.0):
        self._stop.set()
        # unblock a producer waiting on a full queue, then wait for it to
        # leave the JAX runtime — a daemon thread still inside device_put at
        # interpreter teardown crashes the process exit.  A producer that
        # already CRASHED (terminal exception delivered) is dead; the drain
        # loop is skipped and join returns immediately.  Bounded: if the
        # producer wedges inside device_put/shard_batch we abandon the
        # daemon thread with a warning instead of spinning train()'s
        # finally block forever.
        deadline = time.monotonic() + timeout
        while self._thread.is_alive() and time.monotonic() < deadline:
            while not self._q.empty():
                try:
                    self._q.get_nowait()
                except Exception:  # pragma: no cover - raced drain
                    break
            self._thread.join(timeout=0.2)
        if not self._thread.is_alive():
            # Release the underlying generator's worker threads/pools NOW
            # (the rewind path re-iterates the same loader; waiting for GC
            # would leak a thread pool per rewind).  Safe only once the
            # producer thread left the generator frame.
            close_it = getattr(self._it, "close", None)
            if close_it is not None:
                try:
                    close_it()
                except Exception:  # pragma: no cover - raced teardown
                    log.debug("loader iterator close raised", exc_info=True)
            return
        if self._thread.is_alive():  # pragma: no cover - wedged upload
            # Abandon the daemon thread so train()'s finally block cannot
            # spin forever — but give it one last bounded join at interpreter
            # exit: a daemon thread killed MID-device_put at teardown can
            # crash process exit (the hazard the loop above normally
            # retires), and the atexit grace period lets a late upload
            # complete before teardown begins.
            log.warning("device prefetch thread still alive after %.1fs; "
                        "abandoning it (final %.1fs join registered at "
                        "interpreter exit)", timeout, timeout)
            import atexit
            atexit.register(self._thread.join, timeout)


def build_loader(train_cfg: TrainConfig, data_root: str,
                 checkpoint_dir: str, name: str) -> StereoLoader:
    """The loader ``train()`` reads when it is handed none: the recipe's
    mixture under ``data_root`` through ``StereoLoader``'s own defaults
    (workers, prefetch), this process's shard of it, the quarantine list
    beside the run's checkpoints.  A caller that wants to see the batches a
    run trains on wraps THIS and hands it in as ``loader=``."""
    mixture = build_training_mixture(train_cfg, data_root)
    return StereoLoader(mixture, batch_size=train_cfg.batch_size,
                        seed=train_cfg.seed,
                        quarantine_path=os.path.join(
                            checkpoint_dir, f"{name}.quarantine.json"),
                        **distributed.loader_shard_kwargs())


def train(model_cfg: RaftStereoConfig, train_cfg: TrainConfig,
          name: str = "raft-stereo",
          data_root: str = "datasets",
          checkpoint_dir: str = "checkpoints",
          restore: Optional[str] = None,
          log_dir: str = "runs",
          validate_fn=None,
          loader: Optional[StereoLoader] = None,
          use_mesh: bool = True,
          warm_start: bool = False,
          telemetry=None,
          should_stop: Optional[Callable[[int, TrainState], bool]] = None
          ) -> TrainState:
    """Run the training loop; returns the final state.

    ``restore`` accepts a previous run's checkpoint directory (exact resume,
    optimizer state and step included) or a reference ``.pth`` (warm start,
    like the reference's --restore_ckpt).  ``warm_start=True`` makes an
    orbax ``restore`` load WEIGHTS ONLY — fresh optimizer and step 0 — the
    fine-tune lifecycle (the reference fine-tunes KITTI from the sceneflow
    .pth the same way: weights in, schedule restarts).
    ``validate_fn(variables, model_cfg) -> dict`` runs every
    ``train_cfg.validation_frequency`` steps; ``model_cfg`` is the
    AUTHORITATIVE architecture (a checkpoint restore re-derives it, so a
    config captured at CLI time could be stale).
    ``loader`` stands in for ``build_loader``'s (tests; a caller that
    records the batches a run trains on wraps that one).
    ``telemetry`` is an optional ``telemetry.TrainTelemetry``: step-time
    split, memory gauges, recompile detection, structured run events, and
    — layer 2 — per-step span traces (reconstructed from the timings this
    loop already clocks; TrainConfig.trace_sample_rate), the host phases
    ``train.data_wait`` / ``dispatch`` / ``drain`` / ``checkpoint`` /
    ``upload`` scoped once each (``telemetry.phases``: an event of an open
    profiler capture and ``train_phase_seconds{phase=}``), a non-finite
    loss/grad sentinel riding the buffered metric drain, a step-stall
    watchdog, and a flight recorder that bundles the evidence on anomaly
    (cli/train.py wires all of it for --metrics_port).  When None — the
    default — the loop takes the exact pre-telemetry path: no extra
    timing calls, no extra device fetches (tests/test_telemetry.py and
    tests/test_observability.py pin this).
    ``should_stop(step, state)`` is asked once a loop iteration, at the one
    place where a SIGTERM's request is looked at, with the steps dispatched
    so far and the state they leave; true stops the run as the signal does
    (final checkpoint with its exact-resume sidecar, clean return).  The
    loop runs ahead of the device: ``state``'s arrays are ready only once
    the device has run those steps, so a caller that wants to know that
    step ``step`` is DONE waits on them (``jax.block_until_ready``).
    """
    # Defensive: form the process group (no-op single-host / already done)
    # BEFORE the jax.devices() call below latches the backend.
    distributed.initialize()
    devices = jax.devices()
    n_corr = model_cfg.corr_w2_shards
    n_rows = model_cfg.rows_shards
    if (n_corr > 1 or n_rows > 1) and not use_mesh:
        raise ValueError(
            "corr_w2_shards/rows_shards > 1 requires use_mesh=True")
    if use_mesh and len(devices) < n_corr * n_rows:
        raise ValueError(
            f"corr_w2_shards={n_corr} x rows_shards={n_rows} exceeds the "
            f"{len(devices)} available devices — no device is left for the "
            f"data axis")
    if n_rows > 1 and train_cfg.image_size[0] % (4 * n_rows):
        raise ValueError(
            f"rows_shards={n_rows} needs image height "
            f"{train_cfg.image_size[0]} divisible by {4 * n_rows} "
            f"(two stride-2 stages x row shards)")
    n_data = train_cfg.data_parallel or len(devices) // (n_corr * n_rows)
    if use_mesh and n_data * n_corr * n_rows > len(devices):
        raise ValueError(
            f"data_parallel={n_data} x corr_w2_shards={n_corr} x "
            f"rows_shards={n_rows} needs {n_data * n_corr * n_rows} devices "
            f"but only {len(devices)} are available")
    if train_cfg.batch_size % n_data:
        raise ValueError(f"batch_size={train_cfg.batch_size} not divisible "
                         f"by {n_data} data-parallel devices")
    mesh = make_mesh(n_data=n_data, n_corr=n_corr, n_rows=n_rows,
                     devices=devices[:n_data * n_corr * n_rows]
                     ) if use_mesh else None

    # W2-sharded correlation / rows-sharded encoding need their mesh active
    # whenever the model is traced (init, warm-start re-init, and the
    # jitted step), so hold the contexts for the whole run.
    with contextlib.ExitStack() as ctx:
        if n_corr > 1:
            ctx.enter_context(corr_sharding(mesh))
        if n_rows > 1:
            from raft_stereo_tpu.parallel.mesh import ROWS_AXIS
            from raft_stereo_tpu.parallel.rows_sharded import rows_sharding
            ctx.enter_context(rows_sharding(mesh, axis=ROWS_AXIS))
        return _train_impl(model_cfg, train_cfg, name, data_root,
                           checkpoint_dir, restore, log_dir, validate_fn,
                           loader, mesh, warm_start, telemetry, should_stop)


def _train_impl(model_cfg: RaftStereoConfig, train_cfg: TrainConfig,
                name: str, data_root: str, checkpoint_dir: str,
                restore: Optional[str], log_dir: str, validate_fn,
                loader: Optional[StereoLoader], mesh,
                warm_start: bool = False, telemetry=None,
                should_stop=None) -> TrainState:
    phase = telemetry.phases.phase if telemetry is not None else _no_phase
    h, w = train_cfg.image_size
    init_shape = (1, h, w, 3)
    rng = jax.random.PRNGKey(train_cfg.seed)

    if restore == "latest":
        # Resume-from-latest-valid: scan the checkpoint dir for this
        # run's newest COMPLETE checkpoint (atomic saves + validity
        # check, training/checkpoint.py).  A preemption mid-save can
        # never leave a torn checkpoint at a final name, and anything
        # torn by an older writer is skipped instead of crash-looping
        # the restart.  deep=True verifies the SHA-256 manifest: a
        # bit-flipped blob (bad disk, torn copy) falls back to the
        # newest checkpoint that still verifies, typed (counter + log)
        # instead of restoring garbage.
        def _reject(path, reason):
            log.warning("skipping corrupt checkpoint %s (%s)", path, reason)
            if telemetry is not None:
                telemetry.observe_checkpoint_rejected(path, reason)
        restore = ckpt.latest_checkpoint(checkpoint_dir, name=name,
                                         deep=True, on_reject=_reject)
        if restore is None:
            log.warning("--restore_ckpt latest: no valid checkpoint "
                        "under %s for run %r; starting fresh",
                        checkpoint_dir, name)
        else:
            log.info("--restore_ckpt latest resolved to %s", restore)

    start_step = 0
    runtime: Optional[Dict] = None   # round-20 exact-resume sidecar
    if restore and restore.endswith(".pth"):
        # warm start from a reference torch checkpoint
        from raft_stereo_tpu.io.torch_import import import_torch_checkpoint
        model_cfg, variables = import_torch_checkpoint(pth_path(restore),
                                                       config=model_cfg)
        state = create_train_state(model_cfg, train_cfg, rng, init_shape)
        state = state.replace(params=variables["params"],
                              batch_stats=variables.get("batch_stats", {}))
        log.info("warm start from torch checkpoint %s", restore)
    elif restore and warm_start:
        # weights-only fine-tune start from one of our orbax checkpoints;
        # execution-level fields stay the caller's (the mesh and sharding
        # contexts were built from them — merge_warm_start_config)
        from raft_stereo_tpu.training.checkpoint import load_weights
        ckpt_cfg, variables = load_weights(restore)
        model_cfg = merge_warm_start_config(model_cfg, ckpt_cfg)
        state = create_train_state(model_cfg, train_cfg, rng, init_shape)
        state = state.replace(params=variables["params"],
                              batch_stats=variables.get("batch_stats", {}))
        log.info("warm start (weights only) from %s", restore)
    elif restore:
        state = create_train_state(model_cfg, train_cfg, rng, init_shape)
        model_cfg, restored = ckpt.load_checkpoint(
            restore, target=_arrays_of(state))
        # step goes back as a weak-typed scalar (int(...)): the live
        # TrainState's step aval is weak int32, and a non-weak restored
        # array would silently recompile the step executable.
        state = state.replace(params=restored["params"],
                              batch_stats=restored["batch_stats"],
                              opt_state=restored["opt_state"],
                              step=jnp.asarray(int(np.asarray(
                                  restored["step"]))))
        start_step = int(restored["step"])
        # Round 20: the runtime sidecar restores what the array tree
        # cannot — loop step (skipped updates make it run ahead of the
        # device step counter), loader position + reshuffle salts, host
        # RNG, anomaly history, loss EWMA — so a preempt+resume run is
        # bitwise identical to an uninterrupted one, data order included.
        runtime = ckpt.load_runtime_state(restore)
        if runtime:
            start_step = int(runtime.get("loop_step", start_step))
            _set_host_rng(runtime.get("host_rng"))
        # The post-restore validation probe: finite params/opt state =>
        # this checkpoint is stamped GOOD (the rewind target contract —
        # a checkpoint is only known-good once a restore of it passed).
        if _finite_state(restored):
            ckpt.mark_good(restore)
        log.info("exact resume from %s at step %d", restore, start_step)
    else:
        state = create_train_state(model_cfg, train_cfg, rng, init_shape)

    if mesh is not None:
        state = replicate(state, mesh)

    if loader is None:
        loader = build_loader(train_cfg, data_root, checkpoint_dir, name)
    # Fast-forward the loader to the checkpointed position (a no-op
    # without a runtime sidecar: legacy checkpoints keep the old
    # restart-at-epoch-0 behavior).  set_state is duck-typed so test
    # loaders without resume support still work.
    if runtime and runtime.get("loader") is not None:
        set_state = getattr(loader, "set_state", None)
        if set_state is not None:
            set_state(runtime["loader"])
            log.info("loader resumed at %s", runtime["loader"])
    # Adapt the validation hook's arity ONCE, before the loop: a legacy
    # one-arg validate_fn(variables) must not TypeError hours in at the
    # first validation boundary.
    run_validation = None
    if validate_fn is not None:
        import inspect
        try:
            n_params = len(inspect.signature(validate_fn).parameters)
        except (TypeError, ValueError):
            n_params = 2
        if n_params >= 2:
            run_validation = lambda v: validate_fn(v, model_cfg)  # noqa: E731
        else:
            run_validation = validate_fn

    # Divergence-proof runtime (round 20, training/anomaly.py): with the
    # policy on, the step gains the on-device skip gate and threads the
    # loss EWMA; the tracker below turns drained skip flags into rewind
    # decisions.  Policy off (default) compiles the exact two-arg step.
    policy = AnomalyPolicy.from_train_config(train_cfg)
    tracker = AnomalyTracker(policy) if policy is not None else None
    if tracker is not None and runtime:
        tracker.load_history(runtime.get("anomaly"))
    loss_ewma = float(runtime.get("loss_ewma", 0.0)) if runtime else 0.0

    step_fn = make_train_step(train_cfg, mesh=mesh, anomaly=policy)
    if telemetry is not None and getattr(telemetry, "costs", None) is not None:
        # AOT-instrumented step dispatch (telemetry/costs.py): the first
        # batch lowers + compiles through the cost registry, recording the
        # executable's flops/bytes/memory — the numerator of train_mfu and
        # the step_flops field of every step_stats event.  Without a cost
        # registry the jitted step is called exactly as before.
        from raft_stereo_tpu.telemetry.train_metrics import (
            TRAIN_STEP_COST_KEY)
        step_fn = telemetry.costs.instrument(
            step_fn, key=TRAIN_STEP_COST_KEY, site="train")
    _, schedule = make_optimizer(train_cfg)

    os.makedirs(checkpoint_dir, exist_ok=True)
    total = train_cfg.num_steps
    step = start_step
    t0 = time.time()

    if telemetry is not None:
        telemetry.run_start(model_cfg, train_cfg, start_step, name=name)
        if restore:
            telemetry.resumed(restore, start_step)

    # Preemption safety (beyond the reference, which loses up to 10k steps on
    # a kill — SURVEY.md §5): SIGTERM/SIGINT request a checkpoint at the next
    # step boundary, then a clean exit.  Preempted TPU VMs deliver SIGTERM;
    # with exact-resume checkpoints the run continues where it stopped.
    stop_requested = False
    stop_asked = False          # by ``should_stop``
    prev_handlers = {}

    def _restore_handlers():
        while prev_handlers:
            sig, h = prev_handlers.popitem()
            signal.signal(sig, h)

    def _request_stop(signum, frame):
        nonlocal stop_requested
        if stop_requested:
            # Second signal: force quit.  (Keeping the handler installed
            # until then protects the preemption checkpoint write itself
            # from a single signal.)
            _restore_handlers()
            raise KeyboardInterrupt(f"second signal {signum}: force quit")
        stop_requested = True
        if telemetry is not None:
            telemetry.stop_requested(signum)
        log.warning("signal %d: checkpointing at next step boundary "
                    "(send again to force-quit)", signum)

    if threading.current_thread() is threading.main_thread():
        for sig in (signal.SIGTERM, signal.SIGINT):
            prev_handlers[sig] = signal.signal(sig, _request_stop)

    # Device-side metric dicts awaiting a host fetch.  Fetching per step
    # would force a host sync every step, pinning the device to the Python
    # loop's pace; buffering SUM_FREQ steps (the logger's own aggregation
    # cadence) lets async dispatch run the device ahead and costs one
    # transfer of ~8 scalars x SUM_FREQ instead of SUM_FREQ round-trips.
    pending_metrics = []
    run_status = "failed"  # overwritten on every clean exit path

    # Logger is a context manager so the TensorBoard writer closes on EVERY
    # exit path — return, preemption, or a raising step.
    with Logger(log_dir=log_dir, total_steps=start_step) as logger:
        def drain_metrics():
            if not pending_metrics:
                return
            with phase("drain", step=step,
                       window=len(pending_metrics)) as drained:
                fetched = jax.device_get(pending_metrics)
                pending_metrics.clear()
                first = step - len(fetched) + 1
                # One vectorized schedule eval for the whole span (the
                # per-step float(schedule(step)) alternative is itself a
                # device sync).
                lrs = np.asarray(schedule(np.arange(first, step + 1)))
                # The gru_delta_px entry is a VECTOR (per-iteration
                # convergence curve, TrainConfig.gru_telemetry) — split it
                # off before the scalar-only logger sees the dicts.
                gru_deltas = [m.pop("gru_delta_px") for m in fetched
                              if "gru_delta_px" in m]
                for m, lr in zip(fetched, lrs):
                    logger.push(m, lr=float(lr))
                if tracker is not None:
                    # The anomaly tracker consumes the drained per-step
                    # skip flags (already host floats — zero extra fetches,
                    # the NonFiniteSentinel contract) and arms the rewind
                    # check the loop runs right after each drain.
                    for offset, m in enumerate(fetched):
                        kind = tracker.observe(first + offset, m)
                        if kind is not None and telemetry is not None:
                            telemetry.observe_anomaly_skip(first + offset,
                                                           kind)
            if telemetry is not None:
                means = ({k: float(np.mean([m[k] for m in fetched]))
                          for k in fetched[0]} if fetched else {})
                telemetry.observe_drain(drained.seconds, means, step,
                                        window=len(fetched))
                for d in gru_deltas:
                    telemetry.observe_gru_deltas(np.asarray(d).ravel())
                if hasattr(loader, "stats"):
                    telemetry.observe_loader_stats(loader.stats)

        # Host->device upload (or global shard assembly) runs on a prefetch
        # thread, ahead of the step dispatch — the synchronous per-step
        # upload is otherwise serial with compute (see _DevicePrefetcher).
        upload = ((lambda b: shard_batch(b, mesh)) if mesh is not None
                  else jax.device_put)

        def compact(b):
            # halve the GT bytes on the wire (config.compact_upload):
            # fp16 flow + uint8 valid, cast back to f32 in train_step
            c = dict(b)
            if c["flow"].dtype == np.float32:
                c["flow"] = c["flow"].astype(np.float16)
            if c["valid"].dtype == np.float32:
                c["valid"] = (c["valid"] > 0.5).astype(np.uint8)
            return c

        def put_from(first_step: int):
            """The prefetch thread's upload of the batches of steps
            ``first_step``, ``first_step + 1``, ..."""
            steps = itertools.count(first_step)

            def put(b):
                with phase("upload", step=next(steps)) as up:
                    if train_cfg.compact_upload:
                        b = compact(b)
                    if telemetry is not None:
                        up.set(batch_size=len(b["flow"]), bytes=sum(
                            int(v.nbytes) for v in b.values()))
                    return upload(b)
            return put

        batches = _DevicePrefetcher(iter(loader), put_from(start_step + 1))
        # Loader-position bookkeeping for the exact-resume sidecar: the
        # current iterator started at the loader's own start_offset when
        # the loop step counter read anchor_step, so the position after
        # step S is start_offset + (S - anchor_step).
        anchor_step = start_step
        ewma_dev = (jnp.asarray(loss_ewma, jnp.float32)
                    if policy is not None else None)

        def _runtime_blob():
            blob: Dict = {"loop_step": step, "host_rng": _get_host_rng()}
            loader_state = getattr(loader, "state", None)
            if loader_state is not None:
                blob["loader"] = loader_state(consumed=step - anchor_step)
            if tracker is not None:
                blob["anomaly"] = tracker.history()
            if ewma_dev is not None:
                blob["loss_ewma"] = float(jax.device_get(ewma_dev))
            return blob

        def do_rewind():
            """Restore the newest checkpoint that passes the finite-state
            probe, reshuffle the remaining epoch order (salt event) so
            the poison batch is not deterministically replayed, and
            resume the loop there.  Raises the typed TrainingDiverged
            when the rewind budget or the checkpoint supply is out."""
            nonlocal state, step, batches, anchor_step, ewma_dev
            if not tracker.rewind_budget_left():
                raise TrainingDiverged(
                    step, f"{tracker.consecutive} consecutive anomalous "
                    f"steps and max_rewinds={policy.max_rewinds} exhausted")
            target = _arrays_of(state)
            for path in ckpt.valid_checkpoints(checkpoint_dir, name=name,
                                               deep=True):
                try:
                    _, restored = ckpt.load_checkpoint(path, target=target)
                except Exception:
                    log.warning("rewind: restore of %s failed; trying "
                                "older", path, exc_info=True)
                    continue
                if not _finite_state(restored):
                    log.warning("rewind: %s fails the finite-state probe "
                                "(saved post-divergence?); trying older",
                                path)
                    continue
                ckpt.mark_good(path)   # probe passed => known-good
                rt = ckpt.load_runtime_state(path) or {}
                to_step = int(rt.get("loop_step",
                                     int(np.asarray(restored["step"]))))
                new_state = state.replace(
                    params=restored["params"],
                    batch_stats=restored["batch_stats"],
                    opt_state=restored["opt_state"],
                    # weak-typed like the live state's step (see the
                    # exact-resume branch) — a non-weak aval would
                    # recompile the step executable after every rewind
                    step=jnp.asarray(int(np.asarray(restored["step"]))))
                if mesh is not None:
                    new_state = replicate(new_state, mesh)
                else:
                    # Restored leaves are host numpy arrays; upload them
                    # now so the resumed dispatch hits the SAME compiled
                    # executable (a numpy-leaved call re-lowers through
                    # the AOT instrumentation and reads as a recompile).
                    new_state = jax.device_put(new_state)
                from_step = step
                tracker.note_rewind(from_step, to_step, path)
                _set_host_rng(rt.get("host_rng"))
                # Reposition the loader at the checkpoint and add the
                # reshuffle salt (keyed by the rewind ordinal so repeated
                # rewinds draw different permutations).
                if hasattr(loader, "set_state"):
                    loader.set_state(rt.get("loader")
                                     or {"offset": to_step, "salts": []})
                    if hasattr(loader, "add_salt") and len(loader) > 0:
                        e, b = divmod(loader.start_offset, len(loader))
                        loader.add_salt(e, b, tracker.rewinds)
                batches.close()
                batches = _DevicePrefetcher(iter(loader),
                                            put_from(to_step + 1))
                pending_metrics.clear()
                state = new_state
                step = to_step
                anchor_step = to_step
                ewma_dev = jnp.asarray(float(rt.get("loss_ewma", 0.0)),
                                       jnp.float32)
                log.warning("anomaly rewind %d/%d: step %d -> %d from %s "
                            "(remaining epoch order reshuffled)",
                            tracker.rewinds, policy.max_rewinds,
                            from_step, to_step, path)
                if telemetry is not None:
                    telemetry.observe_rewind(from_step, to_step, path)
                return
            raise TrainingDiverged(
                step, "no checkpoint passes the finite-state probe — "
                "nothing to rewind to")

        try:
            while True:
                # Without telemetry every ``phase`` scope is ``_no_phase``
                # and every other site is gated on ``telemetry is not
                # None``: the disabled path is the pre-telemetry loop — no
                # clock reads, no extra device fetches.
                # Fetch BEFORE the stop collective so loader exhaustion is
                # part of the global stop decision: any_process's call-count
                # invariant (once per loop iteration on EVERY process) would
                # break if one process's sharded loader ran a step short and
                # left this loop early — the others would hang in the next
                # allgather.  With exhaustion folded into the collective,
                # all processes break together at the earliest exhaustion.
                with phase("data_wait", step=step + 1) as waited:
                    batch = next(batches, None)
                # The stop decision must be GLOBAL: a signal lands on one
                # host only, and every process has to break at the same step
                # boundary before the collective checkpoint save
                # (any_process is itself a collective — called once per loop
                # iteration; `step` is identical on all processes so the
                # short-circuit is consistent).  A caller's ``should_stop``
                # is a stop request like the signal's.
                stop_asked = (should_stop is not None
                              and should_stop(step, state))
                if step >= total or distributed.any_process(
                        stop_requested or stop_asked or batch is None):
                    break
                if telemetry is not None:
                    telemetry.note_batch(batch)
                # dispatch leg only (async dispatch returns at submit); the
                # device-bound tail shows up in the drain
                with phase("dispatch", step=step + 1,
                           batch_size=train_cfg.batch_size) as dispatched:
                    if policy is not None:
                        state, metrics, ewma_dev = step_fn(state, batch,
                                                           ewma_dev)
                    else:
                        state, metrics = step_fn(state, batch)
                step += 1
                if telemetry is not None:
                    telemetry.observe_step(
                        step, data_wait_s=waited.seconds,
                        dispatch_s=dispatched.seconds)
                pending_metrics.append(metrics)
                if len(pending_metrics) >= SUM_FREQ:
                    drain_metrics()
                    if tracker is not None and tracker.should_rewind():
                        do_rewind()
                        continue

                if (step % train_cfg.validation_frequency == 0
                        or step == total):
                    drain_metrics()
                    # Rewind decisions come BEFORE the save: K consecutive
                    # anomalies mean the current state is suspect, and a
                    # checkpoint of it would poison the rewind ladder.
                    if tracker is not None and tracker.should_rewind():
                        do_rewind()
                        continue
                    save_path = os.path.join(checkpoint_dir,
                                             f"{step}_{name}")
                    _save(save_path, model_cfg, state, step, telemetry,
                          runtime_state=_runtime_blob())
                    if train_cfg.checkpoint_keep > 0:
                        ckpt.prune_checkpoints(
                            checkpoint_dir, name=name,
                            keep=train_cfg.checkpoint_keep)
                    if run_validation is not None:
                        variables = {
                            "params": jax.device_get(state.params),
                            "batch_stats":
                                jax.device_get(state.batch_stats) or {}}
                        results = run_validation(variables)
                        logger.write_dict(results)
                        if telemetry is not None:
                            telemetry.observe_validation(results, step)
            # Final (or preemption) checkpoint — written while the
            # stop-request handler may still be installed, so a first signal
            # here cannot kill a half-written save.
            _save(os.path.join(checkpoint_dir, name), model_cfg, state,
                  step, telemetry, runtime_state=_runtime_blob())
            run_status = ("stopped" if stop_requested or stop_asked
                          else "complete")
        finally:
            # Also on the exception path: a crash at step N must not discard
            # the buffered metrics of steps N-1..N-SUM_FREQ+1 — that window
            # of the loss curve is exactly what diagnoses the crash.
            # Guarded so a failed fetch can't mask the original exception.
            try:
                drain_metrics()
            except Exception:
                log.exception("could not drain buffered metrics")
            batches.close()
            _restore_handlers()
            if telemetry is not None:
                telemetry.run_end(run_status, step)

    if stop_requested or stop_asked:
        log.warning("stopped by %s at step %d; resume with "
                    "--restore_ckpt %s",
                    "signal" if stop_requested else "the caller", step,
                    os.path.join(checkpoint_dir, name))
    log.info("training done: %d steps in %.1fs", step - start_step,
             time.time() - t0)
    return state


def pth_path(p: str) -> str:
    return os.path.abspath(os.path.expanduser(p))


def _arrays_of(state: TrainState):
    """The serializable leaves of a TrainState (drops apply_fn / tx)."""
    return {"params": jax.device_get(state.params),
            "batch_stats": jax.device_get(state.batch_stats) or {},
            "opt_state": jax.device_get(state.opt_state),
            "step": np.asarray(jax.device_get(state.step))}


def _finite_state(tree) -> bool:
    """The post-restore validation probe: every float leaf of the restored
    params/opt_state is finite.  A checkpoint saved after divergence (NaN
    already in the weights or the Adam moments) fails here and the rewind
    falls through to an older one."""
    for leaf in jax.tree_util.tree_leaves(
            {"params": tree.get("params"),
             "opt_state": tree.get("opt_state")}):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating) and not np.all(
                np.isfinite(arr)):
            return False
    return True


def _get_host_rng():
    """The global NumPy RNG state as a JSON-serializable blob (everything
    seeded explicitly — loader permutations, per-sample augmentation — is
    already deterministic; this covers any library code drawing from the
    GLOBAL stream so exact resume reproduces it too)."""
    name, keys, pos, has_gauss, cached = np.random.get_state()
    return [name, np.asarray(keys).tolist(), int(pos), int(has_gauss),
            float(cached)]


def _set_host_rng(blob) -> None:
    if not blob:
        return
    try:
        name, keys, pos, has_gauss, cached = blob
        np.random.set_state((name, np.asarray(keys, np.uint32), int(pos),
                             int(has_gauss), float(cached)))
    except (ValueError, TypeError):  # pragma: no cover - foreign blob
        log.warning("could not restore host RNG state from checkpoint")


def _save(path: str, model_cfg: RaftStereoConfig, state: TrainState,
          step: int, telemetry=None, runtime_state=None) -> None:
    phase = telemetry.phases.phase if telemetry is not None else _no_phase
    with phase("checkpoint", step=step) as saved:
        ckpt.save_checkpoint(path, model_cfg, _arrays_of(state),
                             runtime_state=runtime_state)
    log.info("saved checkpoint %s", path)
    if telemetry is not None:
        telemetry.observe_checkpoint(saved.seconds, path, step)
