"""Entry ``train_job``: one training job, closed loop, in this process —
``raft_stereo_tpu.training.train_loop.train``, the function
``raft-stereo-train`` calls, with the mesh, the compiled step, the logger
and the checkpoint writer it builds itself and the loader it builds when
handed none (``train_loop.build_loader``).  The job reads a
seeded SceneFlow-layout tree (``scenes_tree.py``) and starts from seeded
weights handed in as a weights-only checkpoint (the fine-tune lifecycle).

One run is two calls of ``train()``, both on the recipe's own schedule, so
the compiled step is the same program in both:

* *call A*: from the seeded checkpoint, exactly ``steps_compared`` steps,
  stopped through ``should_stop`` as a SIGTERM stops a run.  What the loop
  itself wrote is what ``correct`` judges (``post_train.py``): the final
  checkpoint (parameters, Adam's moments, step) and each step's loss as
  the loop's logger wrote it, against the plain reference's replay of the
  batches the loop's loader yielded.
* *call B*: ``restore=`` that checkpoint (exact resume).  After
  ``warmup_steps`` set-up ends; a traced run then traces ``trace.steps``
  whole steps; then the window opens at a step boundary with the device
  waited for.  The loop runs ahead of the device as far as its own drain
  lets it (100 steps), so inside the window the entry waits for the device
  every ``sync_steps`` steps, and the first such boundary after
  ``--seconds`` closes the window: steps COMPLETED over the time they took.

What the entry reads of the program, it reads as a caller can: the batches
through the loader it hands in (``train_loop.build_loader``'s own, wrapped
to keep the first ones), each step's loss from the event files the loop's
logger writes under ``log_dir``, the state from the checkpoint.
"""

from __future__ import annotations

import gc
import inspect
import json
import os
import time

import numpy as np

from benchmark import harness, prom, scenes_tree

RUN_NAME = "sceneflow"


def recipe_of(cell: dict, seed: int) -> dict:
    """``TrainConfig``'s fields as this run sets them: the configuration's
    recipe, the cell's batch, devices and crop, the run's seed (folded to
    what a 32-bit key takes)."""
    tr = cell["workload"]["traffic"]
    return dict(cell["config"]["train"],
                batch_size=tr["batch_size"],
                data_parallel=tr["data_parallel"],
                image_size=list(tr["image_hw"]),
                train_iters=cell["workload"]["iters"],
                seed=seed % (2 ** 31 - 1))


class _Recorded:
    """``train_loop.build_loader``'s loader, handed to ``train()`` as
    ``loader=``, with the first ``keep`` batches it yields kept (float32
    truth, before the upload's rounding).  Everything else the loop asks of
    a loader (its position for the exact resume, its statistics) is the
    wrapped one's."""

    def __init__(self, loader, keep: int):
        self._loader, self.keep, self.kept = loader, keep, []

    def __iter__(self):
        inner = iter(self._loader)
        try:
            for batch in inner:
                if len(self.kept) < self.keep:
                    self.kept.append({k: np.array(v)
                                      for k, v in batch.items()})
                yield batch
        finally:
            inner.close()

    def __len__(self):
        return len(self._loader)

    def __getattr__(self, name):
        return getattr(self._loader, name)


def logged_losses(log_dir: str) -> dict:
    """``{step: loss}`` as the loop's logger wrote it, a step at a time
    (``live_loss`` in the event files under ``log_dir``, what a user's
    TensorBoard shows)."""
    from tensorboard.backend.event_processing.event_accumulator import (
        EventAccumulator)

    events = EventAccumulator(log_dir, size_guidance={"scalars": 0})
    events.Reload()
    if "live_loss" not in events.Tags()["scalars"]:
        raise harness.BenchError(
            f"the run's logger wrote no live_loss under {log_dir} (it logs "
            f"to the console only where tensorboard is not installed)")
    return {e.step: float(e.value) for e in events.Scalars("live_loss")}


def flat_arrays(tree, prefix: str = "") -> dict:
    """``{"a": {"b": x}}`` to ``{"a/b": x}`` (``weights.nest``'s
    inverse)."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat_arrays(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = np.asarray(v)
    return out


def _adam_moments(node):
    """The one node of a restored optimizer state that holds Adam's ``mu``
    and ``nu`` (the tree's containers are dicts or lists, whatever the
    checkpoint's format made of optax's tuples)."""
    if isinstance(node, dict):
        if "mu" in node and "nu" in node:
            return node
        children = node.values()
    elif isinstance(node, (list, tuple)):
        children = node
    else:
        return None
    for child in children:
        found = _adam_moments(child)
        if found is not None:
            return found
    return None


def prepare(cell: dict, seed: int, wd: str, rig: harness.TestRig) -> dict:
    """The job's inputs: the data tree and the seeded weights as a
    weights-only checkpoint.  Returns the configs and the paths."""
    import jax

    from benchmark import weights
    from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
    from raft_stereo_tpu.training.checkpoint import save_weights

    tr, model = cell["workload"]["traffic"], cell["config"]["model"]
    t0 = time.monotonic()
    data_root = os.path.join(wd, "datasets")
    tree_bytes = scenes_tree.write_tree(data_root, seed, tr["pool_pairs"],
                                        tuple(tr["frame_hw"]))
    t_tree = time.monotonic() - t0
    model_cfg = RaftStereoConfig.from_dict(
        {**model, **rig.program_overrides})
    tree = jax.block_until_ready(
        weights.nest(weights.make_weights(model, seed)))
    ckpt = os.path.join(wd, "seeded_weights")
    save_weights(ckpt, model_cfg, tree["params"],
                 batch_stats=tree.get("batch_stats"))
    del tree
    return {"model_cfg": model_cfg,
            "train_cfg": TrainConfig.from_dict(recipe_of(cell, seed)),
            "data_root": data_root, "weights": ckpt,
            "tree_s": t_tree, "tree_bytes": tree_bytes,
            "weights_s": time.monotonic() - t0 - t_tree}


def call_a(cell: dict, job: dict, wd: str) -> dict:
    """``steps_compared`` steps from the seeded checkpoint, stopped by
    ``should_stop``; leaves under ``<wd>/call_a`` the batches, the logged
    losses and the written state as flat ``.npz`` tables, and returns their
    paths with the checkpoint call B resumes."""
    from raft_stereo_tpu.training import checkpoint as ckpt
    from raft_stereo_tpu.training.train_loop import build_loader, train

    k = cell["workload"]["steps_compared"]
    out = os.path.join(wd, "call_a")
    checkpoints = os.path.join(out, "checkpoints")
    loader = _Recorded(build_loader(job["train_cfg"], job["data_root"],
                                    checkpoints, RUN_NAME), k)
    t0 = time.monotonic()
    train(job["model_cfg"], job["train_cfg"], name=RUN_NAME,
          data_root=job["data_root"], checkpoint_dir=checkpoints,
          restore=job["weights"], warm_start=True, loader=loader,
          log_dir=os.path.join(out, "runs"),
          should_stop=lambda step, state: step >= k)
    seconds = time.monotonic() - t0
    final = os.path.join(checkpoints, RUN_NAME)
    _, written = ckpt.load_checkpoint(final)
    moments = _adam_moments(written["opt_state"])
    state = {"step": np.asarray(written["step"])}
    for group, tree in (("params", written["params"]),
                        ("mu", moments["mu"]), ("nu", moments["nu"])):
        state.update({f"{group}:params/{p}": a
                      for p, a in flat_arrays(tree).items()})
    paths = {"state": os.path.join(out, "state.npz"),
             "batches": os.path.join(out, "batches.npz"),
             "steps": os.path.join(out, "steps.json")}
    np.savez(paths["state"], **state)
    np.savez(paths["batches"], **{f"{i}:{name}": a
                                  for i, b in enumerate(loader.kept)
                                  for name, a in b.items()})
    losses = logged_losses(os.path.join(out, "runs"))
    with open(paths["steps"], "w") as f:
        json.dump([{"loss": losses[i]} for i in sorted(losses)], f)
    return dict(paths, checkpoint=final, seconds=seconds,
                steps_written=int(state["step"]))


class _Window:
    """Call B's ``should_stop``: asked at every step boundary with the
    steps dispatched so far and the state they leave.  It lets the warm-up
    steps pass, traces whole steps where the run is traced, then opens the
    window with the device waited for.  Left alone the loop runs ahead of
    the device up to its drain, 100 steps on, and a clock read at a
    boundary says nothing of the steps DONE; so inside the window every
    ``sync_steps``-th boundary waits for the device (``block_until_ready``
    on the state, inside the clock), the device has at most that many steps
    queued, and the first such boundary after ``seconds`` closes the
    window: the steps between its ends are steps completed."""

    def __init__(self, first_step: int, warmup: int, seconds: float,
                 sync_steps: int, trace_dir, trace_steps: int, registry):
        self.warm_until = first_step + warmup
        self.trace_from = self.warm_until if trace_dir else None
        self.trace_dir, self.trace_steps = trace_dir, trace_steps
        self.seconds, self.sync_steps = seconds, sync_steps
        self.registry = registry
        self.setup_s = self.traced_s = self._t_trace = None
        self.opened = self.closed = None        # (step, instant, counters)

    def _counters(self):
        return prom.parse(self.registry.render_text())

    def __call__(self, step: int, state) -> bool:
        import jax

        if step < self.warm_until or self.closed:
            return False
        if self.setup_s is None:
            jax.block_until_ready(state)
            self.setup_s = time.monotonic() - harness.T_PROCESS_START
        if self.trace_from is not None:
            if step == self.trace_from:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=options)
                self._t_trace = time.monotonic()
                return False
            if step < self.trace_from + self.trace_steps:
                return False
            jax.block_until_ready(state)
            self.traced_s = time.monotonic() - self._t_trace
            jax.profiler.stop_trace()
            self.trace_from = None
        if self.opened is None:
            jax.block_until_ready(state)
            self.opened = (step, time.monotonic(), self._counters())
            return False
        if (step - self.opened[0]) % self.sync_steps:
            return False
        jax.block_until_ready(state)
        now = time.monotonic()
        if now - self.opened[1] < self.seconds:
            return False
        self.closed = (step, now, self._counters())
        return True


def run(cell: dict, seed: int, seconds: float, trace: bool,
        rig: harness.TestRig = harness.NO_RIG) -> dict:
    wl = cell["workload"]
    harness.use_cache_in_process()
    os.environ.update(cell["config"].get("env", {}))
    os.environ.update(rig.env)
    device = harness.require_chips(cell["chips"], rig.require_accelerator)
    import jax

    from raft_stereo_tpu.telemetry import TrainTelemetry
    from raft_stereo_tpu.training.train_loop import train

    if "should_stop" not in inspect.signature(train).parameters:
        raise harness.BenchError(
            "this program's train() takes no should_stop: a run of it "
            "cannot be bounded in time or read step by step")
    wd = harness.work_dir(cell["name"])
    job = prepare(cell, seed, wd, rig)
    a = call_a(cell, job, wd)

    # ---- call B: exact resume, warm-up, (trace,) window
    telemetry = TrainTelemetry()
    trace_dir = os.path.join(wd, "trace") if trace else None
    window = _Window(a["steps_written"], wl["warmup_steps"], seconds,
                     wl["sync_steps"], trace_dir, wl["trace"]["steps"],
                     telemetry.registry)
    out_b = os.path.join(wd, "call_b")
    state = train(job["model_cfg"], job["train_cfg"], name=RUN_NAME,
                  data_root=job["data_root"],
                  checkpoint_dir=os.path.join(out_b, "checkpoints"),
                  restore=a["checkpoint"],
                  log_dir=os.path.join(out_b, "runs"),
                  telemetry=telemetry, should_stop=window)
    if window.closed is None:
        raise harness.BenchError("call B ended before its window closed")

    # ---- the window has closed: memory first, then free, then compare
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    del state
    gc.collect()
    jax.clear_caches()
    (s0, t0, c0), (s1, t1, c1) = window.opened, window.closed
    elapsed, done = t1 - t0, s1 - s0
    b = job["train_cfg"].batch_size
    losses = logged_losses(os.path.join(out_b, "runs"))
    in_window = [losses[i] for i in range(s0 + 1, s1 + 1) if i in losses]
    failed = sum(1 for loss in in_window if not np.isfinite(loss))
    post_path = os.path.join(wd, "post.json")
    result_path = os.path.join(wd, "post_result.json")
    from benchmark import post_train

    post_train.write_request(
        post_path, cell, seed, recipe_of(cell, seed), a, trace_dir,
        result_path, trace_window_s=window.traced_s,
        require_accelerator=rig.require_accelerator)
    post_train.main([post_path])
    with open(result_path) as f:
        post_result = json.load(f)
    compared = post_result["compared"]
    compared.append({"name": "window_steps_with_finite_loss",
                     "value": len(in_window) - failed, "limit": done,
                     "ok": failed == 0 and len(in_window) == done})
    return {
        "e2e": {"pairs_per_s": done * b / elapsed,
                "setup_s": window.setup_s},
        "observed": {"cell": cell, "seconds": elapsed,
                     "pairs_completed": done * b,
                     "counters": prom.delta(c0, c1),
                     "trace": post_result.get("trace"),
                     "device_kind": rig.device_kind or device["kind"]},
        "compared": compared, "attempted": done, "failed": failed,
        "device": device,
        "memory": {"memory_stats": stats},
        "trace": post_result.get("trace"),
        "extra": {"steps": done, "window_s": elapsed,
                  "parts_s": {"tree": job["tree_s"],
                              "weights": job["weights_s"],
                              "call_a": a["seconds"],
                              "reference": post_result["reference_s"]}},
    }
