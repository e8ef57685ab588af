#!/usr/bin/env python
"""Training chaos harness: deterministic fault injection against the
divergence-proof train runtime (round 20 — the r13 serving playbook
applied to the other half of the stack).

Each leg runs a real ``train()`` on a tiny synthetic model/dataset (CPU,
no accelerator, no datasets on disk) with ONE injected fault class and
asserts the run ends in RUN-TO-COMPLETION with the matching TYPED
telemetry counter moved — zero silent skips:

* ``nan_grads``     — a poison batch (NaN ground truth) makes loss/grads
  non-finite: the on-device gate drops the update
  (train_batches_skipped_total{reason="nonfinite"}), params stay finite.
* ``loss_spike``    — a finite but huge-loss batch trips the EWMA spike
  gate (train_batches_skipped_total{reason="spike"}).
* ``rewind``        — a contiguous poison window forces K consecutive
  anomalies: the loop restores the newest GOOD checkpoint and
  reshuffles the remaining epoch order (train_rewinds_total).
* ``raising_sample``— a sample that raises on every decode is retried
  once then quarantined + substituted
  (train_loader_samples_quarantined_total), quarantine list persisted.
* ``worker_kill``   — a process loader worker SIGKILLs itself
  mid-decode; the pool is respawned and the batch resubmitted
  (train_loader_worker_respawns_total).
* ``byte_flip``     — a flipped byte in the newest checkpoint fails the
  SHA-256 manifest; resume falls back to the newest checkpoint that
  still verifies (train_checkpoints_rejected_total), never garbage.
* ``sigterm_resume``— SIGTERM mid-run checkpoints at the step boundary;
  the resumed run's FINAL PARAMS ARE BITWISE EQUAL to an uninterrupted
  run's (host RNG + loader position + EWMA all restored from the
  runtime sidecar).

Determinism: every fault is keyed by (epoch, sample index) — a pure
function of the seeded data order — so two runs inject identically.

Writes the chaos matrix to ``--out`` (default RESILIENCE_TRAIN_r20.json)
with the shared bench_record header.  Exit 0 only if every leg passed.

Run from the repo root:  JAX_PLATFORMS=cpu python tools/train_chaos.py
The fast CI subset lives in scripts/train_smoke.py.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import signal
import sys
import tempfile
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax  # noqa: E402

from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig  # noqa: E402
from raft_stereo_tpu.data.loader import StereoLoader  # noqa: E402
from raft_stereo_tpu.telemetry import (EventLog, MetricsRegistry,  # noqa: E402
                                       TrainTelemetry)
from raft_stereo_tpu.training import checkpoint as ckpt  # noqa: E402
from raft_stereo_tpu.training.train_loop import train  # noqa: E402

H, W = 32, 48
N_SAMPLES = 32
BATCH = 2


# fnet_norm="batch": the chaos model uses the frozen-batch-norm encoder —
# same train-loop code paths, and the anomaly machinery under test is
# norm-agnostic.
def tiny_model_cfg() -> RaftStereoConfig:
    return RaftStereoConfig(n_gru_layers=1, hidden_dims=(32,), fnet_dim=64,
                            corr_levels=2, corr_radius=3, fnet_norm="batch")


def tiny_train_cfg(num_steps: int = 12, **kw) -> TrainConfig:
    base = dict(batch_size=BATCH, train_iters=1, num_steps=num_steps,
                image_size=(H, W), validation_frequency=4,
                data_parallel=1, anomaly_policy=True,
                anomaly_spike_factor=8.0, anomaly_rewind_after=3,
                anomaly_max_rewinds=2, checkpoint_keep=4)
    base.update(kw)
    return TrainConfig(**base)


class ChaosDataset:
    """Synthetic stereo samples with deterministic fault hooks.

    Faults key on the SAMPLE INDEX (and epoch where noted) — a pure
    function of the seeded data order, so injection is reproducible:

    * ``nan_indices``  — ground-truth flow is NaN (non-finite loss/grads)
    * ``spike_indices``— gt flow magnitude ~600 px (finite loss ~100x
      normal: the spike-gate case; stays under max_flow=700 so the loss
      mask keeps it)
    * ``raise_indices``— decode raises (every call — the corrupt shard)
    * ``kill_index``   — first decode SIGKILLs the decoding process
      after dropping a marker file, so the respawned worker's retry
      decodes normally (the OOM-killed/segfaulted worker)
    * ``sigterm``      — (epoch, index) at which decode SIGTERMs the
      PARENT process (the preemption notice; use num_workers=0)
    """

    def __init__(self, nan_indices=(), spike_indices=(), raise_indices=(),
                 kill_index=None, kill_marker=None, sigterm=None):
        self.nan_indices = set(nan_indices)
        self.spike_indices = set(spike_indices)
        self.raise_indices = set(raise_indices)
        self.kill_index = kill_index
        self.kill_marker = kill_marker
        self.sigterm = sigterm

    def __len__(self):
        return N_SAMPLES

    def __getitem__(self, i, epoch=0):
        if i in self.raise_indices:
            raise ValueError(f"injected corrupt sample {i}")
        if self.kill_index is not None and i == self.kill_index:
            if not os.path.exists(self.kill_marker):
                with open(self.kill_marker, "w") as f:
                    f.write("killed\n")
                    f.flush()
                    os.fsync(f.fileno())
                os.kill(os.getpid(), signal.SIGKILL)
        if self.sigterm is not None and (epoch, i) == tuple(self.sigterm):
            os.kill(os.getpid(), signal.SIGTERM)
        rng = np.random.default_rng(1000 + i)
        img = rng.uniform(0, 255, (H, W, 3)).astype(np.float32)
        flow = rng.normal(-4.0, 1.0, (H, W)).astype(np.float32)
        if i in self.nan_indices:
            flow = np.full((H, W), np.nan, np.float32)
        if i in self.spike_indices:
            flow = np.sign(flow) * 600.0
        return {"image1": img, "image2": img + 1.0, "flow": flow,
                "valid": np.ones((H, W), np.float32)}


def make_loader(ds, workdir, **kw) -> StereoLoader:
    base = dict(batch_size=BATCH, num_workers=0, shuffle=False, seed=7,
                quarantine_path=os.path.join(workdir, "quarantine.json"))
    base.update(kw)
    return StereoLoader(ds, **base)


def make_telemetry(workdir):
    events = EventLog(os.path.join(workdir, "events.jsonl"))
    return TrainTelemetry(registry=MetricsRegistry(), events=events), events


def params_digest(state) -> str:
    leaves = jax.tree_util.tree_leaves(jax.device_get(state.params))
    h = hashlib.sha256()
    for leaf in leaves:
        arr = np.ascontiguousarray(np.asarray(leaf))
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def finite_params(state) -> bool:
    return all(np.all(np.isfinite(np.asarray(leaf)))
               for leaf in jax.tree_util.tree_leaves(
                   jax.device_get(state.params)))


def run_train(workdir, ds, name, num_steps=12, loader_kw=None,
              restore=None, **cfg_kw):
    """One instrumented train run; returns (state, telemetry)."""
    telemetry, events = make_telemetry(workdir)
    loader = make_loader(ds, workdir, **(loader_kw or {}))
    try:
        state = train(tiny_model_cfg(), tiny_train_cfg(num_steps, **cfg_kw),
                      name=name, checkpoint_dir=os.path.join(workdir, "ck"),
                      log_dir=os.path.join(workdir, "runs"), loader=loader,
                      restore=restore, use_mesh=False, telemetry=telemetry)
    finally:
        events.close()
    return state, telemetry, loader


# ------------------------------------------------------------------- legs
def leg_baseline(workdir):
    """Uninterrupted reference run: the bitwise anchor for sigterm_resume
    and the completion baseline."""
    t0 = time.time()
    state, telemetry, _ = run_train(workdir, ChaosDataset(), "base")
    assert int(state.step) == 12, f"baseline stopped at {int(state.step)}"
    assert finite_params(state)
    assert telemetry.batches_skipped["nonfinite"].value == 0
    return {"completed": True, "steps": int(state.step),
            "wall_s": round(time.time() - t0, 2),
            "params_sha256": params_digest(state)}


def leg_nan_grads(workdir):
    """One poison batch (samples 8,9 = batch 5 of epoch 0): non-finite
    loss/grads -> on-device skip, typed counter, finite final params."""
    ds = ChaosDataset(nan_indices=(8, 9))
    state, telemetry, _ = run_train(workdir, ds, "nan")
    skipped = telemetry.batches_skipped["nonfinite"].value
    assert skipped >= 1, "NaN batch not counted as skipped"
    assert finite_params(state), "NaN leaked into params"
    return {"completed": True,
            "counter": "train_batches_skipped_total{reason=nonfinite}",
            "count": skipped}


def leg_loss_spike(workdir):
    """A finite ~600 px gt batch vs ~4 px normal: loss ~100x the EWMA,
    spike gate drops it (factor 8)."""
    ds = ChaosDataset(spike_indices=(10, 11))
    state, telemetry, _ = run_train(workdir, ds, "spike")
    skipped = telemetry.batches_skipped["spike"].value
    assert skipped >= 1, "spike batch not dropped by the EWMA gate"
    assert finite_params(state)
    return {"completed": True,
            "counter": "train_batches_skipped_total{reason=spike}",
            "count": skipped}


def leg_rewind(workdir):
    """A contiguous poison window (samples 18..25 = batches 9..12 of the
    unshuffled epoch): >= 3 consecutive skips at the step-12 drain
    boundary -> rewind to the step-8 checkpoint + salted reshuffle of the
    remaining epoch order, then run to completion (the scattered poison
    batches each skip individually, never K in a row again)."""
    ds = ChaosDataset(nan_indices=tuple(range(18, 26)))
    state, telemetry, loader = run_train(workdir, ds, "rew", num_steps=16)
    rewinds = telemetry.rewinds.value
    assert rewinds >= 1, "no rewind despite a poison window"
    # state.step counts APPLIED updates only (skips leave it untouched);
    # run-to-completion is the loop reaching its step budget cleanly.
    health = telemetry.healthz()
    assert health["status"] == "complete" and health["step"] == 16, health
    assert finite_params(state)
    assert loader.salts, "rewind did not add a reshuffle salt"
    return {"completed": True, "counter": "train_rewinds_total",
            "count": rewinds,
            "skipped_nonfinite":
                telemetry.batches_skipped["nonfinite"].value,
            "loader_salts": [list(s) for s in loader.salts]}


def leg_raising_sample(workdir):
    """Sample 5 raises on every decode: retried once, quarantined,
    substituted deterministically; quarantine list persisted."""
    ds = ChaosDataset(raise_indices=(5,))
    state, telemetry, loader = run_train(workdir, ds, "raise")
    q = telemetry.loader_quarantined.value
    assert q >= 1, "raising sample not quarantined"
    assert int(state.step) == 12
    qfile = os.path.join(workdir, "quarantine.json")
    with open(qfile) as f:
        persisted = json.load(f)["indices"]
    assert 5 in persisted, f"quarantine not persisted: {persisted}"
    return {"completed": True,
            "counter": "train_loader_samples_quarantined_total",
            "count": q, "persisted_indices": persisted}


def leg_worker_kill(workdir):
    """A process worker SIGKILLs itself decoding sample 6: the pool is
    respawned, the in-flight batches resubmitted, the run completes."""
    marker = os.path.join(workdir, "killed.marker")
    ds = ChaosDataset(kill_index=6, kill_marker=marker)
    state, telemetry, _ = run_train(
        workdir, ds, "kill", num_steps=8,
        loader_kw=dict(num_workers=2, worker_type="process"))
    respawns = telemetry.loader_respawns.value
    assert respawns >= 1, "dead worker pool not respawned"
    assert int(state.step) == 8
    assert os.path.exists(marker)
    return {"completed": True,
            "counter": "train_loader_worker_respawns_total",
            "count": respawns}


def leg_byte_flip(workdir):
    """Flip one byte in every file of the newest checkpoint in turn: deep
    validation must reject it each time and resume-from-latest must fall
    back to the next-newest intact checkpoint — never load garbage."""
    state, telemetry, _ = run_train(workdir, ds := ChaosDataset(), "flip")
    ck_dir = os.path.join(workdir, "ck")
    newest = ckpt.latest_checkpoint(ck_dir, name="flip", deep=True)
    assert newest is not None
    fallback_expected = ckpt.valid_checkpoints(ck_dir, name="flip")[1]
    flips = 0
    rejects = []
    for root, _dirs, files in os.walk(newest):
        for fn in files:
            if fn == ckpt.GOOD_FILE:
                continue   # advisory stamp, deliberately outside the seal
            fp = os.path.join(root, fn)
            blob = open(fp, "rb").read()
            if not blob:
                continue
            bad = bytearray(blob)
            bad[len(bad) // 2] ^= 0xFF
            open(fp, "wb").write(bytes(bad))
            flips += 1
            assert not ckpt.is_valid_checkpoint(newest, deep=True), \
                f"flip in {fn} undetected"
            got = ckpt.latest_checkpoint(
                ck_dir, name="flip", deep=True,
                on_reject=lambda p, r: rejects.append(r))
            assert got == fallback_expected, \
                f"fallback after flip in {fn}: {got}"
            open(fp, "wb").write(blob)
    assert flips > 0 and len(rejects) >= flips
    # End-to-end: corrupt the newest for good; a resumed run restores
    # the fallback and finishes.
    blob_path = os.path.join(newest, ckpt.MANIFEST_FILE)
    blob = bytearray(open(blob_path, "rb").read())
    blob[0] ^= 0xFF
    open(blob_path, "wb").write(bytes(blob))
    state2, telemetry2, _ = run_train(workdir, ds, "flip", num_steps=16,
                                      restore="latest")
    assert int(state2.step) == 16
    assert telemetry2.checkpoints_rejected.value >= 1, \
        "corrupt checkpoint not counted at resume"
    return {"completed": True,
            "counter": "train_checkpoints_rejected_total",
            "count": telemetry2.checkpoints_rejected.value,
            "byte_flips_detected": flips,
            "reject_reasons": sorted(set(rejects))[:6]}


def leg_sigterm_resume(workdir, baseline_digest):
    """SIGTERM mid-run (decoding (epoch 0, sample 12) = step 7's batch)
    -> checkpoint at the boundary, exit clean; resume-from-latest runs to
    the same step 12 — final params BITWISE equal to the uninterrupted
    baseline (loader position, host RNG, EWMA all from the sidecar)."""
    ds = ChaosDataset(sigterm=(0, 12))
    state, telemetry, _ = run_train(workdir, ds, "pre")
    stopped = int(state.step)
    assert 0 < stopped < 12, f"SIGTERM did not stop the run ({stopped})"
    state2, telemetry2, _ = run_train(workdir, ChaosDataset(), "pre",
                                      restore="latest")
    assert int(state2.step) == 12
    digest = params_digest(state2)
    assert digest == baseline_digest, (
        f"preempt+resume params differ from uninterrupted run: "
        f"{digest[:16]} != {baseline_digest[:16]}")
    return {"completed": True, "stopped_at": stopped,
            "bitwise_equal": True, "params_sha256": digest}


LEGS = ("baseline", "nan_grads", "loss_spike", "rewind", "raising_sample",
        "worker_kill", "byte_flip", "sigterm_resume")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=os.path.join(
        _REPO, "RESILIENCE_TRAIN_r20.json"))
    ap.add_argument("--legs", nargs="+", default=list(LEGS),
                    choices=list(LEGS))
    args = ap.parse_args(argv)

    results = {}
    failures = []
    baseline_digest = None
    t_start = time.time()
    for leg in args.legs:
        workdir = tempfile.mkdtemp(prefix=f"train_chaos_{leg}_")
        t0 = time.time()
        try:
            if leg == "baseline":
                rec = leg_baseline(workdir)
                baseline_digest = rec["params_sha256"]
            elif leg == "sigterm_resume":
                if baseline_digest is None:
                    rec = leg_baseline(tempfile.mkdtemp(
                        prefix="train_chaos_base_"))
                    baseline_digest = rec["params_sha256"]
                rec = leg_sigterm_resume(workdir, baseline_digest)
            else:
                rec = globals()[f"leg_{leg}"](workdir)
            rec["wall_s"] = round(time.time() - t0, 2)
            print(f"[train_chaos] {leg}: OK {rec}")
        except BaseException as e:
            rec = {"completed": False, "error": repr(e)}
            failures.append(leg)
            print(f"[train_chaos] {leg}: FAIL {e!r}")
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        results[leg] = rec

    from raft_stereo_tpu.telemetry.events import bench_record
    record = bench_record(
        {"metric": "train_resilience_chaos_matrix",
         "legs": results,
         "all_completed": not failures,
         "wall_s": round(time.time() - t_start, 2)},
        tool="train_chaos")
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"[train_chaos] wrote {args.out}")
    if failures:
        print(f"[train_chaos] FAILED legs: {failures}")
        return 1
    print(f"[train_chaos] chaos matrix green: {len(results)} legs")
    return 0


if __name__ == "__main__":
    sys.exit(main())
