"""Divergence-proof training (round 20): anomaly policy units, loader
fault isolation, checkpoint integrity/retention, prefetcher crash
semantics.

The quick tier here is deliberately host-side (no model compiles): the
policy/tracker logic, the loader's quarantine + exact-resume state
machine, the checkpoint manifest byte-flip property sweep (on a tiny
synthetic tree — satellite 2), and the _DevicePrefetcher terminal-state
fix (satellite 1).  The jitted-step gate and the full rewind/preempt
loop run in the slow tier and, end to end with injected faults, in
scripts/train_smoke.py (CI) / tools/train_chaos.py (the chaos matrix).
"""

import json
import os

import numpy as np
import pytest

from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
from raft_stereo_tpu.data.loader import StereoLoader
from raft_stereo_tpu.training import checkpoint as ckpt
from raft_stereo_tpu.training.anomaly import (AnomalyPolicy, AnomalyTracker,
                                              TrainingDiverged)


# ------------------------------------------------------------ policy units
def test_anomaly_policy_validation():
    with pytest.raises(ValueError, match="spike_factor"):
        AnomalyPolicy(spike_factor=-1.0)
    with pytest.raises(ValueError, match="ewma_beta"):
        AnomalyPolicy(ewma_beta=1.0)
    with pytest.raises(ValueError, match="rewind_after"):
        AnomalyPolicy(rewind_after=-1)
    with pytest.raises(ValueError, match="max_rewinds"):
        AnomalyPolicy(max_rewinds=-1)


def test_anomaly_policy_from_train_config():
    assert AnomalyPolicy.from_train_config(TrainConfig()) is None
    p = AnomalyPolicy.from_train_config(TrainConfig(
        anomaly_policy=True, anomaly_spike_factor=5.0,
        anomaly_rewind_after=2, anomaly_max_rewinds=1))
    assert p == AnomalyPolicy(spike_factor=5.0, ewma_beta=0.98,
                              rewind_after=2, max_rewinds=1)


def test_tracker_consecutive_counting_and_rewind_arming():
    t = AnomalyTracker(AnomalyPolicy(rewind_after=3))
    assert t.observe(1, {"skipped": 0.0}) is None
    assert t.observe(2, {"skipped": 1.0, "skip_nonfinite": 1.0}) \
        == "nonfinite"
    assert t.observe(3, {"skipped": 1.0, "skip_nonfinite": 0.0,
                         "skip_spike": 1.0}) == "spike"
    assert not t.should_rewind()         # 2 consecutive < 3
    assert t.observe(4, {"skipped": 0.0}) is None
    assert t.consecutive == 0            # a clean step re-arms
    for s in (5, 6, 7):
        t.observe(s, {"skipped": 1.0, "skip_nonfinite": 1.0})
    assert t.should_rewind()
    t.note_rewind(7, 4, "/ck/4_run")
    assert not t.should_rewind() and t.rewinds == 1
    assert t.skipped_nonfinite == 4 and t.skipped_spike == 1


def test_tracker_history_roundtrip():
    t = AnomalyTracker(AnomalyPolicy(rewind_after=2, max_rewinds=3))
    for s in (1, 2):
        t.observe(s, {"skipped": 1.0, "skip_nonfinite": 1.0})
    t.note_rewind(2, 0, "/ck/x")
    h = json.loads(json.dumps(t.history()))   # JSON round-trip like the blob
    t2 = AnomalyTracker(AnomalyPolicy(rewind_after=2, max_rewinds=3))
    t2.load_history(h)
    assert t2.rewinds == 1 and t2.skipped_nonfinite == 2
    assert t2.rewind_budget_left()
    t2.note_rewind(5, 3, "/ck/y")
    t2.note_rewind(9, 6, "/ck/z")
    assert not t2.rewind_budget_left()    # budget survives the round-trip


def test_training_diverged_is_typed():
    e = TrainingDiverged(123, "out of rewinds")
    assert e.step == 123 and "out of rewinds" in str(e)
    assert isinstance(e, RuntimeError)


# ------------------------------------------------- loader fault isolation
class _FaultDataset:
    """Deterministic samples; ``bad`` raise always, ``flaky`` raise on
    the first decode only."""

    def __init__(self, n=8, bad=(), flaky=()):
        self.n = n
        self.bad = set(bad)
        self.flaky = dict.fromkeys(flaky, 0)

    def __len__(self):
        return self.n

    def __getitem__(self, i, epoch=0):
        if i in self.bad:
            raise ValueError(f"corrupt sample {i}")
        if i in self.flaky and self.flaky[i] == 0:
            self.flaky[i] += 1
            raise ValueError(f"flaky sample {i}")
        return {"x": np.full((2, 2), float(i) + 100.0 * epoch)}


def _values(loader):
    return [sorted(b["x"][:, 0, 0].tolist()) for b in loader]


def test_loader_quarantines_raising_sample_and_substitutes(tmp_path):
    qp = str(tmp_path / "q.json")
    loader = StereoLoader(_FaultDataset(bad=(3,)), batch_size=2,
                          num_workers=0, shuffle=False, epochs=1,
                          quarantine_path=qp)
    vals = _values(loader)
    # sample 3's slot is filled by its deterministic substitute (4)
    assert vals == [[0.0, 1.0], [2.0, 4.0], [4.0, 5.0], [6.0, 7.0]]
    assert loader.stats["quarantined"] == 1 and loader.quarantined == {3}
    with open(qp) as f:
        payload = json.load(f)
    # round 21: content-hash keyed format (key None here — the test
    # dataset exposes no sample_paths, so index identity is the fallback)
    assert payload["version"] == 2
    assert [e["index"] for e in payload["samples"]] == [3]
    # a fresh loader starts from the persisted quarantine list
    loader2 = StereoLoader(_FaultDataset(bad=(3,)), batch_size=2,
                           num_workers=0, shuffle=False, epochs=1,
                           quarantine_path=qp)
    assert loader2.quarantined == {3}
    assert _values(loader2) == vals
    assert loader2.stats["quarantined"] == 0   # no NEW quarantine


def test_loader_quarantine_legacy_index_file_migrates(tmp_path):
    qp = str(tmp_path / "q.json")
    with open(qp, "w") as f:
        json.dump({"indices": [3]}, f)        # pre-round-21 format
    loader = StereoLoader(_FaultDataset(bad=(3,)), batch_size=2,
                          num_workers=0, shuffle=False, epochs=1,
                          quarantine_path=qp)
    assert loader.quarantined == {3}
    with open(qp) as f:                       # rewritten as v2 in place
        payload = json.load(f)
    assert payload["version"] == 2
    assert [e["index"] for e in payload["samples"]] == [3]


def test_loader_quarantine_content_key_survives_relisting(tmp_path):
    from raft_stereo_tpu.data.loader import sample_content_key

    class _FileDataset(_FaultDataset):
        """_FaultDataset with real file identity (sample_paths)."""

        def __init__(self, files, **kw):
            super().__init__(n=len(files), **kw)
            self.files = list(files)

        def sample_paths(self, i):
            return (self.files[i],)

    files = []
    for i in range(8):
        p = tmp_path / f"s{i}.bin"
        p.write_bytes(bytes([i]) * (i + 1))
        files.append(str(p))
    qp = str(tmp_path / "q.json")
    ds = _FileDataset(files, bad=(3,))
    loader = StereoLoader(ds, batch_size=2, num_workers=0, shuffle=False,
                          epochs=1, quarantine_path=qp)
    list(loader)
    assert loader.quarantined == {3}
    key3 = sample_content_key(ds, 3)
    with open(qp) as f:
        assert json.load(f)["samples"] == [{"index": 3, "key": key3}]
    # Re-list the dataset with a new file prepended: every index shifts
    # by one, but the content key re-locates the same bad file.
    extra = tmp_path / "s_new.bin"
    extra.write_bytes(b"xx" * 9)
    ds2 = _FileDataset([str(extra)] + files, bad=(4,))
    loader2 = StereoLoader(ds2, batch_size=2, num_workers=0,
                           shuffle=False, epochs=1, quarantine_path=qp)
    assert loader2.quarantined == {4}         # same file, new index
    # Replacing the bad file (different size) clears its quarantine.
    with open(files[3], "ab") as f:
        f.write(b"repaired")
    loader3 = StereoLoader(_FileDataset(files, bad=()), batch_size=2,
                           num_workers=0, shuffle=False, epochs=1,
                           quarantine_path=qp)
    assert loader3.quarantined == set()


def test_loader_retry_succeeds_without_quarantine():
    loader = StereoLoader(_FaultDataset(flaky=(5,)), batch_size=2,
                          num_workers=0, shuffle=False, epochs=1)
    vals = _values(loader)
    assert vals[2] == [4.0, 5.0]          # the flaky sample decoded
    assert loader.stats == {"retried": 1, "quarantined": 0,
                            "worker_respawns": 0}


def test_loader_threaded_matches_sync_under_faults():
    mk = lambda w: StereoLoader(_FaultDataset(bad=(3,)), batch_size=2,  # noqa: E731
                                num_workers=w, shuffle=False, epochs=1)
    assert _values(mk(3)) == _values(mk(0))


def test_loader_fault_isolation_off_propagates():
    loader = StereoLoader(_FaultDataset(bad=(3,)), batch_size=2,
                          num_workers=0, shuffle=False, epochs=1,
                          fault_isolation=False)
    with pytest.raises(ValueError, match="corrupt sample 3"):
        list(loader)


def test_loader_all_quarantined_is_typed():
    from raft_stereo_tpu.data.loader import LoaderBroken, _substitute_index
    with pytest.raises(LoaderBroken, match="quarantined"):
        _substitute_index(0, 4, {0, 1, 2, 3})


# ------------------------------------------------- loader exact-resume state
def test_loader_offset_resume_is_exact():
    mk = lambda: StereoLoader(_FaultDataset(16), batch_size=2,  # noqa: E731
                              num_workers=0, seed=7, epochs=2)
    full = [b["x"][:, 0, 0].tolist() for b in mk()]
    resumed = mk()
    resumed.set_state({"offset": 5, "salts": []})
    assert [b["x"][:, 0, 0].tolist() for b in resumed] == full[5:]


def test_loader_salt_reshuffles_remaining_epoch_only():
    mk = lambda: StereoLoader(_FaultDataset(16), batch_size=2,  # noqa: E731
                              num_workers=0, seed=7, epochs=1)
    base = [b["x"][:, 0, 0].tolist() for b in mk()]
    salted = mk()
    salted.set_state({"offset": 3, "salts": [[0, 3, 1]]})
    tail = [b["x"][:, 0, 0].tolist() for b in salted]
    flat_base = [v for b in base[3:] for v in b]
    flat_tail = [v for b in tail for v in b]
    # same sample set (no repeats, nothing lost), different order
    assert sorted(flat_base) == sorted(flat_tail)
    assert flat_base != flat_tail
    # salts apply with shuffle OFF too (that is the rewind's whole point)
    unshuffled = StereoLoader(_FaultDataset(16), batch_size=2,
                              num_workers=0, shuffle=False, epochs=1)
    plain = [b["x"][:, 0, 0].tolist() for b in unshuffled]
    unshuffled2 = StereoLoader(_FaultDataset(16), batch_size=2,
                               num_workers=0, shuffle=False, epochs=1)
    unshuffled2.add_salt(0, 0, 1)
    assert [b["x"][:, 0, 0].tolist() for b in unshuffled2] != plain


def test_loader_state_roundtrip_and_consumed_accounting():
    loader = StereoLoader(_FaultDataset(16), batch_size=2, num_workers=0,
                          seed=7, epochs=2)
    loader.set_state({"offset": 3, "salts": [[0, 3, 2]]})
    it = iter(loader)
    consumed = [next(it) for _ in range(4)]
    state = loader.state(consumed=4)
    assert state == {"offset": 7, "salts": [[0, 3, 2]]}
    twin = StereoLoader(_FaultDataset(16), batch_size=2, num_workers=0,
                        seed=7, epochs=2)
    twin.set_state(state)
    rest = [b["x"][:, 0, 0].tolist() for b in twin]
    tail = [b["x"][:, 0, 0].tolist() for b in it]
    assert rest == tail
    del consumed


@pytest.mark.slow
def test_loader_process_worker_respawn(tmp_path):
    """A SIGKILLed process worker (the OOM-kill case) is respawned and
    its in-flight batches resubmitted in order — the consumer sees every
    batch exactly once, plus a worker_respawns count."""
    import procworker_support as sup   # importable by spawn children

    marker = str(tmp_path / "killed.marker")
    loader = StereoLoader(sup.KillOnceDataset(marker, kill_index=5),
                          batch_size=2, num_workers=2, shuffle=False,
                          epochs=1, worker_type="process")
    vals = _values(loader)
    assert vals == [[0.0, 1.0], [2.0, 3.0], [4.0, 5.0], [6.0, 7.0]]
    assert loader.stats["worker_respawns"] >= 1
    assert os.path.exists(marker)


def test_loader_process_workers_stay_off_the_device():
    """One process per chip: the trainer holds the device and spawns the
    loader's workers, so a worker that initialised a jax backend would
    fail or hang on a TPU host.  Every sample is decoded in a child, and
    no child has a backend up (importing jax is fine, touching a device
    is not)."""
    import procworker_support as sup   # importable by spawn children

    loader = StereoLoader(sup.BackendProbeDataset(), batch_size=2,
                          num_workers=2, shuffle=False, epochs=1,
                          worker_type="process")
    batches = list(loader)
    assert len(batches) == 4
    pids = {int(p) for b in batches for p in b["pid"]}
    assert os.getpid() not in pids
    assert not any(bool(u) for b in batches for u in b["backend_up"])


# --------------------------------------------------- prefetcher (satellite 1)
def test_prefetcher_reraises_and_stays_terminal():
    from raft_stereo_tpu.training.train_loop import _DevicePrefetcher

    def gen():
        yield 1
        yield 2
        raise RuntimeError("upload died")

    pf = _DevicePrefetcher(gen(), put=lambda x: x * 10, depth=1)
    assert next(pf) == 10 and next(pf) == 20
    with pytest.raises(RuntimeError, match="upload died"):
        next(pf)
    # the old bug: this second call blocked forever on the empty queue
    with pytest.raises(RuntimeError, match="upload died"):
        next(pf)
    pf.close(timeout=2.0)
    assert not pf._thread.is_alive()


def test_prefetcher_put_exception_surfaces():
    from raft_stereo_tpu.training.train_loop import _DevicePrefetcher

    def bad_put(x):
        raise ValueError("device_put failed")

    pf = _DevicePrefetcher(iter([1, 2, 3]), put=bad_put, depth=1)
    with pytest.raises(ValueError, match="device_put failed"):
        next(pf)
    with pytest.raises(ValueError, match="device_put failed"):
        next(pf)   # terminal, no hang
    pf.close(timeout=2.0)
    assert not pf._thread.is_alive()


def test_prefetcher_exhaustion_is_sticky_and_close_joins():
    from raft_stereo_tpu.training.train_loop import _DevicePrefetcher

    pf = _DevicePrefetcher(iter([1]), put=lambda x: x, depth=1)
    assert next(pf) == 1
    assert next(pf, None) is None
    assert next(pf, None) is None   # sticky StopIteration, no hang
    pf.close(timeout=2.0)
    assert not pf._thread.is_alive()


# ------------------------------------- checkpoint integrity (satellite 2)
def _tiny_tree(step=7, seed=0):
    rng = np.random.default_rng(seed)
    return {"params": {"w": rng.normal(size=(4, 3)).astype(np.float32),
                       "b": rng.normal(size=(3,)).astype(np.float32)},
            "batch_stats": {},
            "opt_state": {"mu": {"w": np.zeros((4, 3), np.float32)}},
            "step": np.asarray(step)}


def _save(tmp_path, name, step, runtime=None):
    path = str(tmp_path / f"{step}_{name}")
    ckpt.save_checkpoint(path, RaftStereoConfig(), _tiny_tree(step),
                         runtime_state=runtime)
    return path


def test_checkpoint_byte_flip_property_sweep(tmp_path):
    """Satellite 2 (the handoff-codec v2 pattern): flip a byte ANYWHERE
    in the newest checkpoint — deep validation must reject it and
    latest_checkpoint must fall back to the newest intact step, with a
    typed reject reason.  Never a crash, never garbage."""
    older = _save(tmp_path, "run", 7)
    newest = _save(tmp_path, "run", 9)
    rng = np.random.default_rng(11)
    flips = 0
    reasons = set()
    for root, _dirs, files in os.walk(newest):
        for fn in files:
            fp = os.path.join(root, fn)
            with open(fp, "rb") as f:
                blob = f.read()
            if not blob:
                continue
            pos = int(rng.integers(0, len(blob)))
            bad = bytearray(blob)
            bad[pos] ^= 0xFF
            with open(fp, "wb") as f:
                f.write(bytes(bad))
            flips += 1
            rej = []
            assert not ckpt.is_valid_checkpoint(newest, deep=True), \
                f"flip in {fn} at {pos} undetected"
            got = ckpt.latest_checkpoint(
                str(tmp_path), name="run", deep=True,
                on_reject=lambda p, r: rej.append(r))
            assert got == older, f"flip in {fn}: fell back to {got}"
            assert rej, "rejection must be typed"
            reasons.update(rej)
            with open(fp, "wb") as f:
                f.write(blob)
    assert flips >= 4            # config, runtime-less commit, manifest, state
    # intact again after the sweep restored every byte
    assert ckpt.is_valid_checkpoint(newest, deep=True)
    assert ckpt.latest_checkpoint(str(tmp_path), name="run",
                                  deep=True) == newest
    assert any(r.startswith(("hash_mismatch", "manifest", "commit"))
               for r in reasons)


def test_checkpoint_truncation_and_missing_file_detected(tmp_path):
    path = _save(tmp_path, "run", 5)
    manifest = json.load(open(os.path.join(path, ckpt.MANIFEST_FILE)))
    victim = os.path.join(path, sorted(manifest["files"])[-1])
    blob = open(victim, "rb").read()
    with open(victim, "wb") as f:
        f.write(blob[: len(blob) // 2])
    assert not ckpt.is_valid_checkpoint(path, deep=True)
    os.remove(victim)
    ok, reason = ckpt.verify_manifest(path)
    assert not ok and reason.startswith("missing_file:")


def test_checkpoint_runtime_sidecar_roundtrip(tmp_path):
    rt = {"loop_step": 7, "loader": {"offset": 7, "salts": [[0, 3, 1]]},
          "loss_ewma": 1.5, "anomaly": {"rewinds": 1}}
    path = _save(tmp_path, "run", 7, runtime=rt)
    assert ckpt.load_runtime_state(path) == rt
    # absent on checkpoints saved without one (legacy/weights-only)
    bare = str(tmp_path / "bare")
    ckpt.save_checkpoint(bare, RaftStereoConfig(), _tiny_tree(0))
    assert ckpt.load_runtime_state(bare) is None
    assert ckpt.is_valid_checkpoint(bare, deep=True)


def test_checkpoint_good_stamp_and_prune_retention(tmp_path):
    paths = {s: _save(tmp_path, "run", s) for s in (3, 5, 7, 9, 11)}
    ckpt.mark_good(paths[5])
    assert ckpt.is_good(paths[5]) and not ckpt.is_good(paths[9])
    # GOOD is advisory metadata outside the manifest seal: deep
    # validation still passes with the stamp present.
    assert ckpt.is_valid_checkpoint(paths[5], deep=True)
    removed = ckpt.prune_checkpoints(str(tmp_path), name="run", keep=2)
    left = sorted(os.listdir(tmp_path))
    assert "11_run" in left and "9_run" in left       # keep-last-2
    assert "5_run" in left                            # newest GOOD survives
    assert "3_run" not in left and "7_run" not in left
    assert sorted(os.path.basename(p) for p in removed) == ["3_run",
                                                            "7_run"]
    # keep=0 = retention off
    assert ckpt.prune_checkpoints(str(tmp_path), name="run", keep=0) == []


def test_valid_checkpoints_orders_newest_first(tmp_path):
    for s in (3, 9, 5):
        _save(tmp_path, "run", s)
    got = [os.path.basename(p)
           for p in ckpt.valid_checkpoints(str(tmp_path), name="run")]
    assert got == ["9_run", "5_run", "3_run"]


def test_legacy_checkpoint_without_manifest_still_validates(tmp_path):
    path = _save(tmp_path, "run", 5)
    os.remove(os.path.join(path, ckpt.MANIFEST_FILE))
    # pre-round-20 writer: COMMIT without a manifest seal
    with open(os.path.join(path, ckpt.COMMIT_FILE), "w") as f:
        json.dump({"complete": True, "step": 5}, f)
    assert ckpt.is_valid_checkpoint(path)
    assert ckpt.is_valid_checkpoint(path, deep=True)   # nothing to verify
    ok, reason = ckpt.verify_manifest(path)
    assert ok and reason == "legacy_no_manifest"
    # but a sealed COMMIT whose manifest vanished is torn, not legacy
    path2 = _save(tmp_path, "run", 7)
    os.remove(os.path.join(path2, ckpt.MANIFEST_FILE))
    assert not ckpt.is_valid_checkpoint(path2, deep=True)


# ------------------------------------------------- jitted-step gate (slow)
@pytest.mark.slow
def test_anomaly_step_skips_nonfinite_and_spike(rng):
    import jax
    import jax.numpy as jnp

    from raft_stereo_tpu.training.state import create_train_state
    from raft_stereo_tpu.training.step import make_train_step

    mcfg = RaftStereoConfig(n_gru_layers=1, hidden_dims=(32,), fnet_dim=64,
                            corr_levels=2, corr_radius=3, fnet_norm="batch")
    tcfg = TrainConfig(train_iters=1, num_steps=100, anomaly_policy=True,
                       anomaly_spike_factor=8.0)
    state = create_train_state(mcfg, tcfg, jax.random.PRNGKey(0),
                               image_shape=(1, 32, 64, 3))
    policy = AnomalyPolicy.from_train_config(tcfg)
    step_fn = make_train_step(tcfg, donate=False, anomaly=policy)
    b, h, w = 2, 32, 64
    batch = {
        "image1": jnp.asarray(rng.uniform(0, 255, (b, h, w, 3)),
                              jnp.float32),
        "image2": jnp.asarray(rng.uniform(0, 255, (b, h, w, 3)),
                              jnp.float32),
        "flow": jnp.asarray(rng.normal(0, 5, (b, h, w)), jnp.float32),
        "valid": jnp.ones((b, h, w), jnp.float32)}

    s1, m1, e1 = step_fn(state, batch, jnp.float32(0.0))
    assert float(m1["skipped"]) == 0.0 and float(e1) > 0
    assert int(s1.step) == 1

    nan_batch = dict(batch, flow=jnp.full((b, h, w), jnp.nan))
    s2, m2, e2 = step_fn(s1, nan_batch, e1)
    assert float(m2["skipped"]) == 1.0
    assert float(m2["skip_nonfinite"]) == 1.0
    assert float(e2) == float(e1)           # skipped loss never enters EWMA
    assert int(s2.step) == 1                # step counter untouched
    for a, b_ in zip(jax.tree_util.tree_leaves(s1.params),
                     jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    for a, b_ in zip(jax.tree_util.tree_leaves(s1.opt_state),
                     jax.tree_util.tree_leaves(s2.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    spike_batch = dict(batch, flow=jnp.asarray(
        np.sign(np.asarray(batch["flow"])) * 600.0, jnp.float32))
    s3, m3, e3 = step_fn(s2, spike_batch, e2)
    assert float(m3["skip_spike"]) == 1.0 and float(m3["skipped"]) == 1.0
    assert np.isfinite(float(m3["loss"]))   # finite — the gate, not NaN
    assert float(e3) == float(e2)
    for a, b_ in zip(jax.tree_util.tree_leaves(s2.params),
                     jax.tree_util.tree_leaves(s3.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


@pytest.mark.slow
def test_anomaly_step_policy_off_signature_unchanged(rng):
    """policy=None keeps the exact two-arg, two-output step (the
    pre-round-20 program; existing suites pin its numerics)."""
    import jax
    import jax.numpy as jnp

    from raft_stereo_tpu.training.state import create_train_state
    from raft_stereo_tpu.training.step import make_train_step

    mcfg = RaftStereoConfig(n_gru_layers=1, hidden_dims=(32,), fnet_dim=64,
                            corr_levels=2, corr_radius=3, fnet_norm="batch")
    tcfg = TrainConfig(train_iters=1, num_steps=100)
    state = create_train_state(mcfg, tcfg, jax.random.PRNGKey(0),
                               image_shape=(1, 32, 64, 3))
    step_fn = make_train_step(tcfg, donate=False)
    batch = {
        "image1": jnp.asarray(rng.uniform(0, 255, (2, 32, 64, 3)),
                              jnp.float32),
        "image2": jnp.asarray(rng.uniform(0, 255, (2, 32, 64, 3)),
                              jnp.float32),
        "flow": jnp.asarray(rng.normal(0, 5, (2, 32, 64)), jnp.float32),
        "valid": jnp.ones((2, 32, 64), jnp.float32)}
    out = step_fn(state, batch)
    assert len(out) == 2
    _, metrics = out
    assert "skipped" not in metrics
