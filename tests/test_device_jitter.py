"""On-device photometric jitter (data/device_jitter.py): op-level parity vs
the host ColorJitter ops, pair semantics, determinism, and the train-step /
loader wiring of TrainConfig.device_photometric."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_stereo_tpu.data import augment as host_aug
from raft_stereo_tpu.data.device_jitter import (JitterParams,
                                                adjust_brightness,
                                                adjust_contrast,
                                                adjust_gamma, adjust_hue,
                                                adjust_saturation,
                                                apply_photometric,
                                                params_for_datasets)


@pytest.fixture
def img(rng):
    return rng.integers(0, 256, (40, 56, 3)).astype(np.uint8)


def dev(x):
    return jnp.asarray(np.asarray(x, np.float32))


def test_ops_match_host(img):
    """Fixed-factor device ops == uint8 host ops within rounding (host
    truncates to uint8 after each op; hue additionally quantizes the shift
    to cv2's 1/180-turn grid, so it gets a wider tolerance)."""
    f = dev(img)
    for factor in (0.6, 1.0, 1.37):
        np.testing.assert_allclose(
            np.asarray(adjust_brightness(f, factor)),
            host_aug.adjust_brightness(img, factor).astype(np.float32),
            atol=1.0)
        host_mean = img.mean(axis=-1, dtype=np.float32).mean(
            dtype=np.float32)
        np.testing.assert_allclose(
            np.asarray(adjust_contrast(f, factor, host_mean)),
            host_aug.adjust_contrast(img, factor).astype(np.float32),
            atol=1.0)
        np.testing.assert_allclose(
            np.asarray(adjust_saturation(f, factor)),
            host_aug.adjust_saturation(img, factor).astype(np.float32),
            atol=1.0)
    for gamma, gain in ((0.7, 1.0), (1.3, 1.1)):
        np.testing.assert_allclose(
            np.asarray(adjust_gamma(f, gamma, gain)),
            host_aug.adjust_gamma(img, gamma, gain).astype(np.float32),
            atol=1.0)
    for shift in (-0.11, 0.0, 0.25, 0.4):
        got = np.asarray(adjust_hue(f, shift))
        want = host_aug.adjust_hue(img, shift).astype(np.float32)
        # cv2 quantizes hue to 1/180 turns and round-trips through uint8
        # HSV; allow a few counts of drift on a minority of pixels
        assert np.median(np.abs(got - want)) <= 2.0
        assert np.mean(np.abs(got - want) > 6.0) < 0.02


def test_hue_identity_and_full_turn(img):
    f = dev(img)
    np.testing.assert_allclose(np.asarray(adjust_hue(f, 0.0)),
                               np.asarray(f, np.float32), atol=1e-3)
    np.testing.assert_allclose(np.asarray(adjust_hue(f, 1.0)),
                               np.asarray(f, np.float32), atol=1e-2)


def test_pair_symmetric_vs_asymmetric(rng):
    b, h, w = 6, 24, 32
    img = rng.integers(0, 256, (b, h, w, 3)).astype(np.uint8)
    key = jax.random.PRNGKey(3)

    # asymmetric_prob=0: identical views get identical jitter (shared
    # factors AND order; contrast blends toward the joint mean)
    sym = JitterParams(asymmetric_prob=0.0)
    o1, o2 = apply_photometric(dev(img), dev(img), key, sym)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)

    # asymmetric_prob=1: independent factors -> views diverge
    asym = JitterParams(asymmetric_prob=1.0)
    a1, a2 = apply_photometric(dev(img), dev(img), key, asym)
    assert np.max(np.abs(np.asarray(a1) - np.asarray(a2))) > 1.0

    # determinism: same key -> bit-identical stream
    r1, r2 = apply_photometric(dev(img), dev(img), key, asym)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(r1))
    np.testing.assert_array_equal(np.asarray(a2), np.asarray(r2))

    # different key -> different factors
    d1, _ = apply_photometric(dev(img), dev(img), jax.random.PRNGKey(4), asym)
    assert np.max(np.abs(np.asarray(a1) - np.asarray(d1))) > 1.0

    # range contract
    for x in (o1, a1, a2):
        arr = np.asarray(x)
        assert arr.dtype == np.float32
        assert arr.min() >= 0.0 and arr.max() <= 255.0


def test_per_sample_independence(rng):
    """Each batch sample draws its own factors: a batch of identical images
    comes out with per-sample distinct jitter."""
    img = np.broadcast_to(rng.integers(0, 256, (1, 24, 32, 3)),
                          (4, 24, 32, 3)).astype(np.uint8)
    out, _ = apply_photometric(dev(img), dev(img), jax.random.PRNGKey(0),
                               JitterParams())
    out = np.asarray(out)
    assert np.max(np.abs(out[0] - out[1])) > 1.0


def test_params_for_datasets():
    dense = params_for_datasets(("sceneflow", "falling_things"))
    assert dense.brightness == 0.4 and dense.saturation == (0.6, 1.4)
    sparse = params_for_datasets(("kitti",))
    assert sparse.brightness == 0.3 and sparse.saturation == (0.7, 1.3)
    # host SparseAugmentor jitters the stacked pair unconditionally —
    # the device profile must be symmetric-only
    assert sparse.asymmetric_prob == 0.0
    tartan = params_for_datasets(("tartan_air_seasons",))
    assert tartan.brightness == 0.4
    with pytest.raises(ValueError, match="mixture"):
        params_for_datasets(("sceneflow", "kitti"))
    # overrides flow through like build_training_mixture's aug_params
    p = params_for_datasets(("sceneflow",), saturation_range=(0.0, 1.4),
                            img_gamma=(0.5, 1.2))
    assert p.saturation == (0.0, 1.4)
    assert p.gamma == (0.5, 1.2, 1.0, 1.0)


def test_host_augmentor_photometric_opt_out(rng):
    """photometric=False skips ColorJitter on the host (the device applies
    it instead); spatial/eraser still run."""
    img1 = rng.integers(0, 256, (64, 96, 3)).astype(np.uint8)
    img2 = rng.integers(0, 256, (64, 96, 3)).astype(np.uint8)
    flow = rng.standard_normal((64, 96, 2)).astype(np.float32)
    aug = host_aug.DenseAugmentor((32, 48), photometric=False)
    a1, a2, af = aug(img1, img2, flow, np.random.default_rng(0))
    assert a1.shape == (32, 48, 3) and af.shape == (32, 48, 2)
    # pixel values of view 1 are crop/resize outputs of the ORIGINAL image
    # (no photometric changes); with jitter on they would differ.
    jit_on = host_aug.DenseAugmentor((32, 48), photometric=True)
    b1, _, _ = jit_on(img1, img2, flow, np.random.default_rng(0))
    assert not np.array_equal(a1, b1)


def test_train_step_with_device_photometric(rng):
    """make_train_step wires jitter from TrainConfig; loss stays finite and
    params update; the jitter stream is step-deterministic."""
    from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
    from raft_stereo_tpu.training.state import create_train_state
    from raft_stereo_tpu.training.step import make_train_step

    mcfg = RaftStereoConfig(hidden_dims=(16, 16, 16), fnet_dim=32,
                            corr_levels=2, corr_radius=2, n_gru_layers=1,
                            corr_backend="reg")
    tcfg = TrainConfig(batch_size=2, train_iters=2, image_size=(32, 48),
                       device_photometric=True, train_datasets=("sceneflow",))
    state = create_train_state(mcfg, tcfg, jax.random.PRNGKey(0),
                               (1, 32, 48, 3))
    step = make_train_step(tcfg, mesh=None, donate=False)
    batch = {
        "image1": rng.integers(0, 256, (2, 32, 48, 3)).astype(np.uint8),
        "image2": rng.integers(0, 256, (2, 32, 48, 3)).astype(np.uint8),
        "flow": rng.uniform(-8, 0, (2, 32, 48)).astype(np.float32),
        "valid": np.ones((2, 32, 48), np.float32),
    }
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_state.step) == 1
    # same state/batch -> same jitter key -> bit-identical loss
    _, metrics2 = step(state, batch)
    assert float(metrics["loss"]) == float(metrics2["loss"])


def test_process_worker_loader_matches_sync(tmp_path):
    """worker_type='process' yields byte-identical batches in the same
    order as the synchronous path (determinism is scheduling-free)."""
    from golden_data import build_tree
    from raft_stereo_tpu.data.datasets import SceneFlow
    from raft_stereo_tpu.data.loader import StereoLoader

    root = str(tmp_path / "sf")
    build_tree(root, n_pairs=6, hw=(96, 144))
    aug = {"crop_size": (64, 96), "min_scale": -0.2, "max_scale": 0.4,
           "do_flip": None, "yjitter": True}

    def batches(**kw):
        ds = SceneFlow(aug, root=root, dstype="frames_cleanpass")
        return list(StereoLoader(ds, batch_size=2, seed=5, epochs=1, **kw))

    ref = batches(num_workers=0)
    got = batches(num_workers=2, worker_type="process")
    assert len(ref) == len(got) == 3
    for b_ref, b_got in zip(ref, got):
        for k in b_ref:
            np.testing.assert_array_equal(b_ref[k], b_got[k])
