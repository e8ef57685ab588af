"""Data layer: format readers, augmentors, datasets, loader."""
import os

import numpy as np
import pytest
from PIL import Image

from raft_stereo_tpu.data import frame_utils
from raft_stereo_tpu.data.augment import (ColorJitter, DenseAugmentor,
                                          SparseAugmentor)
from raft_stereo_tpu.data.datasets import KITTI, StereoDataset
from raft_stereo_tpu.data.loader import StereoLoader


# ------------------------------------------------------------------ formats
def test_pfm_roundtrip(tmp_path, rng):
    disp = rng.uniform(0, 100, (13, 17)).astype(np.float32)
    path = str(tmp_path / "x.pfm")
    frame_utils.write_pfm(path, disp)
    back = frame_utils.read_pfm(path)
    np.testing.assert_array_equal(back, disp)


def test_flo_roundtrip(tmp_path, rng):
    flow = rng.normal(size=(7, 9, 2)).astype(np.float32)
    path = str(tmp_path / "x.flo")
    frame_utils.write_flo(path, flow)
    np.testing.assert_array_equal(frame_utils.read_flo(path), flow)


def test_kitti_disp_roundtrip(tmp_path, rng):
    disp = (rng.uniform(0, 200, (11, 19)) * 256).astype(np.uint16) / 256.0
    disp[0, :5] = 0.0  # invalid pixels
    path = str(tmp_path / "d.png")
    frame_utils.write_disp_kitti(path, disp)
    back, valid = frame_utils.read_disp_kitti(path)
    np.testing.assert_allclose(back, disp, atol=1 / 256)
    assert not valid[0, :5].any() and valid[5:].all()


def test_sintel_packed_disparity(tmp_path):
    # disparity d encodes as R*4 + G/64 + B/16384
    rgb = np.zeros((4, 6, 3), np.uint8)
    rgb[..., 0] = 10  # 2.5 px
    rgb[..., 1] = 64  # +1 px
    (tmp_path / "disparities").mkdir()
    (tmp_path / "occlusions").mkdir()
    Image.fromarray(rgb).save(tmp_path / "disparities" / "frame_0001.png")
    occ = np.zeros((4, 6), np.uint8)
    occ[0, 0] = 255  # occluded pixel
    Image.fromarray(occ).save(tmp_path / "occlusions" / "frame_0001.png")
    disp, valid = frame_utils.read_disp_sintel(
        str(tmp_path / "disparities" / "frame_0001.png"))
    np.testing.assert_allclose(disp, 41.0, atol=1e-5)
    assert not valid[0, 0] and valid[1:].all()


def test_read_gen_dispatch(tmp_path, rng):
    img = rng.integers(0, 255, (5, 7, 3), dtype=np.uint8)
    Image.fromarray(img).save(tmp_path / "i.png")
    out = frame_utils.read_gen(str(tmp_path / "i.png"))
    np.testing.assert_array_equal(out, img)
    with pytest.raises(ValueError):
        frame_utils.read_gen("nope.xyz")


# --------------------------------------------------------------- augmentors
def test_color_jitter_deterministic(rng):
    img = rng.integers(0, 255, (32, 32, 3), dtype=np.uint8)
    jit = ColorJitter(0.4, 0.4, (0.6, 1.4), 0.16)
    a = jit(img, np.random.default_rng(7))
    b = jit(img, np.random.default_rng(7))
    c = jit(img, np.random.default_rng(8))
    np.testing.assert_array_equal(a, b)
    assert a.shape == img.shape and a.dtype == np.uint8
    assert np.any(a != c)  # different draw actually changes the image


def test_dense_augmentor_shapes_and_determinism(rng):
    crop = (64, 96)
    aug = DenseAugmentor(crop, yjitter=True)
    img1 = rng.integers(0, 255, (120, 160, 3), dtype=np.uint8)
    img2 = rng.integers(0, 255, (120, 160, 3), dtype=np.uint8)
    flow = rng.normal(size=(120, 160, 2)).astype(np.float32)
    o1 = aug(img1, img2, flow, np.random.default_rng(3))
    o2 = aug(img1, img2, flow, np.random.default_rng(3))
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(a, b)
    assert o1[0].shape == (*crop, 3) and o1[2].shape == (*crop, 2)


def test_sparse_resize_scatters_not_interpolates():
    # one valid pixel among invalid neighbours must stay a single valid
    # pixel after 2x upscale, with flow scaled by the factor
    flow = np.zeros((8, 8, 2), np.float32)
    valid = np.zeros((8, 8), np.float32)
    flow[4, 4] = [-10.0, 0.0]
    valid[4, 4] = 1
    f2, v2 = SparseAugmentor.resize_sparse_flow(flow, valid, 2.0, 2.0)
    assert f2.shape == (16, 16, 2)
    assert v2.sum() == 1
    yy, xx = np.nonzero(v2)
    np.testing.assert_allclose(f2[yy[0], xx[0]], [-20.0, 0.0])


def test_sparse_augmentor_shapes(rng):
    crop = (64, 96)
    aug = SparseAugmentor(crop)
    img1 = rng.integers(0, 255, (120, 160, 3), dtype=np.uint8)
    img2 = rng.integers(0, 255, (120, 160, 3), dtype=np.uint8)
    flow = np.zeros((120, 160, 2), np.float32)
    valid = (rng.uniform(size=(120, 160)) < 0.3).astype(np.float32)
    i1, i2, f, v = aug(img1, img2, flow, valid, np.random.default_rng(5))
    assert i1.shape == (*crop, 3) and f.shape == (*crop, 2)
    assert v.shape == crop and set(np.unique(v)).issubset({0, 1})


def test_stereo_hflip_swaps_views(rng):
    aug = DenseAugmentor((64, 96), min_scale=0, max_scale=0, do_flip="h",
                         yjitter=False)
    aug.jitter = ColorJitter(0, 0, (1, 1), 0)  # disable photometric noise
    aug.stretch_prob = 0.0  # keep scale exactly 1 so crops match raw pixels
    img1 = rng.integers(0, 255, (80, 120, 3), dtype=np.uint8)
    img2 = rng.integers(0, 255, (80, 120, 3), dtype=np.uint8)
    flow = np.zeros((80, 120, 2), np.float32)
    # find an rng draw that triggers the flip (prob 0.5)
    for seed in range(20):
        r = np.random.default_rng(seed)
        o1, o2, _ = aug(img1, img2, flow, r)
        # after swap-and-mirror, img1's crop must come from mirrored img2
        flipped2 = img2[:, ::-1]
        found = any(
            np.array_equal(o1, flipped2[y:y + 64, x:x + 96])
            for y in range(0, 17) for x in range(0, 25))
        if found:
            return
    pytest.fail("stereo h-flip never produced a crop of mirrored img2")


# ----------------------------------------------------------------- datasets
def _make_kitti_tree(tmp_path, n=5, size=(40, 60)):
    h, w = size
    rng = np.random.default_rng(0)
    for sub in ("image_2", "image_3", "disp_occ_0"):
        (tmp_path / "training" / sub).mkdir(parents=True)
    for i in range(n):
        for sub in ("image_2", "image_3"):
            img = rng.integers(0, 255, (h, w, 3), dtype=np.uint8)
            Image.fromarray(img).save(
                tmp_path / "training" / sub / f"{i:06d}_10.png")
        disp = rng.uniform(1, 30, (h, w)).astype(np.float32)
        frame_utils.write_disp_kitti(
            str(tmp_path / "training" / "disp_occ_0" / f"{i:06d}_10.png"),
            disp)
    return tmp_path


def test_kitti_dataset_sample(tmp_path):
    root = _make_kitti_tree(tmp_path)
    ds = KITTI(aug_params=None, root=str(root))
    assert len(ds) == 5
    s = ds[0]
    assert s["image1"].shape == (40, 60, 3)
    assert s["flow"].shape == (40, 60)
    assert (s["flow"] <= 0).all()  # x-flow = -disparity
    assert s["valid"].min() >= 0 and s["valid"].max() <= 1


def test_dataset_mul_and_concat(tmp_path):
    root = _make_kitti_tree(tmp_path)
    ds = KITTI(aug_params=None, root=str(root))
    tripled = ds * 3
    assert len(tripled) == 15
    both = ds + tripled
    assert len(both) == 20
    # concat indexing reaches the second part
    s = both[17]
    assert s["image1"].shape == (40, 60, 3)


def test_loader_threaded_matches_sync(tmp_path):
    root = _make_kitti_tree(tmp_path, n=6)
    aug = {"crop_size": (32, 48), "min_scale": -0.2, "max_scale": 0.4,
           "do_flip": None, "yjitter": False}
    ds = KITTI(aug_params=aug, root=str(root))
    mk = lambda workers: StereoLoader(ds, batch_size=2, num_workers=workers,
                                      seed=42, epochs=2)
    sync_batches = list(mk(0))
    thr_batches = list(mk(3))
    assert len(sync_batches) == len(thr_batches) == 6
    for a, b in zip(sync_batches, thr_batches):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    assert sync_batches[0]["image1"].shape == (2, 32, 48, 3)


def test_loader_epoch_reshuffles(tmp_path):
    root = _make_kitti_tree(tmp_path, n=6)
    ds = KITTI(aug_params=None, root=str(root))
    loader = StereoLoader(ds, batch_size=6, num_workers=0, seed=0, epochs=2)
    b1, b2 = list(loader)
    assert any(not np.array_equal(b1[k], b2[k]) for k in b1)


def test_sceneflow_loader_decode_throughput(tmp_path):
    """Guards the PFM+PNG decode -> DenseAugmentor -> batch path on the
    SceneFlow disk layout (the training recipe's input, reference:
    core/stereo_datasets.py:123-184).  Uses the tree builder of
    tests/golden_data.py, the one chip_smoke.py's training phase reads;
    asserts correctness and, on request, a very conservative throughput
    floor."""
    import time

    from golden_data import build_tree
    from raft_stereo_tpu.data.datasets import SceneFlow

    root = str(tmp_path / "sf")
    build_tree(root, n_pairs=8, hw=(120, 200))
    aug = {"crop_size": (96, 160), "min_scale": -0.2, "max_scale": 0.4,
           "do_flip": None, "yjitter": True}
    ds = SceneFlow(aug, root=root, dstype="frames_cleanpass")
    assert len(ds) == 8
    loader = StereoLoader(ds, batch_size=4, num_workers=2, seed=0, epochs=2)
    t0 = time.perf_counter()
    batches = list(loader)
    dt = time.perf_counter() - t0
    assert len(batches) == 4
    b = batches[0]
    assert b["image1"].shape == (4, 96, 160, 3)
    assert b["image1"].dtype == np.uint8  # device-transfer-lean contract
    assert b["flow"].shape == (4, 96, 160)
    assert np.all(b["flow"] <= 0)  # x-flow = -disparity
    assert set(np.unique(b["valid"])) <= {0.0, 1.0}
    # 16 images decoded+augmented; wall-clock floors flake on oversubscribed
    # CI runners no matter the headroom, so the timing assert is opt-in
    # (RAFT_TPU_TIMING_ASSERTS=1 on a quiet host).  Whether the loader keeps
    # up with the chip's step is not measured yet (PERF.md §7 0a: no
    # training cell); the shape/dtype contract asserts above stay
    # unconditional.
    if os.environ.get("RAFT_TPU_TIMING_ASSERTS", "").lower() in (
            "1", "true", "yes"):
        assert 16 / dt > 2.0, f"decode path too slow: {16 / dt:.1f} images/s"
