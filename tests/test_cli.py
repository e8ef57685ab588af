"""End-to-end CLI + training-loop tests on synthetic data (CPU)."""

import glob
import os

import numpy as np
import pytest
from PIL import Image

from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
from raft_stereo_tpu.data import frame_utils
from raft_stereo_tpu.data.datasets import KITTI
from raft_stereo_tpu.data.loader import StereoLoader

pytestmark = pytest.mark.slow  # full-model / subprocess-scale tests

TINY = dict(hidden_dims=(32, 32, 32), fnet_dim=64)  # fast CPU compiles


def _make_kitti_tree(root, n=3, size=(64, 96)):
    h, w = size
    rng = np.random.default_rng(0)
    for sub in ("image_2", "image_3", "disp_occ_0"):
        (root / "training" / sub).mkdir(parents=True)
    for i in range(n):
        left = rng.integers(0, 255, (h, w, 3), dtype=np.uint8)
        Image.fromarray(left).save(
            root / "training" / "image_2" / f"{i:06d}_10.png")
        Image.fromarray(np.roll(left, -3, axis=1)).save(
            root / "training" / "image_3" / f"{i:06d}_10.png")
        frame_utils.write_disp_kitti(
            str(root / "training" / "disp_occ_0" / f"{i:06d}_10.png"),
            np.full((h, w), 3.0, np.float32))
    return root


@pytest.fixture(scope="module")
def tiny_checkpoint(tmp_path_factory):
    """A saved orbax checkpoint of a tiny random-init model."""
    import jax

    from raft_stereo_tpu.models.raft_stereo import RAFTStereo
    from raft_stereo_tpu.training.checkpoint import save_weights

    cfg = RaftStereoConfig(**TINY)
    model = RAFTStereo(cfg)
    import jax.numpy as jnp
    dummy = jnp.zeros((1, 64, 96, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), dummy, dummy, iters=1,
                           test_mode=True)
    path = str(tmp_path_factory.mktemp("ckpt") / "tiny")
    save_weights(path, cfg, variables["params"],
                 variables.get("batch_stats"))
    return path


def test_demo_cli(tmp_path, tiny_checkpoint):
    from raft_stereo_tpu.cli.demo import main

    root = _make_kitti_tree(tmp_path / "KITTI")
    out = tmp_path / "out"
    main(["--restore_ckpt", tiny_checkpoint,
          "-l", str(root / "training" / "image_2" / "*_10.png"),
          "-r", str(root / "training" / "image_3" / "*_10.png"),
          "--output_directory", str(out),
          "--save_numpy", "--valid_iters", "2"])
    pngs = sorted(glob.glob(str(out / "*-disparity.png")))
    npys = sorted(glob.glob(str(out / "*.npy")))
    assert len(pngs) == 3 and len(npys) == 3
    disp = np.load(npys[0])
    assert disp.shape == (64, 96) and np.isfinite(disp).all()


def test_demo_cli_sequence_mode(tmp_path, tiny_checkpoint, caplog):
    """--sequence runs the frames in order with warm-start chaining and
    logs per-frame iters_used + cumulative FPS (round-14 satellite)."""
    import logging

    from raft_stereo_tpu.cli.demo import main

    root = _make_kitti_tree(tmp_path / "KITTI")
    out = tmp_path / "seq_out"
    with caplog.at_level(logging.INFO):
        main(["--restore_ckpt", tiny_checkpoint,
              "-l", str(root / "training" / "image_2" / "*_10.png"),
              "-r", str(root / "training" / "image_3" / "*_10.png"),
              "--output_directory", str(out), "--sequence",
              "--valid_iters", "2", "--exit_threshold_px", "1e9"])
    pngs = sorted(glob.glob(str(out / "*-disparity.png")))
    assert len(pngs) == 3
    text = caplog.text
    assert "frame 0 cold" in text
    assert "frame 1 warm" in text and "frame 2 warm" in text
    assert "cumulative" in text and "sequence done" in text


def test_evaluate_cli(tmp_path, tiny_checkpoint, capsys):
    from raft_stereo_tpu.cli.evaluate import main

    _make_kitti_tree(tmp_path / "KITTI")
    results = main(["--restore_ckpt", tiny_checkpoint,
                    "--dataset", "kitti",
                    "--data_root", str(tmp_path),
                    "--valid_iters", "2", "--max_images", "2"])
    assert "kitti-epe" in results and "kitti-d1" in results
    assert np.isfinite(results["kitti-epe"])


def test_evaluate_cli_sequence_mode(tmp_path, tiny_checkpoint):
    """--sequence reports warm-start EPE drift vs cold per-frame
    inference and records it to --stream_out (round-14 satellite)."""
    import json

    from raft_stereo_tpu.cli.evaluate import main

    _make_kitti_tree(tmp_path / "KITTI")
    out = tmp_path / "STREAM_test.json"
    results = main(["--restore_ckpt", tiny_checkpoint,
                    "--dataset", "kitti", "--data_root", str(tmp_path),
                    "--valid_iters", "2", "--max_images", "2",
                    "--sequence", "--stream_out", str(out)])
    for key in ("kitti-epe-cold", "kitti-epe-warm",
                "kitti-warm-drift-epe"):
        assert key in results and np.isfinite(results[key])
    assert results["kitti-warm-drift-epe"] == pytest.approx(
        results["kitti-epe-warm"] - results["kitti-epe-cold"])
    rec = json.loads(out.read_text())
    assert rec["metric"] == "warm_start_sequence_drift"
    assert rec["dataset"] == "kitti" and "results" in rec


def test_train_loop_and_exact_resume(tmp_path):
    from raft_stereo_tpu.training.train_loop import train

    root = _make_kitti_tree(tmp_path / "KITTI", n=4)
    model_cfg = RaftStereoConfig(**TINY)
    train_cfg = TrainConfig(batch_size=2, train_iters=2, num_steps=3,
                            image_size=(48, 64), data_parallel=2,
                            validation_frequency=2, seed=7)
    aug = {"crop_size": (48, 64), "min_scale": -0.2, "max_scale": 0.4,
           "do_flip": None, "yjitter": False}
    ds = KITTI(aug_params=aug, root=str(root))
    loader = StereoLoader(ds, batch_size=2, num_workers=0, seed=7)

    ckpt_dir = str(tmp_path / "ckpts")
    state = train(model_cfg, train_cfg, name="t", data_root="unused",
                  checkpoint_dir=ckpt_dir, log_dir=str(tmp_path / "runs"),
                  loader=loader)
    assert int(state.step) == 3
    assert os.path.isdir(os.path.join(ckpt_dir, "t"))

    # exact resume continues from the saved step with optimizer state intact
    train_cfg2 = TrainConfig(**{**train_cfg.to_dict(), "num_steps": 5})
    loader2 = StereoLoader(ds, batch_size=2, num_workers=0, seed=7)
    state2 = train(model_cfg, train_cfg2, name="t2", data_root="unused",
                   checkpoint_dir=ckpt_dir, log_dir=str(tmp_path / "runs2"),
                   restore=os.path.join(ckpt_dir, "t"), loader=loader2)
    assert int(state2.step) == 5


def test_train_cli_with_periodic_validation(tmp_path, capsys):
    """The reference's every-N-steps validation regression check
    (train_stereo.py:183-193), wired through the CLI: a 2-step run on a
    synthetic KITTI tree validates at step 2 and logs the metrics dict."""
    from raft_stereo_tpu.cli import train as train_cli

    _make_kitti_tree(tmp_path / "KITTI", n=4, size=(64, 96))
    state = train_cli.main([
        "--name", "t", "--data_root", str(tmp_path),
        "--checkpoint_dir", str(tmp_path / "ck"),
        "--log_dir", str(tmp_path / "runs"),
        "--train_datasets", "kitti", "--batch_size", "2", "--num_steps", "2",
        "--train_iters", "2", "--valid_iters", "2",
        "--image_size", "48", "64", "--hidden_dims", "32", "32", "32",
        "--validate_datasets", "kitti", "--validation_frequency", "2",
        "--validate_max_images", "2", "--data_parallel", "2",
    ])
    assert int(state.step) == 2
    out = capsys.readouterr().out
    assert "Validation kitti" in out


def test_runner_cache_bounded_and_bucketed(tiny_checkpoint):
    """Per-shape compile cache evicts LRU-style, and shape_bucket collapses
    nearby shapes into one compiled program."""
    import numpy as np

    from raft_stereo_tpu.eval.runner import InferenceRunner
    from raft_stereo_tpu.training.checkpoint import load_weights

    cfg, variables = load_weights(tiny_checkpoint)
    runner = InferenceRunner(cfg, variables, iters=1, max_cached_shapes=2)
    rng = np.random.default_rng(0)
    for h, w in ((32, 64), (64, 64), (64, 96), (32, 64)):
        img = rng.uniform(0, 255, (h, w, 3)).astype(np.float32)
        flow, _ = runner(img, img)
        assert flow.shape == (h, w)
    assert len(runner._compiled) == 2  # bounded; oldest evicted

    bucketed = InferenceRunner(cfg, variables, iters=1, shape_bucket=64)
    for h, w in ((60, 90), (62, 94), (33, 65)):
        img = rng.uniform(0, 255, (h, w, 3)).astype(np.float32)
        flow, _ = bucketed(img, img)
        assert flow.shape == (h, w)  # exact unpad regardless of bucket
    assert len(bucketed._compiled) == 1  # all bucket to (64, 128)


def test_runner_batched_matches_per_image(tiny_checkpoint):
    """run_batch (one upload / one forward / one fetch for N pairs) returns
    the same flows as N per-image calls — the throughput product mode."""
    import numpy as np

    from raft_stereo_tpu.eval.runner import InferenceRunner
    from raft_stereo_tpu.training.checkpoint import load_weights

    cfg, variables = load_weights(tiny_checkpoint)
    runner = InferenceRunner(cfg, variables, iters=2)
    rng = np.random.default_rng(3)
    lefts = [rng.uniform(0, 255, (60, 90, 3)).astype(np.uint8)
             for _ in range(3)]
    rights = [np.roll(l, -3, axis=1) for l in lefts]

    flows, secs = runner.run_batch(lefts, rights)
    assert flows.shape == (3, 60, 90) and secs > 0
    for i in range(3):
        per_img, _ = runner(lefts[i], rights[i])
        # batch-3 and batch-1 are different executables; XLA layout/fusion
        # reassociation drifts a few 1e-5 on O(10) flows
        np.testing.assert_allclose(flows[i], per_img, atol=5e-4)

    with pytest.raises(AssertionError, match="same-shape"):
        runner.run_batch([lefts[0], lefts[1][:32]],
                         [rights[0], rights[1][:32]])


@pytest.mark.quick  # overrides the module slow mark: runner-construction only
def test_runner_deep_iters_bf16_corr_guard():
    """iters >= DEEP_ITERS_FP32_CORR with bf16 corr flips corr_fp32 in the
    runner's effective config (measured 32-iter drift, tools/bf16_drift.py);
    the as-given config is preserved for identity comparisons, and
    corr_fp32_auto=False opts out (tools/bf16_drift.py measures raw bf16)."""
    import dataclasses

    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.eval.runner import DEEP_ITERS_FP32_CORR, InferenceRunner

    cfg = RaftStereoConfig(mixed_precision=True)
    assert not cfg.corr_fp32
    deep = InferenceRunner(cfg, {}, iters=DEEP_ITERS_FP32_CORR)
    assert deep.effective_config.corr_fp32
    assert deep.config == cfg  # make_validation_fn compares this
    assert deep.effective_config == dataclasses.replace(cfg, corr_fp32=True)

    shallow = InferenceRunner(cfg, {}, iters=7)
    assert not shallow.effective_config.corr_fp32

    opted_out = InferenceRunner(cfg, {}, iters=32, corr_fp32_auto=False)
    assert not opted_out.effective_config.corr_fp32

    fp32_cfg = RaftStereoConfig()  # no mixed precision -> nothing to guard
    assert not InferenceRunner(fp32_cfg, {},
                               iters=32).effective_config.corr_fp32


def test_train_cli_rows_gru(tmp_path):
    """Full-loop context parallelism from the user-facing surface: the one
    -flag UX the reference gives DataParallel (train_stereo.py:134), here
    ``--rows_shards 2 --rows_gru``.  Launches a real training step through
    cli.train on a 2-device rows mesh (1 data x 1 corr x 2 rows)."""
    from raft_stereo_tpu.cli import train as train_cli

    # fine level = 192/4 = 48 rows -> slab 24 = 2*halo at halo=12
    _make_kitti_tree(tmp_path / "KITTI", n=4, size=(192, 96))
    state = train_cli.main([
        "--name", "rg", "--data_root", str(tmp_path),
        "--checkpoint_dir", str(tmp_path / "ck"),
        "--log_dir", str(tmp_path / "runs"),
        "--train_datasets", "kitti", "--batch_size", "1", "--num_steps", "1",
        "--train_iters", "2", "--valid_iters", "2",
        "--image_size", "192", "64", "--hidden_dims", "32", "32", "32",
        "--data_parallel", "1",
        "--rows_shards", "2", "--rows_gru", "--rows_gru_halo", "12",
    ])
    assert int(state.step) == 1
