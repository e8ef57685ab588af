"""End-to-end model tests: shapes, modes, config variants, gradients."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_stereo_tpu.config import RaftStereoConfig
from raft_stereo_tpu.models import RAFTStereo


def _init_and_run(cfg, B=1, H=64, W=96, iters=3, test_mode=False, seed=0):
    model = RAFTStereo(cfg)
    rngs = jax.random.PRNGKey(seed)
    img1 = jnp.asarray(
        np.random.default_rng(seed).uniform(0, 255, (B, H, W, 3)), jnp.float32)
    img2 = img1 + 1.0
    variables = model.init(rngs, img1, img2, iters=2, test_mode=True)
    out = model.apply(variables, img1, img2, iters=iters, test_mode=test_mode)
    return variables, out


@pytest.mark.slow
def test_train_mode_shapes():
    cfg = RaftStereoConfig()
    _, preds = _init_and_run(cfg, B=2, H=64, W=96, iters=3)
    assert preds.shape == (3, 2, 64, 96)
    assert np.all(np.isfinite(np.asarray(preds)))


@pytest.mark.slow
def test_test_mode_shapes():
    cfg = RaftStereoConfig()
    _, (disp_low, disp_up) = _init_and_run(cfg, iters=3, test_mode=True)
    assert disp_low.shape == (1, 16, 24)   # 1/4 res (n_downsample=2)
    assert disp_up.shape == (1, 64, 96)


@pytest.mark.slow
@pytest.mark.parametrize("n_gru_layers", [1, 2, 3])
def test_gru_layer_variants(n_gru_layers):
    cfg = RaftStereoConfig(n_gru_layers=n_gru_layers)
    _, preds = _init_and_run(cfg, iters=2)
    assert preds.shape == (2, 1, 64, 96)


@pytest.mark.slow
def test_realtime_config():
    """shared_backbone + n_downsample 3 + 2 GRU layers + slow_fast
    (reference: README.md:84)."""
    cfg = RaftStereoConfig(shared_backbone=True, n_downsample=3,
                           n_gru_layers=2, slow_fast_gru=True,
                           mixed_precision=True, corr_backend="reg_fused")
    _, (disp_low, disp_up) = _init_and_run(cfg, iters=2, test_mode=True)
    assert disp_low.shape == (1, 8, 12)
    assert disp_up.shape == (1, 64, 96)
    assert np.all(np.isfinite(np.asarray(disp_up)))


@pytest.mark.slow
def test_alt_backend_matches_reg():
    """Backend interchangeability — the reference's core contract
    (core/raft_stereo.py:90-100)."""
    out = {}
    for backend in ("reg", "alt"):
        cfg = RaftStereoConfig(corr_backend=backend)
        variables, preds = _init_and_run(cfg, iters=2, seed=7)
        out[backend] = np.asarray(preds)
    np.testing.assert_allclose(out["reg"], out["alt"], rtol=1e-4, atol=1e-3)


@pytest.mark.slow
def test_flow_init_warm_start():
    cfg = RaftStereoConfig()
    model = RAFTStereo(cfg)
    img = jnp.zeros((1, 64, 96, 3))
    variables = model.init(jax.random.PRNGKey(0), img, img, iters=1,
                           test_mode=True)
    flow_init = jnp.full((1, 16, 24), -3.0)
    disp_low, _ = model.apply(variables, img, img, iters=1,
                              flow_init=flow_init, test_mode=True)
    # one GRU iteration moves the field but it should stay near the init
    assert np.abs(np.asarray(disp_low).mean() - (-3.0)) < 3.0


@pytest.mark.slow
def test_gradients_flow():
    cfg = RaftStereoConfig(n_gru_layers=2)
    model = RAFTStereo(cfg)
    img1 = jnp.ones((1, 32, 64, 3)) * 100
    img2 = jnp.ones((1, 32, 64, 3)) * 120
    variables = model.init(jax.random.PRNGKey(0), img1, img2, iters=1,
                           test_mode=True)

    def loss_fn(params):
        preds = model.apply({**variables, "params": params}, img1, img2,
                            iters=2)
        return jnp.mean(jnp.abs(preds))

    grads = jax.grad(loss_fn)(variables["params"])
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in leaves)
    # the fnet and update block must receive gradient signal
    total = sum(float(jnp.sum(jnp.abs(g))) for g in leaves)
    assert total > 0


def test_sequential_fnet_matches_batched():
    """The full-res sequential-fnet path (peak-HBM halving) is numerically
    identical to the batched concat path."""
    import dataclasses

    cfg = RaftStereoConfig(n_gru_layers=1, hidden_dims=(32,), corr_levels=2,
                           fnet_dim=32)
    model = RAFTStereo(cfg)
    rng = np.random.default_rng(3)
    img1 = jnp.asarray(rng.uniform(0, 255, (1, 32, 48, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (1, 32, 48, 3)), jnp.float32)
    v = model.init(jax.random.PRNGKey(0), img1, img2, iters=1, test_mode=True)

    _, up_batched = model.apply(v, img1, img2, iters=2, test_mode=True)
    cfg_seq = dataclasses.replace(cfg, sequential_fnet_pixels=0)
    _, up_seq = RAFTStereo(cfg_seq).apply(v, img1, img2, iters=2,
                                          test_mode=True)
    # batch-2 vs batch-1 convolutions reassociate differently (~1e-6 on the
    # feature maps), and the untrained GRU amplifies ~5x/iteration — same
    # drift scale as the sharded-model comparison (test_parallel).
    np.testing.assert_allclose(np.asarray(up_seq), np.asarray(up_batched),
                               rtol=1e-3, atol=1e-3)


def test_fullres_gates_are_memory_derived(monkeypatch):
    """Path-selection pins (VERDICT round 2 weak #5): the sequential-fnet
    threshold and banded band height derive from device HBM, scale with it,
    and respect their config overrides."""
    from raft_stereo_tpu.models import banded
    from raft_stereo_tpu.models.raft_stereo import sequential_fnet_threshold

    cfg = RaftStereoConfig()
    # CPU backend reports no bytes_limit -> 16 GiB fallback: the derived
    # threshold must keep KITTI/SceneFlow batched and Middlebury-F-class
    # frames sequential (the round-2 proven split).
    thr = sequential_fnet_threshold(cfg)
    assert 544 * 960 < thr <= 1088 * 1984, thr
    # Explicit override wins, including the force-sequential 0.
    import dataclasses
    assert sequential_fnet_threshold(
        dataclasses.replace(cfg, sequential_fnet_pixels=0)) == 0
    assert sequential_fnet_threshold(
        dataclasses.replace(cfg, sequential_fnet_pixels=7)) == 7

    # Threshold scales linearly with HBM capacity.
    import raft_stereo_tpu.profiling as prof
    monkeypatch.setattr(prof, "device_memory_stats",
                        lambda: {"bytes_limit": 32 * 2 ** 30})
    assert abs(sequential_fnet_threshold(cfg) - 2 * thr) <= 2

    # Band height: even, clamped, wider images get shorter bands.
    monkeypatch.setattr(prof, "device_memory_stats", lambda: {})
    b_narrow = banded.default_band_rows(1, 1984)
    b_wide = banded.default_band_rows(1, 4608)
    assert b_narrow % 2 == 0 and b_wide % 2 == 0
    assert banded._BAND_MIN <= b_wide <= b_narrow <= banded._BAND_MAX
    # At the round-2 measurement shape the derivation reproduces the band
    # that carried the round-2 measurements within a factor of ~2.
    assert 128 <= banded.default_band_rows(1, 2880) <= 512
