"""Pallas fused corr lookup vs the XLA reference implementation.

Runs the kernel in interpreter mode (CPU) — same code path the TPU compiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu.kernels import corr_lookup
from raft_stereo_tpu.models.corr import (build_corr_pyramid,
                                         lookup_pyramid_xla)


@pytest.fixture(autouse=True)
def _interpret_mode():
    corr_lookup._interpret_override = True
    yield
    corr_lookup._interpret_override = None


def _pyramid(rng, b=2, h=6, w=40, levels=3):
    vol = jnp.asarray(rng.normal(size=(b, h, w, w)).astype(np.float32))
    return build_corr_pyramid(vol, levels)


def test_fused_matches_xla_forward(rng):
    pyr = _pyramid(rng)
    b, h, w, _ = pyr[0].shape
    coords = jnp.asarray(
        rng.uniform(-3, w + 3, size=(b, h, w)).astype(np.float32))
    fused = corr_lookup.lookup_pyramid_fused(pyr, coords, radius=4)
    ref = lookup_pyramid_xla(pyr, coords, radius=4)
    assert fused.shape == ref.shape
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_fused_matches_xla_gradient(rng):
    pyr = _pyramid(rng, b=1, h=4, w=32, levels=2)
    b, h, w, _ = pyr[0].shape
    coords = jnp.asarray(
        rng.uniform(0, w, size=(b, h, w)).astype(np.float32))
    probe = jnp.asarray(rng.normal(size=(b, h, w, 2 * 9)).astype(np.float32))

    def loss_fused(vol):
        out = corr_lookup.lookup_pyramid_fused(
            build_corr_pyramid(vol, 2), coords, radius=4)
        return jnp.sum(out * probe)

    def loss_xla(vol):
        out = lookup_pyramid_xla(build_corr_pyramid(vol, 2), coords, radius=4)
        return jnp.sum(out * probe)

    vol0 = pyr[0]
    g_fused = jax.grad(loss_fused)(vol0)
    g_xla = jax.grad(loss_xla)(vol0)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_xla),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_fused_keeps_bf16(rng):
    pyr = [p.astype(jnp.bfloat16) for p in _pyramid(rng, levels=2)]
    b, h, w, _ = pyr[0].shape
    coords = jnp.asarray(rng.uniform(0, w, size=(b, h, w)).astype(np.float32))
    out = corr_lookup.lookup_pyramid_fused(pyr, coords, radius=4)
    assert out.dtype == jnp.bfloat16
    ref = lookup_pyramid_xla([p.astype(jnp.float32) for p in pyr], coords, 4)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), atol=0.15)


def test_fused_zero_padding(rng):
    """Far out-of-range centers sample all-zero windows."""
    pyr = _pyramid(rng, b=1, h=4, w=24, levels=1)
    b, h, w, _ = pyr[0].shape
    coords = jnp.full((b, h, w), -100.0)
    out = corr_lookup.lookup_pyramid_fused(pyr, coords, radius=4)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@pytest.mark.slow
def test_model_runs_with_fused_backend(rng):
    """End-to-end: reg_fused backend through the full model (interpret)."""
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    cfg = RaftStereoConfig(hidden_dims=(32, 32, 32), fnet_dim=64,
                           corr_backend="reg_fused")
    model = RAFTStereo(cfg)
    img1 = jnp.asarray(rng.uniform(0, 255, (1, 32, 64, 3)).astype(np.float32))
    img2 = jnp.asarray(rng.uniform(0, 255, (1, 32, 64, 3)).astype(np.float32))
    variables = model.init(jax.random.PRNGKey(0), img1, img2, iters=1,
                           test_mode=True)
    low, up = model.apply(variables, img1, img2, iters=2, test_mode=True)
    assert np.isfinite(np.asarray(up)).all()

    # and the reg backend agrees (same weights, different lookup path)
    cfg_reg = RaftStereoConfig(hidden_dims=(32, 32, 32), fnet_dim=64,
                               corr_backend="reg")
    low2, up2 = RAFTStereo(cfg_reg).apply(variables, img1, img2, iters=2,
                                          test_mode=True)
    np.testing.assert_allclose(np.asarray(up), np.asarray(up2), atol=1e-3)


# ------------------------------------------- the backward's own VMEM check
def test_multi_backward_estimate_matches_the_v5e_compiler():
    """The calibration points in ``_multi_bwd_scoped_bytes``'s docstring:
    what Mosaic reported for the v5e (tests/test_v5e_compile.py compiles
    the two SceneFlow cases for real) against the 16 MiB scoped limit."""
    limit = 2 * corr_lookup.VMEM_BUDGET
    est = corr_lookup._multi_bwd_scoped_bytes
    assert est([180, 90, 45, 22], 4, 4) > limit       # refused: 16.32 MiB
    assert est([180, 90, 45, 22], 4, 2) <= limit      # compiles
    assert est([312, 156, 78, 39], 4, 2) > limit      # refused: 18.67 MiB
    assert est([128, 64, 32, 16], 4, 4) <= limit      # compiles
    # never below what the compiler reported at the refused points
    assert est([180, 90, 45, 22], 4, 4) >= 16.32 * 2 ** 20
    assert est([312, 156, 78, 39], 4, 4) >= 22.42 * 2 ** 20


def test_backward_falls_to_per_level_launches_with_same_gradient(
        rng, monkeypatch):
    """When the all-levels backward would not fit, the forward keeps its
    single launch and the backward runs one launch per level: the
    gradient is the same one."""
    pyr = _pyramid(rng, b=1, h=4, w=32, levels=3)
    b, h, w, _ = pyr[0].shape
    coords = jnp.asarray(
        rng.uniform(0, w, size=(b, h, w)).astype(np.float32))
    probe = jnp.asarray(rng.normal(size=(b, h, w, 3 * 9)).astype(np.float32))

    def grads():
        return jax.grad(lambda p: jnp.sum(
            corr_lookup.lookup_pyramid_fused(p, coords, radius=4) * probe)
        )(pyr)

    launched = []
    real = corr_lookup._launch_bwd
    monkeypatch.setattr(corr_lookup, "_launch_bwd",
                        lambda *a, **k: launched.append(1) or real(*a, **k))
    single = grads()
    assert not launched
    monkeypatch.setattr(corr_lookup, "_multi_bwd_scoped_bytes",
                        lambda *a: 10 ** 12)
    per_level = grads()
    assert len(launched) == 3
    for a, b_ in zip(single, per_level):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_path_choice_is_logged_once(rng, caplog):
    import logging

    corr_lookup._path_choices.clear()
    pyr = _pyramid(rng, b=1, h=4, w=24, levels=2)
    coords = jnp.zeros(pyr[0].shape[:3], jnp.float32)
    with caplog.at_level(logging.INFO, logger=corr_lookup.__name__):
        for _ in range(3):
            corr_lookup.lookup_pyramid_fused(pyr, coords, radius=4)
    lines = [r.getMessage() for r in caplog.records
             if "kernel path: lookup W2=24/12 float32" in r.getMessage()]
    assert len(lines) == 1 and "single all-levels launch" in lines[0]


def test_gru_auto_fallback_says_why(caplog):
    """fused_gru="auto" at the accuracy arch's finest level, KITTI width:
    the working set has no W-blocking and does not fit — the Flax path is
    taken and the reason logged (it used to be silent)."""
    import logging

    from raft_stereo_tpu.kernels import gru_fused

    corr_lookup._path_choices.clear()
    with caplog.at_level(logging.INFO, logger=corr_lookup.__name__):
        assert not gru_fused.gru_fused_should_use(
            "auto", kernel_size=3, w=312, cin=384, ch=128, itemsize=2)
        assert gru_fused.gru_fused_should_use(
            "auto", kernel_size=3, w=78, cin=256, ch=128, itemsize=4)
    text = "\n".join(r.getMessage() for r in caplog.records)
    assert "ConvGRU level W=312 Cin=384 Ch=128 16-bit: flax" in text
    assert "does not block along W" in text
    assert "ConvGRU level W=78 Cin=256 Ch=128 32-bit: kernel" in text
