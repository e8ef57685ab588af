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


def _pyramid(rng, b=2, h=6, w=40, levels=3, w2=None, dtype=jnp.float32):
    """The (B,H,W1,W2_i) levels, pooled in float32 and then cast, so that
    every path under test reads the SAME numbers."""
    vol = jnp.asarray(rng.normal(size=(b, h, w, w2 or w)).astype(np.float32))
    return [p.astype(dtype) for p in build_corr_pyramid(vol, levels)]


def _t(pyr):
    """The layout the kernel reads: level i as (B,H,W2_i,W1)."""
    return [jnp.swapaxes(p, -1, -2) for p in pyr]


def _f32(pyr):
    return [p.astype(jnp.float32) for p in pyr]


# W1 no whole number of 128 lanes; level widths no whole number of sublane
# tiles (39; 45 and 22), as the two cells that run the kernel have them
SHAPES = {"kitti": dict(w=312, levels=4),          # W2 312/156/78/39
          "sceneflow": dict(w=180, levels=4)}      # W2 180/90/45/22
# bfloat16: the kernel's result is rounded to 8 bits of mantissa
TOL = {"float32": dict(rtol=1e-4, atol=1e-4),
       "bfloat16": dict(rtol=1e-2, atol=3e-2)}


@pytest.fixture
def plan(request, monkeypatch):
    """``all_levels``: the budget as it is (one launch at these widths);
    ``per_level``: a budget under which every level takes its own launch
    with its row block shrunk (8 rows of the widest level do not fit)."""
    if request.param == "per_level":
        monkeypatch.setattr(corr_lookup, "VMEM_BUDGET", 400_000)
    return request.param


def _said(what):
    return [m for m in corr_lookup.path_choices() if m.startswith(what)]


PLANS = pytest.mark.parametrize("plan", ["all_levels", "per_level"],
                                indirect=True)
CASES = pytest.mark.parametrize("shape", list(SHAPES))
DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                 ids=["float32", "bfloat16"])


@PLANS
@DTYPES
@CASES
def test_fused_matches_xla_forward(rng, shape, dtype, plan):
    corr_lookup._path_choices.clear()
    pyr = _pyramid(rng, b=1, h=3, dtype=dtype, **SHAPES[shape])
    b, h, w, _ = pyr[0].shape
    coords = jnp.asarray(
        rng.uniform(-6, w + 6, size=(b, h, w)).astype(np.float32))
    fused = corr_lookup.lookup_pyramid_fused(_t(pyr), coords, radius=4)
    ref = lookup_pyramid_xla(_f32(pyr), coords, radius=4)
    assert fused.shape == ref.shape and fused.dtype == dtype
    np.testing.assert_allclose(np.asarray(fused, np.float32),
                               np.asarray(ref), **TOL[jnp.dtype(dtype).name])
    (said,) = _said("transposed-volume lookup W2=")
    assert ("single all-levels launch" in said) == (plan == "all_levels")


@PLANS
@DTYPES
@CASES
def test_fused_matches_xla_gradient(rng, shape, dtype, plan):
    """The backward kernel against ``jax.grad`` of the XLA sampler: the
    cotangent of every level of the TRANSPOSED pyramid, in its dtype."""
    corr_lookup._path_choices.clear()
    pyr = _pyramid(rng, b=1, h=2, dtype=dtype, **SHAPES[shape])
    b, h, w, _ = pyr[0].shape
    coords = jnp.asarray(
        rng.uniform(-6, w + 6, size=(b, h, w)).astype(np.float32))
    probe = jnp.asarray(
        rng.normal(size=(b, h, w, len(pyr) * 9)).astype(np.float32))

    g_fused = jax.grad(lambda p: jnp.sum(corr_lookup.lookup_pyramid_fused(
        p, coords, radius=4).astype(jnp.float32) * probe))(_t(pyr))
    g_xla = jax.grad(lambda p: jnp.sum(
        lookup_pyramid_xla(p, coords, radius=4) * probe))(_f32(pyr))
    for got, want in zip(g_fused, g_xla):
        assert got.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(jnp.swapaxes(got, -1, -2), np.float32),
            np.asarray(want), **TOL[jnp.dtype(dtype).name])
    (said,) = _said("transposed-volume lookup backward W2=")
    assert ("single all-levels launch" in said) == (plan == "all_levels")


def test_fused_zero_padding(rng):
    """Far out-of-range centers sample all-zero windows."""
    pyr = _pyramid(rng, b=1, h=4, w=24, levels=1)
    b, h, w, _ = pyr[0].shape
    coords = jnp.full((b, h, w), -100.0)
    out = corr_lookup.lookup_pyramid_fused(_t(pyr), coords, radius=4)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@pytest.mark.parametrize("w2", [19, 37, 40, 156])
def test_sublane_scatter_matches_hat_scatter(rng, w2):
    """The backward's scatter on a transposed tile against the plain
    ``hat_scatter`` on the same cotangent, centres beyond both borders and
    on whole bins included."""
    rows, w1b, radius = 3, 128, 4
    k = 2 * radius + 1
    g = jnp.asarray(rng.standard_normal((rows, w1b, k)), jnp.float32)
    c = rng.uniform(-radius - 3, w2 + radius + 3, (rows, w1b))
    c[:, 0], c[:, 1], c[:, 2], c[:, 3] = 0.0, w2 - 1.0, -7.0, w2 + 7.0
    c[:, 4::7] = np.round(c[:, 4::7])
    c = jnp.asarray(c, jnp.float32)
    ref = corr_lookup.hat_scatter(g, c, w2, radius)          # (R, W1B, W2)
    w2p = corr_lookup.w2_rows(w2, 4)
    got = jnp.stack([corr_lookup.sublane_scatter(
        [g[r, :, t][None] for t in range(k)], c[r:r + 1], radius, w2, w2p)
        for r in range(rows)])                               # (R, W2p, W1B)
    np.testing.assert_allclose(np.asarray(got[:, :w2]),
                               np.asarray(jnp.swapaxes(ref, 1, 2)),
                               rtol=1e-5, atol=1e-5)


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


# --------------------------------------------------- the launch plan's gate
def test_program_bytes_match_the_v5e_compiler():
    """The calibration points in ``_program_bytes``'s docstring: what the
    v5e compiler needed, by bisection of ``vmem_limit_bytes``
    (tests/test_v5e_compile.py compiles three of them for real), against
    twice the estimate: never under the need, and within a quarter."""
    mib = 2 ** 20

    def scoped(w2s, itemsize, backward, tap_itemsize=None):
        per_row, fixed = corr_lookup._program_bytes(
            w2s, 4, itemsize, tap_itemsize or itemsize, backward)
        return 2 * (corr_lookup.ROW_BLK * per_row + fixed) / mib

    kitti, crop, wide = ([312, 156, 78, 39], [180, 90, 45, 22],
                         [720, 360, 180, 90])
    for w2s, itemsize, backward, need, tap in [
            (kitti, 4, False, 5.21, None), (kitti, 2, False, 2.73, None),
            (kitti, 1, False, 1.74, 4), (crop, 4, False, 3.23, None),
            (crop, 2, False, 1.74, None), (wide, 4, False, 11.16, None),
            (kitti, 4, True, 5.46, None), (kitti, 2, True, 2.98, None),
            (crop, 4, True, 3.23, None), (crop, 2, True, 1.74, None)]:
        est = scoped(w2s, itemsize, backward, tap)
        assert need - 0.25 <= est <= 1.25 * need, (w2s, itemsize, backward)
    # every shape a cell runs is ONE launch, forward and backward; Mosaic's
    # default scoped limit is twice the budget
    assert corr_lookup._single_launch(kitti, 4, 4, 4)
    assert corr_lookup._single_launch(crop, 4, 2, 2, backward=True)
    assert not corr_lookup._single_launch([1440, 720, 360, 180], 4, 4, 4)


def test_backward_falls_to_per_level_launches_with_same_gradient(
        rng, monkeypatch):
    """When the all-levels backward would not fit, the forward keeps its
    single launch and the backward runs one launch per level: the
    gradient is the same one."""
    pyr = _t(_pyramid(rng, b=1, h=4, w=32, levels=3))
    b, h, _, w = pyr[0].shape
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
    assert len(launched) == 1
    fits = corr_lookup._single_launch
    monkeypatch.setattr(
        corr_lookup, "_single_launch",
        lambda *a, backward=False: not backward and fits(*a))
    per_level = grads()
    assert len(launched) == 1 + 3
    for a, b_ in zip(single, per_level):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_path_choice_is_logged_once(rng, caplog):
    import logging

    corr_lookup._path_choices.clear()
    pyr = _pyramid(rng, b=1, h=4, w=24, levels=2)
    coords = jnp.zeros(pyr[0].shape[:3], jnp.float32)
    with caplog.at_level(logging.INFO, logger=corr_lookup.__name__):
        for _ in range(3):
            corr_lookup.lookup_pyramid_fused(_t(pyr), coords, radius=4)
    lines = [r.getMessage() for r in caplog.records
             if "kernel path: transposed-volume lookup W2=24/12 float32"
             in r.getMessage()]
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
