"""Fused no-volume alt kernel (kernels/corr_alt.py) vs the XLA alt backend.

Runs the kernel in interpreter mode on CPU — the same program the TPU
compiles.  The XLA path (feature sampling + einsum) is the semantics
reference; the kernel must match it in values and feature gradients
(coords gradients are intentionally zero — RAFT detaches coords).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

slow = pytest.mark.slow  # full-model / subprocess-scale tests

from raft_stereo_tpu.config import RaftStereoConfig
from raft_stereo_tpu.kernels import corr_alt, corr_lookup
from raft_stereo_tpu.models.corr import make_corr_fn_alt


@pytest.fixture
def _interpret_mode():
    corr_lookup._interpret_override = True
    yield
    corr_lookup._interpret_override = None


def _said_since(before):
    """The launch choices ``log_path_once`` was told since ``before``
    (``corr_lookup.path_choices()`` then)."""
    return [m for m, n in corr_lookup.path_choices().items()
            if n > before.get(m, 0)]


def _xla_alt(cfg, f1, f2):
    """The REAL pure-XLA alt fallback in make_corr_fn_alt, reached by
    forcing the fused dispatch off."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(corr_alt, "alt_fused_available", lambda: False)
        return make_corr_fn_alt(cfg, f1, f2)


@slow
@pytest.mark.parametrize("w2", [40, 37])
def test_alt_fused_matches_xla(rng, _interpret_mode, w2):
    cfg = RaftStereoConfig(corr_backend="alt")
    b, h, w1, d = 1, 4, 24, 16
    f1 = jnp.asarray(rng.standard_normal((b, h, w1, d)), jnp.float32)
    f2 = jnp.asarray(rng.standard_normal((b, h, w2, d)), jnp.float32)
    coords = jnp.asarray(rng.uniform(-3, w2 + 3, (b, h, w1)), jnp.float32)

    ref = _xla_alt(cfg, f1, f2)(coords)
    fused = make_corr_fn_alt(cfg, f1, f2)(coords)  # dispatches to the kernel
    assert fused.shape == ref.shape
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@slow
def test_alt_fused_gradients_match_xla(rng, _interpret_mode):
    cfg = RaftStereoConfig(corr_backend="alt", corr_levels=2)
    b, h, w1, w2, d = 1, 3, 16, 24, 8
    f1 = jnp.asarray(rng.standard_normal((b, h, w1, d)), jnp.float32)
    f2 = jnp.asarray(rng.standard_normal((b, h, w2, d)), jnp.float32)
    coords = jnp.asarray(rng.uniform(0, w2, (b, h, w1)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal(
        (b, h, w1, cfg.corr_levels * (2 * cfg.corr_radius + 1))), jnp.float32)

    def loss_ref(f1_, f2_):
        return jnp.sum(_xla_alt(cfg, f1_, f2_)(coords) * cot)

    def loss_fused(f1_, f2_):
        return jnp.sum(make_corr_fn_alt(cfg, f1_, f2_)(coords) * cot)

    g_ref = jax.grad(loss_ref, argnums=(0, 1))(f1, f2)
    g_fused = jax.grad(loss_fused, argnums=(0, 1))(f1, f2)
    for a, b_ in zip(g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


@slow
def test_alt_per_level_fallback_matches_multi(rng, _interpret_mode,
                                              monkeypatch):
    """The per-level launch path (taken at full resolution, over the VMEM
    budget) must agree with the single-launch multi-level path."""
    cfg = RaftStereoConfig(corr_backend="alt")
    b, h, w1, w2, d = 1, 4, 24, 40, 16
    f1 = jnp.asarray(rng.standard_normal((b, h, w1, d)), jnp.float32)
    f2 = jnp.asarray(rng.standard_normal((b, h, w2, d)), jnp.float32)
    coords = jnp.asarray(rng.uniform(-3, w2 + 3, (b, h, w1)), jnp.float32)

    multi = make_corr_fn_alt(cfg, f1, f2)(coords)
    # A budget big enough for 1-row blocks (alt_fused_fits stays True, the
    # kernel stays engaged) but far below the multi launch's working set ->
    # forces the per-level launch path specifically.
    monkeypatch.setattr(corr_alt, "VMEM_BUDGET", 200_000)
    monkeypatch.setattr(corr_lookup, "VMEM_BUDGET", 200_000)
    # ... and the launch plan's own gate, which is what picks the path
    monkeypatch.setattr(corr_alt, "_MOSAIC_SCOPED_VMEM", 0)
    before = corr_lookup.path_choices()
    per_level = make_corr_fn_alt(cfg, f1, f2)(coords)
    said = _said_since(before)
    assert said and all("one launch per level" in m for m in said), said
    np.testing.assert_array_equal(np.asarray(multi), np.asarray(per_level))

    # gradients through the per-level path too
    cot = jnp.asarray(rng.standard_normal(multi.shape), jnp.float32)
    g1 = jax.grad(lambda a: jnp.sum(make_corr_fn_alt(cfg, a, f2)(coords)
                                    * cot))(f1)
    monkeypatch.undo()
    g2 = jax.grad(lambda a: jnp.sum(make_corr_fn_alt(cfg, a, f2)(coords)
                                    * cot))(f1)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-6, atol=1e-6)


@slow
def test_alt_fused_model_forward(rng, _interpret_mode):
    """Whole model with the alt backend routes through the fused kernel in
    interpret mode and stays finite."""
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    cfg = RaftStereoConfig(corr_backend="alt", n_gru_layers=1,
                           hidden_dims=(32,), fnet_dim=64)
    model = RAFTStereo(cfg)
    img1 = jnp.asarray(rng.uniform(0, 255, (1, 32, 64, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (1, 32, 64, 3)), jnp.float32)
    v = model.init(jax.random.PRNGKey(0), img1, img2, iters=1, test_mode=True)
    lo, up = model.apply(v, img1, img2, iters=2, test_mode=True)
    assert up.shape == (1, 32, 64)
    assert np.isfinite(np.asarray(up)).all()


@slow
def test_multi_alt_gate_tracks_mosaic_stack():
    """The single-launch multi-level gate holds the launch PLAN where the
    body before PR 29 put it: that body kept every level's (R, W1B, W2)
    tile, hat field and product live at once, the 544x960 fp32 accuracy
    shape (wcat=450, d=256) measured 18.11 MiB scoped and FAILED to
    compile, so the gate routes it per-level; the realtime KITTI shape
    (bf16, wcat=292) must stay on the fast multi path.  Today's body works
    a row at a time: its live set is the double-buffered feature, centre
    and result blocks plus ONE row's upcast features, transposed
    (W2 padded to 8, W1B) tiles and taps (by the v5e compiler 3.7-4.0
    MiB at the realtime shape and 6.7-7.0 MiB at 544x960 fp32, which
    would compile as one launch); PR 29 left the plan alone and PERF.md
    section 7 names the move of the gate."""
    from raft_stereo_tpu.kernels.corr_alt import (_MOSAIC_SCOPED_VMEM,
                                                  _multi_alt_scoped_bytes)

    full_fp32 = _multi_alt_scoped_bytes([240, 120, 60, 30], 256, 4, 4)
    assert full_fp32 > _MOSAIC_SCOPED_VMEM, full_fp32
    realtime_bf16 = _multi_alt_scoped_bytes([156, 78, 39, 19], 256, 2, 4)
    assert realtime_bf16 <= _MOSAIC_SCOPED_VMEM, realtime_bf16


# ------------------------------------------- the sublane sampler (PR 29)
RADIUS = 4
W2S = [19, 37, 40, 156]    # none a whole number of sublane tiles but 40


def _centres(rng, shape, w2, radius=RADIUS):
    """Centres over [-radius - 3, w2 + radius + 3]: both ends of that
    range, both borders of the row, and every seventh exactly on an
    integer."""
    c = rng.uniform(-radius - 3, w2 + radius + 3, shape).astype(np.float32)
    c[..., 0], c[..., 1] = 0.0, w2 - 1.0
    c[..., 2], c[..., 3] = -radius - 3.0, w2 + radius + 3.0
    c[..., 4::7] = np.round(c[..., 4::7])
    return jnp.asarray(c)


def _pyramid(rng, shape, levels, dtype):
    """Right features pooled along W in float32, then cast: every path
    under test reads the SAME numbers."""
    f2s = [np.asarray(rng.standard_normal(shape), np.float32)]
    for _ in range(levels - 1):
        x = f2s[-1]
        n = x.shape[2] // 2
        f2s.append((x[:, :, 0:2 * n:2] + x[:, :, 1:2 * n:2]) / 2)
    return [jnp.asarray(x).astype(dtype) for x in f2s]


def _xla_levels(f1, f2s, coords):
    """The XLA no-volume sampler, one level at a time, in float32 on the
    given pyramid."""
    cfg1 = RaftStereoConfig(corr_backend="alt", corr_levels=1)
    return jnp.concatenate(
        [_xla_alt(cfg1, f1.astype(jnp.float32), f2.astype(jnp.float32))(
            coords / 2 ** i) for i, f2 in enumerate(f2s)], axis=-1)


@pytest.mark.parametrize("w2", W2S)
def test_sublane_sample_matches_hat_sample(rng, w2):
    """The kernel's sampler on a transposed tile against
    ``corr_lookup.hat_sample`` on the same tile: bins outside [0, w2)
    count as zero in both, whatever the padding rows hold."""
    rows, w1b = 3, 128
    v = jnp.asarray(rng.standard_normal((rows, w1b, w2)), jnp.float32)
    centres = _centres(rng, (rows, w1b), w2)
    ref = jnp.stack([s for _, s in corr_lookup.hat_sample(v, centres,
                                                          RADIUS)], axis=1)
    vt = jnp.pad(jnp.swapaxes(v, 1, 2),
                 ((0, 0), (0, -w2 % corr_alt.SUBLANES), (0, 0)),
                 constant_values=np.nan)
    got = jnp.stack([jnp.concatenate(corr_alt.sublane_sample(
        vt[r], centres[r:r + 1], RADIUS, w2), axis=0) for r in range(rows)])
    assert got.shape == ref.shape == (rows, 2 * RADIUS + 1, w1b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# bfloat16: the kernel's result is rounded to 8 bits of mantissa
TOL = {"float32": 1e-5, "bfloat16": 1e-2}


@pytest.mark.parametrize("launch", ["all-levels", "per-level"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("w2", W2S)
def test_alt_kernel_matches_xla_sampler(rng, _interpret_mode, monkeypatch,
                                        w2, dtype, launch):
    """Both forward launches against the XLA sampler, W1 over two tiles
    with the second ragged, centres outside the row and on its borders."""
    b, h, w1, d = 1, 3, 150, 8
    levels = 4 if w2 < 100 else 2
    f1 = jnp.asarray(rng.standard_normal((b, h, w1, d)),
                     jnp.float32).astype(dtype)
    f2s = _pyramid(rng, (b, h, w2, d), levels, dtype)
    coords = _centres(rng, (b, h, w1), w2)
    if launch == "per-level":
        monkeypatch.setattr(corr_alt, "_MOSAIC_SCOPED_VMEM", 0)
    before = corr_lookup.path_choices()
    got = corr_alt.alt_lookup_fused(f1, f2s, coords, RADIUS)
    said = _said_since(before)
    assert len(said) == 1 and (("single all-levels launch" in said[0])
                               == (launch == "all-levels")), said
    assert got.dtype == jnp.dtype(dtype)
    assert got.shape == (b, h, w1, levels * (2 * RADIUS + 1))
    ref = _xla_levels(f1, f2s, coords)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref),
                               rtol=TOL[dtype], atol=TOL[dtype])


@pytest.mark.parametrize("launch", ["all-levels", "per-level"])
def test_alt_q_kernel_matches_xla_sampler(rng, _interpret_mode, monkeypatch,
                                          launch):
    """The quantized entry shares the forward bodies: int8 features in,
    raw correlations of the grid values out in ``out_dtype``."""
    b, h, w1, w2, d = 1, 3, 150, 37, 8
    f1 = jnp.asarray(rng.integers(-8, 9, (b, h, w1, d)), jnp.int8)
    f2s = [jnp.asarray(rng.integers(-8, 9, (b, h, w, d)), jnp.int8)
           for w in (w2, w2 // 2, w2 // 4)]
    coords = _centres(rng, (b, h, w1), w2)
    if launch == "per-level":
        monkeypatch.setattr(corr_alt, "_MOSAIC_SCOPED_VMEM", 0)
    got = corr_alt.alt_lookup_fused_q(f1, f2s, coords, RADIUS,
                                      out_dtype=jnp.float32)
    assert got.dtype == jnp.float32
    ref = _xla_levels(f1, f2s, coords)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5 * float(
                                   jnp.max(jnp.abs(ref))))
