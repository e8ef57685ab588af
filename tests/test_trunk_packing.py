"""The trunk's 64-channel stage run two images a lane row
(models/extractor.py ``packs_two_images``) against the plain stage: the
same answers and gradients up to float32 accumulation order, and the same
parameter tree."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu.kernels import corr_lookup
from raft_stereo_tpu.models import extractor
from raft_stereo_tpu.models.extractor import ResidualBlock, _Trunk, conv
from raft_stereo_tpu.models.norm import apply_norm, make_norm

NORMS = ["batch", "instance", "none", "group"]
H, W = 32, 48


class _PlainTrunk(nn.Module):
    """The trunk as it was written before its stage packed: the parameter
    tree a checkpoint of that time holds."""

    norm_fn: str
    downsample: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = conv(64, 7, 1 + (self.downsample > 2), dtype=self.dtype,
                 name="conv1")(x)
        x = nn.relu(apply_norm(make_norm(self.norm_fn, 64, self.dtype,
                                         "norm1"), x))
        for i, (dim, stride) in enumerate(
                [(64, 1), (96, 1 + (self.downsample > 1)),
                 (128, 1 + (self.downsample > 0))], start=1):
            x = ResidualBlock(dim, self.norm_fn, stride, dtype=self.dtype,
                              name=f"layer{i}_0")(x)
            x = ResidualBlock(dim, self.norm_fn, 1, dtype=self.dtype,
                              name=f"layer{i}_1")(x)
        return x


def _variables(trunk, x, rng):
    """Initialised variables with every per-channel vector drawn anew, so
    that tiling a wrong scale, shift or statistic shows."""
    v = trunk.init(jax.random.PRNGKey(0), x)

    def draw(path, leaf):
        if leaf.ndim != 1:
            return leaf
        name = jax.tree_util.keystr(path)
        lo = 0.5 if ("var" in name or "scale" in name) else -0.5
        return jnp.asarray(rng.uniform(lo, lo + 1.0, leaf.shape), leaf.dtype)
    return jax.tree_util.tree_map_with_path(draw, v)


def _images(rng, n):
    return jnp.asarray(rng.uniform(-1, 1, (n, H, W, 3)), jnp.float32)


def _said_since(before):
    now = corr_lookup.path_choices()
    return [m for m, k in now.items() if k > before.get(m, 0)
            and m.startswith("trunk stage")]


def _plain(monkeypatch, fn):
    with monkeypatch.context() as m:
        m.setattr(extractor, "LANE_BATCH_IMAGES", 0)
        return fn()


def _close(got, want, scale, rtol=1e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rtol,
                               atol=rtol * scale)


@pytest.mark.parametrize("n", [1, 2, 3, 4])
@pytest.mark.parametrize("downsample", [2, 3], ids=["stride1", "stride2"])
@pytest.mark.parametrize("norm_fn", NORMS)
def test_packed_stage_matches_plain(monkeypatch, rng, norm_fn, downsample, n):
    trunk = _Trunk(norm_fn, downsample, jnp.float32)
    x = _images(rng, n)
    v = _variables(trunk, x, rng)
    before = corr_lookup.path_choices()
    got = trunk.apply(v, x)
    (said,) = _said_since(before)
    packed = n % 2 == 0
    stage = f"{H // (1 + (downsample > 2))}x{W // (1 + (downsample > 2))}"
    assert said == (f"trunk stage {stage} x{n} images: "
                    + ("two images a lane row" if packed
                       else "plain (an odd count)")), said
    want = _plain(monkeypatch, lambda: trunk.apply(v, x))
    assert got.shape == want.shape
    _close(got, want, float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("downsample", [2, 3], ids=["stride1", "stride2"])
@pytest.mark.parametrize("norm_fn", NORMS)
def test_packed_stage_gradients_match_plain(monkeypatch, rng, norm_fn,
                                            downsample):
    """``jax.grad`` with respect to every parameter and the images: the
    block-diagonal kernels and tiled vectors carry each half's gradient back
    to the one stored 64-channel leaf."""
    trunk = _Trunk(norm_fn, downsample, jnp.float32)
    x = _images(rng, 4)
    v = _variables(trunk, x, rng)
    probe = jnp.asarray(rng.standard_normal(
        jax.eval_shape(lambda: trunk.apply(v, x)).shape), jnp.float32)

    def grads():
        def loss(p, x):
            return jnp.sum(trunk.apply({**v, "params": p}, x) * probe)
        return jax.grad(loss, argnums=(0, 1))(v["params"], x)

    got = grads()
    want = _plain(monkeypatch, grads)
    # a leaf that is ~0 by construction (a bias before instance norm) holds
    # rounding noise on the scale of the whole gradient, not its own
    scale = max(float(jnp.max(jnp.abs(g))) for g in jax.tree_util.tree_leaves(
        want))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        _close(g, w, scale, rtol=1e-4)


@pytest.mark.parametrize("norm_fn", NORMS)
def test_init_tree_is_the_plain_one(rng, norm_fn):
    """Names, shapes and the initial values themselves: a checkpoint written
    before the stage packed restores into this trunk."""
    x = _images(rng, 2)
    new = _Trunk(norm_fn, 3, jnp.float32).init(jax.random.PRNGKey(7), x)
    old = _PlainTrunk(norm_fn, 3).init(jax.random.PRNGKey(7), x)
    assert (jax.tree_util.tree_structure(new)
            == jax.tree_util.tree_structure(old))
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_of_plain_trunk_answers_the_same(tmp_path, rng):
    """A model checkpoint saved from the plain trunk's tree, loaded and run
    at a batch that packs, answers as the plain trunk does."""
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo
    from raft_stereo_tpu.training.checkpoint import load_weights, save_weights

    cfg = RaftStereoConfig(hidden_dims=(32, 32, 32), fnet_dim=64,
                           corr_backend="reg")
    img1 = jnp.asarray(rng.uniform(0, 255, (2, 64, 96, 3)), jnp.float32)
    img2 = jnp.roll(img1, 4, axis=2)
    v = RAFTStereo(cfg).init(jax.random.PRNGKey(0), img1, img2, iters=1,
                             test_mode=True)
    save_weights(str(tmp_path / "w"), cfg, v["params"],
                 batch_stats=v["batch_stats"])
    cfg2, loaded = load_weights(str(tmp_path / "w"))

    def answer():
        return RAFTStereo(cfg2).apply(loaded, img1, img2, iters=2,
                                      test_mode=True)[1]

    before = corr_lookup.path_choices()
    got = answer()
    assert "trunk stage 64x96 x4 images: two images a lane row" in \
        _said_since(before)                                  # fnet
    with pytest.MonkeyPatch.context() as m:
        m.setattr(extractor, "LANE_BATCH_IMAGES", 0)
        want = answer()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_training_forward_packs_by_the_image_count(monkeypatch, rng):
    """The training forward (``test_mode`` false) takes the stage's choice
    by the image count as inference does, and each iteration's prediction
    is the plain stage's up to accumulation order (the trunk's gradients:
    ``test_packed_stage_gradients_match_plain``)."""
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    cfg = RaftStereoConfig(hidden_dims=(32, 32, 32), fnet_dim=64,
                           corr_backend="reg")
    img1 = jnp.asarray(rng.uniform(0, 255, (2, 32, 64, 3)), jnp.float32)
    img2 = jnp.roll(img1, -2, axis=2)
    model = RAFTStereo(cfg)
    v = model.init(jax.random.PRNGKey(0), img1, img2, iters=1,
                   test_mode=True)

    def predictions():
        return model.apply(v, img1, img2, iters=2, test_mode=False)

    before = corr_lookup.path_choices()
    got = predictions()
    assert sorted(_said_since(before)) == [
        "trunk stage 32x64 x2 images: two images a lane row",     # cnet
        "trunk stage 32x64 x4 images: two images a lane row"]     # fnet
    want = _plain(monkeypatch, predictions)
    assert len(got) == len(want) == 2
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-3)
