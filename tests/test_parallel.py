"""W2-sharded correlation (parallel/corr_sharded.py) vs the unsharded reg
backend, on the 8-virtual-CPU-device mesh (conftest).

The sharded path must agree with ``reg`` to numerical precision — values AND
gradients — including awkward W2 (padding + floor-pooling masking) and
fractional/out-of-range lookup coordinates.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu.config import RaftStereoConfig
from raft_stereo_tpu.models.corr import make_corr_fn, make_corr_fn_reg
from raft_stereo_tpu.parallel import corr_sharding, make_mesh
from raft_stereo_tpu.parallel.corr_sharded import make_corr_fn_w2_sharded


def _fmaps(rng, b, h, w1, w2, d=16):
    f1 = jnp.asarray(rng.standard_normal((b, h, w1, d)), jnp.float32)
    f2 = jnp.asarray(rng.standard_normal((b, h, w2, d)), jnp.float32)
    return f1, f2


def _coords(rng, b, h, w1, w2):
    # Cover in-range, fractional, and out-of-range positions.
    c = rng.uniform(-3.0, w2 + 3.0, (b, h, w1))
    return jnp.asarray(c, jnp.float32)


@pytest.mark.slow
@pytest.mark.parametrize("n_corr", [2, 4])
@pytest.mark.parametrize("w2", [64, 52, 13])
def test_sharded_matches_reg(rng, n_corr, w2):
    cfg = RaftStereoConfig(corr_w2_shards=n_corr)
    mesh = make_mesh(n_data=8 // n_corr, n_corr=n_corr)
    b, h, w1 = 2, 4, 52
    f1, f2 = _fmaps(rng, b, h, w1, w2)
    coords = _coords(rng, b, h, w1, w2)

    ref = make_corr_fn_reg(cfg, f1, f2)(coords)
    with corr_sharding(mesh):
        out = make_corr_fn_w2_sharded(cfg, f1, f2, mesh)(coords)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_sharded_gradients_match_reg(rng):
    cfg = RaftStereoConfig(corr_w2_shards=2)
    mesh = make_mesh(n_data=4, n_corr=2)
    b, h, w1, w2 = 1, 4, 24, 40
    f1, f2 = _fmaps(rng, b, h, w1, w2, d=8)
    coords = _coords(rng, b, h, w1, w2)
    cot = jnp.asarray(rng.standard_normal(
        (b, h, w1, cfg.corr_channels)), jnp.float32)

    def loss_ref(f1, f2):
        return jnp.sum(make_corr_fn_reg(cfg, f1, f2)(coords) * cot)

    def loss_sharded(f1, f2):
        fn = make_corr_fn_w2_sharded(cfg, f1, f2, mesh)
        return jnp.sum(fn(coords) * cot)

    g_ref = jax.grad(loss_ref, argnums=(0, 1))(f1, f2)
    with corr_sharding(mesh):
        g_sh = jax.jit(jax.grad(loss_sharded, argnums=(0, 1)))(f1, f2)
    for a, b_ in zip(g_sh, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


def test_sharded_reg_fused_backend_matches_reg(rng):
    """corr_w2_shards with the (default) reg_fused backend: the sharded
    volume path must agree with the unsharded reg backend (fp32 inputs ⇒
    fp32 shard storage ⇒ exact)."""
    cfg = RaftStereoConfig(corr_w2_shards=2, corr_backend="reg_fused")
    mesh = make_mesh(n_data=4, n_corr=2)
    b, h, w1, w2 = 1, 4, 24, 40
    f1, f2 = _fmaps(rng, b, h, w1, w2, d=8)
    coords = _coords(rng, b, h, w1, w2)
    ref = make_corr_fn_reg(RaftStereoConfig(corr_backend="reg"), f1, f2)(coords)

    with corr_sharding(mesh):
        out = jax.jit(
            lambda c: make_corr_fn_w2_sharded(cfg, f1, f2, mesh)(c)
        )(coords)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_dispatch_requires_active_mesh(rng):
    cfg = RaftStereoConfig(corr_w2_shards=2)
    f1, f2 = _fmaps(rng, 1, 2, 8, 8)
    with pytest.raises(RuntimeError, match="corr_sharding"):
        make_corr_fn(cfg, f1, f2)


@pytest.mark.slow
def test_full_model_sharded_matches_unsharded(rng):
    """Whole-model forward with corr_w2_shards=2 ≡ the plain reg model."""
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    mesh = make_mesh(n_data=4, n_corr=2)
    cfg_plain = RaftStereoConfig(n_gru_layers=2, hidden_dims=(32, 32),
                                 fnet_dim=64)
    cfg_shard = RaftStereoConfig(n_gru_layers=2, hidden_dims=(32, 32),
                                 fnet_dim=64, corr_w2_shards=2)
    img1 = jnp.asarray(rng.uniform(0, 255, (1, 32, 64, 3)), jnp.float32)
    img2 = jnp.asarray(rng.uniform(0, 255, (1, 32, 64, 3)), jnp.float32)

    model = RAFTStereo(cfg_plain)
    variables = model.init(jax.random.PRNGKey(0), img1, img2, iters=1,
                           test_mode=True)
    lo_ref, up_ref = model.apply(variables, img1, img2, iters=3,
                                 test_mode=True)

    model_sh = RAFTStereo(cfg_shard)
    with corr_sharding(mesh):
        lo_sh, up_sh = jax.jit(
            lambda v, a, b: model_sh.apply(v, a, b, iters=3, test_mode=True)
        )(variables, img1, img2)
    # fp summation-order differences (psum vs in-thread adds) amplify through
    # the recurrent GRU; per-lookup agreement is exact (tests above).
    np.testing.assert_allclose(np.asarray(lo_sh), np.asarray(lo_ref),
                               rtol=1e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(up_sh), np.asarray(up_ref),
                               rtol=1e-3, atol=2e-3)


# ------------------------------------------- Pallas kernel inside the shard
@pytest.fixture
def _interpret_mode():
    from raft_stereo_tpu.kernels import corr_lookup
    corr_lookup._interpret_override = True
    yield
    corr_lookup._interpret_override = None


@pytest.mark.slow
@pytest.mark.parametrize("b,n_data,n_corr", [(1, 4, 2), (4, 2, 4)])
def test_sharded_kernel_matches_reg(rng, _interpret_mode, b, n_data, n_corr):
    """reg_fused + corr_w2_shards engages the Pallas kernel per shard
    (full-manual shard_map); values must match unsharded reg exactly, in
    both the replicated-batch and split-batch spec branches."""
    cfg = RaftStereoConfig(corr_w2_shards=n_corr, corr_backend="reg_fused")
    mesh = make_mesh(n_data=n_data, n_corr=n_corr)
    h, w1, w2 = 4, 24, 40
    f1, f2 = _fmaps(rng, b, h, w1, w2, d=8)
    coords = _coords(rng, b, h, w1, w2)
    ref = make_corr_fn_reg(RaftStereoConfig(corr_backend="reg"),
                           f1, f2)(coords)

    with corr_sharding(mesh):
        out = jax.jit(
            lambda c: make_corr_fn_w2_sharded(cfg, f1, f2, mesh)(c)
        )(coords)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_sharded_kernel_gradients_match_reg(rng, _interpret_mode):
    """Feature gradients THROUGH the per-shard Pallas kernel (custom VJP
    inside a full-manual shard_map) match the unsharded reg backend."""
    cfg = RaftStereoConfig(corr_w2_shards=2, corr_backend="reg_fused")
    mesh = make_mesh(n_data=4, n_corr=2)
    b, h, w1, w2 = 1, 4, 24, 40
    f1, f2 = _fmaps(rng, b, h, w1, w2, d=8)
    coords = _coords(rng, b, h, w1, w2)
    cot = jnp.asarray(rng.standard_normal(
        (b, h, w1, cfg.corr_channels)), jnp.float32)

    def loss_ref(f1, f2):
        return jnp.sum(make_corr_fn_reg(
            RaftStereoConfig(corr_backend="reg"), f1, f2)(coords) * cot)

    def loss_sharded(f1, f2):
        fn = make_corr_fn_w2_sharded(cfg, f1, f2, mesh)
        return jnp.sum(fn(coords) * cot)

    g_ref = jax.grad(loss_ref, argnums=(0, 1))(f1, f2)
    with corr_sharding(mesh):
        g_sh = jax.jit(jax.grad(loss_sharded, argnums=(0, 1)))(f1, f2)
    for a, b_ in zip(g_sh, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_sharded_fullres_structure(rng, _interpret_mode):
    """Full-resolution W2 STRUCTURE (Middlebury-F at 1/4 res has W2=496)
    through the sharded volume + Pallas kernel on the virtual mesh — H kept
    tiny so the CPU interpreter stays fast; the W2 math (padding quantum,
    level widths 496/248/124/62, shard offsets) is the full-res case."""
    cfg = RaftStereoConfig(corr_w2_shards=4, corr_backend="reg_fused")
    mesh = make_mesh(n_data=2, n_corr=4)
    b, h, w1, w2 = 1, 2, 496, 496
    f1, f2 = _fmaps(rng, b, h, w1, w2, d=16)
    coords = _coords(rng, b, h, w1, w2)
    ref = make_corr_fn_reg(RaftStereoConfig(corr_backend="reg"),
                           f1, f2)(coords)
    with corr_sharding(mesh):
        out = jax.jit(
            lambda c: make_corr_fn_w2_sharded(cfg, f1, f2, mesh)(c)
        )(coords)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ----------------------------- kernels split over the data axis by hand
# (parallel/data_sharded.py: XLA partitions everything else of a
# data-parallel step from in_shardings, but not a Mosaic kernel)
def test_kernels_split_over_data_axis_match_unsharded(rng, _interpret_mode):
    """reg_fused under a 4-device data mesh: the lookup (values and the
    gradient through its custom VJP) equals the unsharded kernel's, and
    the batch stays split — in a jit whose inputs are sharded over
    ``data`` nothing is gathered."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raft_stereo_tpu.models.corr import make_corr_fn_reg_fused
    from raft_stereo_tpu.parallel.data_sharded import data_sharding

    cfg = RaftStereoConfig(corr_levels=2)
    mesh = make_mesh(n_data=4, devices=jax.devices()[:4])
    b, h, w1, w2 = 4, 4, 24, 24
    f1, f2 = _fmaps(rng, b, h, w1, w2, d=8)
    coords = _coords(rng, b, h, w1, w2)
    probe = jnp.asarray(rng.standard_normal((b, h, w1, 2 * 9)), jnp.float32)

    def loss(f1, f2, coords):
        out = make_corr_fn_reg_fused(cfg, f1, f2)(coords)
        return jnp.sum(out * probe), out

    want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(
        f1, f2, coords)

    def on_mesh(f1, f2, coords):
        with data_sharding(mesh):
            return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
                f1, f2, coords)

    split = NamedSharding(mesh, P("data"))
    jitted = jax.jit(on_mesh, in_shardings=(split, split, split))
    got = jitted(f1, f2, coords)
    for a, b_ in zip(jax.tree_util.tree_leaves(got),
                     jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-5, rtol=1e-5)
    assert got[0][1].sharding.spec[0] == "data"       # lookup output
    assert "all-gather" not in jitted.lower(f1, f2, coords).compile(
        ).as_text()


def test_over_data_axis_runs_directly_when_there_is_nothing_to_split(rng):
    """No active mesh, a one-device data axis, or a batch the axis does
    not divide (batch-1 init under a mesh): the plain call."""
    from raft_stereo_tpu.parallel.data_sharded import (data_sharding,
                                                       over_data_axis)

    calls = []

    def fn(x, w):
        calls.append(x.shape)
        return x * w

    x = jnp.ones((3, 2))
    w = jnp.full((2,), 2.0)
    np.testing.assert_array_equal(over_data_axis(fn, (x,), (w,)), x * 2)
    with data_sharding(make_mesh(n_data=1, devices=jax.devices()[:1])):
        over_data_axis(fn, (x,), (w,))
    with data_sharding(make_mesh(n_data=4, devices=jax.devices()[:4])):
        over_data_axis(fn, (x,), (w,))            # 3 % 4 != 0
        out = over_data_axis(fn, (jnp.ones((8, 2)),), (w,))
    assert calls[:3] == [(3, 2)] * 3
    assert calls[3] == (2, 2)                     # a quarter of the batch
    np.testing.assert_array_equal(out, jnp.full((8, 2), 2.0))
    with pytest.raises(ValueError, match="no 'data' axis"):
        from jax.sharding import Mesh
        with data_sharding(Mesh(np.array(jax.devices()[:2]), ("rows",))):
            pass


def test_dryrun_needs_its_devices_up_front():
    """__graft_entry__ no longer swaps the backend under the caller: too
    few devices is an error that names the setting."""
    import __graft_entry__ as entry

    assert len(entry._ensure_devices(4)) == 4
    with pytest.raises(RuntimeError,
                       match="xla_force_host_platform_device_count=64"):
        entry._ensure_devices(64)
