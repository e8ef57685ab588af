"""Fused ConvGRU gate kernel (kernels/gru_fused.py) vs the Flax conv path.

Runs the kernel in Pallas interpret mode (CPU) — the same code path the TPU
compiles — via the package-wide interpret override shared with the
correlation kernels.  Covers every acceptance surface of the kernel-family
contract: forward + VJP parity for all three GRU levels (fp32 and bf16
bounds), composition with the ``remat_gru`` + ``save_only_these_names``
policy, the ``fused_gru="off"`` bitwise guarantee, and the capability /
VMEM-fit gating.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu.config import RaftStereoConfig
from raft_stereo_tpu.kernels import corr_lookup, gru_fused
from raft_stereo_tpu.models.update import BasicMultiUpdateBlock, ConvGRU


@pytest.fixture
def interpret_mode():
    corr_lookup._interpret_override = True
    yield
    corr_lookup._interpret_override = None


# Per-level (Ch, n_extra_inputs, H, W) mirroring the three GRU levels'
# input arity in BasicMultiUpdateBlock (gru08: motion+interp, gru16:
# pool+interp, gru32: pool); H=9 exercises the non-divisible row-block
# tail, W is deliberately lane-unaligned.
LEVELS = [
    pytest.param(32, 2, 9, 13, id="gru08"),
    pytest.param(32, 2, 6, 7, id="gru16"),
    pytest.param(24, 1, 4, 5, id="gru32"),
]


def _level_inputs(rng, b, h, w, ch, n_x, dtype=jnp.float32):
    mk = lambda c: jnp.asarray(  # noqa: E731
        rng.normal(size=(b, h, w, c)), dtype)
    hid = mk(ch)
    xs = [mk(ch) for _ in range(n_x)]
    ctx = tuple(mk(ch) for _ in range(3))
    return hid, ctx, xs


@pytest.mark.parametrize("ch,n_x,h,w", LEVELS)
def test_forward_parity_fp32(interpret_mode, rng, ch, n_x, h, w):
    hid, ctx, xs = _level_inputs(rng, 2, h, w, ch, n_x)
    v = ConvGRU(hidden_dim=ch, fused="off", name="g").init(
        jax.random.PRNGKey(0), hid, ctx, *xs)
    out_off = ConvGRU(hidden_dim=ch, fused="off", name="g").apply(
        v, hid, ctx, *xs)
    out_on = ConvGRU(hidden_dim=ch, fused="on", name="g").apply(
        v, hid, ctx, *xs)
    np.testing.assert_allclose(np.asarray(out_on), np.asarray(out_off),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ch,n_x,h,w", LEVELS)
def test_vjp_parity_fp32(interpret_mode, rng, ch, n_x, h, w):
    """Gradients w.r.t. params AND activations agree within fp32 tolerance
    (the kernel's 9-matmul conv reassociates differently from the XLA conv,
    so comparison is relative to each gradient tensor's scale)."""
    hid, ctx, xs = _level_inputs(rng, 1, h, w, ch, n_x)
    v = ConvGRU(hidden_dim=ch, fused="off", name="g").init(
        jax.random.PRNGKey(0), hid, ctx, *xs)

    def loss(fused):
        def f(params, hid_, xs_):
            out = ConvGRU(hidden_dim=ch, fused=fused, name="g").apply(
                {"params": params}, hid_, ctx, *xs_)
            return jnp.sum(jnp.sin(out))
        return f

    g_off = jax.grad(loss("off"), argnums=(0, 1, 2))(v["params"], hid, xs)
    g_on = jax.grad(loss("on"), argnums=(0, 1, 2))(v["params"], hid, xs)
    for a, b in zip(jax.tree_util.tree_leaves(g_off),
                    jax.tree_util.tree_leaves(g_on), strict=True):
        scale = max(1.0, float(jnp.max(jnp.abs(a))))
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-5 * scale)


def test_forward_parity_bf16(interpret_mode, rng):
    """bf16 bound: the kernel computes the gate pointwise chain in fp32
    where the Flax path rounds through bf16 at each op, so outputs agree to
    bf16 resolution (~2^-8 relative, documented bound 3e-2 on the blended
    state whose scale is ~1)."""
    hid, ctx, xs = _level_inputs(rng, 1, 8, 9, 32, 2, dtype=jnp.bfloat16)
    v = ConvGRU(hidden_dim=32, dtype=jnp.bfloat16, fused="off",
                name="g").init(jax.random.PRNGKey(0), hid, ctx, *xs)
    out_off = ConvGRU(hidden_dim=32, dtype=jnp.bfloat16, fused="off",
                      name="g").apply(v, hid, ctx, *xs)
    out_on = ConvGRU(hidden_dim=32, dtype=jnp.bfloat16, fused="on",
                     name="g").apply(v, hid, ctx, *xs)
    assert out_on.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out_on, np.float32),
                               np.asarray(out_off, np.float32), atol=3e-2)


def _update_block_io(rng, cfg, b=1, h=8, w=12, dtype=jnp.float32):
    n = cfg.n_gru_layers
    hd = cfg.hidden_dims
    mk = lambda hh, ww, c: jnp.asarray(  # noqa: E731
        rng.normal(size=(b, hh, ww, c)), dtype)
    net = [mk(h >> l, w >> l, hd[l]) for l in range(n)]
    ctx = [tuple(mk(h >> l, w >> l, hd[l]) for _ in range(3))
           for l in range(n)]
    corr = mk(h, w, cfg.corr_channels)
    flow = mk(h, w, 2)
    return net, ctx, corr, flow


def test_update_block_all_levels_fused(interpret_mode, rng):
    """End-to-end through BasicMultiUpdateBlock: all three GRU levels take
    the fused path (mode "on" would raise if any level fell back) and agree
    with the Flax path."""
    cfg = RaftStereoConfig(hidden_dims=(32, 32, 32), fnet_dim=64)
    net, ctx, corr, flow = _update_block_io(rng, cfg)
    ub_off = BasicMultiUpdateBlock(
        dataclasses.replace(cfg, fused_gru="off"), name="ub")
    v = ub_off.init(jax.random.PRNGKey(1), net, ctx, corr, flow)
    out_off = ub_off.apply(v, net, ctx, corr, flow)
    ub_on = BasicMultiUpdateBlock(
        dataclasses.replace(cfg, fused_gru="on"), name="ub")
    out_on = ub_on.apply(v, net, ctx, corr, flow)
    for a, b in zip(jax.tree_util.tree_leaves(out_off),
                    jax.tree_util.tree_leaves(out_on), strict=True):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-5)


def test_param_tree_identical_across_modes(interpret_mode, rng):
    """The fused path consumes the SAME parameter pytree nn.Conv creates —
    init under either mode yields identical names, shapes, and values, so
    checkpoints are mode-independent."""
    cfg = RaftStereoConfig(hidden_dims=(16, 16, 16), fnet_dim=32)
    net, ctx, corr, flow = _update_block_io(rng, cfg)
    v_off = BasicMultiUpdateBlock(
        dataclasses.replace(cfg, fused_gru="off"), name="ub").init(
        jax.random.PRNGKey(2), net, ctx, corr, flow)
    v_on = BasicMultiUpdateBlock(
        dataclasses.replace(cfg, fused_gru="on"), name="ub").init(
        jax.random.PRNGKey(2), net, ctx, corr, flow)
    pa = jax.tree_util.tree_structure(v_off)
    pb = jax.tree_util.tree_structure(v_on)
    assert pa == pb
    for a, b in zip(jax.tree_util.tree_leaves(v_off),
                    jax.tree_util.tree_leaves(v_on), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_remat_scan_vjp_parity(interpret_mode, rng):
    """The custom VJP composes with the model's exact training structure —
    nn.remat(policy=save_only_these_names("gru_gates", ...)) around an
    nn.scan of the update block: loss and gradients agree between fused and
    Flax paths.  (Exercised at the update-block level: the
    remat/scan/VJP composition under test lives entirely in the update
    block.)"""
    cfg = RaftStereoConfig(hidden_dims=(16, 16), n_gru_layers=2,
                           fnet_dim=32, corr_levels=2, corr_radius=3)
    net, ctx, corr, flow = _update_block_io(rng, cfg)

    class ScanUB(nn.Module):
        config: RaftStereoConfig

        @nn.compact
        def __call__(self, net, iters=3):
            def body(module, carry, _):
                net_l = BasicMultiUpdateBlock(self.config, name="ub")(
                    carry, ctx, corr, flow)[0]
                return tuple(net_l), jnp.mean(net_l[0])
            body = nn.remat(
                body, prevent_cse=False,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "gru_gates", "motion_features"))
            scan = nn.scan(body, variable_broadcast="params",
                           split_rngs={"params": False}, length=iters)
            _, means = scan(self, tuple(net), None)
            return jnp.sum(means)

    v = ScanUB(dataclasses.replace(cfg, fused_gru="off")).init(
        jax.random.PRNGKey(3), net)
    results = {}
    for mode in ("off", "on"):
        model = ScanUB(dataclasses.replace(cfg, fused_gru=mode))
        loss, grads = jax.value_and_grad(
            lambda p, m=model: m.apply({"params": p}, net))(v["params"])
        results[mode] = (float(loss), jax.tree_util.tree_leaves(grads))
    np.testing.assert_allclose(results["on"][0], results["off"][0],
                               rtol=1e-6)
    for a, b in zip(results["off"][1], results["on"][1], strict=True):
        scale = max(1.0, float(jnp.max(jnp.abs(a))))
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-5 * scale)


def test_off_reproduces_flax_graph_bitwise(interpret_mode, rng):
    """fused_gru="off" must reproduce today's graph even when the kernel IS
    available (interpret override on): no pallas_call in the trace, and
    outputs bit-identical to "auto" on a backend where the kernel is
    unavailable (= the pre-kernel graph)."""
    cfg = RaftStereoConfig(hidden_dims=(16, 16), n_gru_layers=2,
                           fnet_dim=32, corr_levels=2, corr_radius=3)
    net, ctx, corr, flow = _update_block_io(rng, cfg)
    ub_off = BasicMultiUpdateBlock(
        dataclasses.replace(cfg, fused_gru="off"), name="ub")
    v = ub_off.init(jax.random.PRNGKey(4), net, ctx, corr, flow)
    jaxpr = jax.make_jaxpr(
        lambda *a: ub_off.apply(v, *a))(net, ctx, corr, flow)
    assert "pallas_call" not in str(jaxpr)
    out_off = ub_off.apply(v, net, ctx, corr, flow)

    corr_lookup._interpret_override = None  # kernel now unavailable (CPU)
    out_auto = BasicMultiUpdateBlock(
        dataclasses.replace(cfg, fused_gru="auto"), name="ub").apply(
        v, net, ctx, corr, flow)
    for a, b in zip(jax.tree_util.tree_leaves(out_off),
                    jax.tree_util.tree_leaves(out_auto), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_auto_uses_kernel_when_available(interpret_mode, rng):
    """auto = kernel on capable backends: with the override active the
    traced graph contains the pallas_call."""
    hid, ctx, xs = _level_inputs(rng, 1, 8, 8, 16, 1)
    gru = ConvGRU(hidden_dim=16, fused="auto", name="g")
    v = gru.init(jax.random.PRNGKey(0), hid, ctx, *xs)
    jaxpr = jax.make_jaxpr(lambda *a: gru.apply(v, *a))(hid, ctx, *xs)
    assert "pallas_call" in str(jaxpr)


def test_capability_and_fit_gating(rng):
    """Contract gating: unavailable backend → auto falls back silently,
    "on" raises; oversized working set → row block is refused."""
    assert corr_lookup._interpret_override is None
    assert not gru_fused.gru_fused_available()  # CPU, no override
    assert not gru_fused.gru_fused_should_use(
        "auto", kernel_size=3, w=64, cin=96, ch=32, itemsize=4)
    with pytest.raises(RuntimeError, match="unavailable"):
        gru_fused.gru_fused_should_use(
            "on", kernel_size=3, w=64, cin=96, ch=32, itemsize=4)
    # VMEM fit: a realistic level fits; an absurdly wide one must not, and
    # the row block never shrinks below the two-view minimum of 4.
    rb = gru_fused.gru_fused_row_block(90, 384, 128, 2)
    assert rb is not None and 4 <= rb <= 8
    # The SceneFlow crop's finest level: 11.57 MiB on the v5e at rb=4 (the
    # halo doubles the rows), refused inside the batch-4 training step —
    # the estimate counts the halo rows and sends it to the Flax path.
    assert gru_fused.gru_fused_row_block(180, 384, 128, 2) is None
    assert gru_fused.gru_fused_row_block(200_000, 384, 128, 4) is None
    # "on" + unfittable working set raises even where the kernel exists.
    corr_lookup._interpret_override = True
    try:
        with pytest.raises(RuntimeError, match="VMEM"):
            gru_fused.gru_fused_should_use(
                "on", kernel_size=3, w=200_000, cin=384, ch=128, itemsize=4)
        assert not gru_fused.gru_fused_should_use(
            "auto", kernel_size=3, w=200_000, cin=384, ch=128, itemsize=4)
    finally:
        corr_lookup._interpret_override = None


def test_config_flag_validation():
    with pytest.raises(ValueError, match="fused_gru"):
        RaftStereoConfig(fused_gru="yes")
    cfg = RaftStereoConfig(fused_gru="on")
    assert RaftStereoConfig.from_json(cfg.to_json()).fused_gru == "on"
    # Old serialized configs (no field) deserialize to the default.
    d = cfg.to_dict()
    del d["fused_gru"]
    assert RaftStereoConfig.from_dict(d).fused_gru == "auto"


def test_public_kernel_api_exports():
    """kernels/__init__.py is the supported import surface."""
    from raft_stereo_tpu import kernels
    for name in ("fused_lookup_available", "alt_fused_available",
                 "lookup_pyramid_fused", "gru_fused_available",
                 "gru_gates_fused", "interpret_enabled"):
        assert callable(getattr(kernels, name)), name
