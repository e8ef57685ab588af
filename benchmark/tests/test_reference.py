"""The plain reference against the program, and ``flops.py`` against XLA,
at a size the CPU holds: published widths, a 60x100 image, few
iterations."""

import jax
import numpy as np
import pytest

from benchmark import flops, harness, reference, scenes, weights

CONFIGS = ("raftstereo-accuracy", "raftstereo-realtime")


def _model(name):
    return harness.load_json("configs", name + ".json")["model"]


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_table_is_the_programs_tree(name):
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.training.state import init_model_variables

    model = _model(name)
    cfg = RaftStereoConfig.from_dict(model)
    shapes = jax.eval_shape(
        lambda: init_model_variables(cfg, jax.random.PRNGKey(0)))
    want = {"/".join(k.key for k in path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    got = {path: shape for path, shape, _ in weights.param_spec(model)}
    assert got == want


@pytest.mark.parametrize("name,iters", [(CONFIGS[0], 3), (CONFIGS[1], 3)])
def test_program_agrees_with_the_reference_in_float32(name, iters):
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.eval.runner import InferenceRunner

    model = dict(_model(name), mixed_precision=False)
    w = weights.make_weights(model, 2147483653)
    left, right = scenes.make_pairs(5, 1, (60, 100))[0]
    runner = InferenceRunner(RaftStereoConfig.from_dict(model),
                             weights.nest(w), iters=iters)
    flow, _ = runner(left, right)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.disparity(model, w, left, right, iters))
    assert np.abs(want).mean() > 0.5          # something was matched
    assert np.abs(flow - want).max() < 1e-4 * np.abs(want).max()


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_against_xla_on_the_unrolled_pure_xla_program(name):
    """XLA counts a loop body once and a kernel as nought, so the program
    is compiled unrolled and without kernels; its count then holds the
    norms and activations ours leaves out.  It also leaves out the taps
    that fall on a convolution's zero padding, which the benchmark's count
    keeps (the usual convention; several percent on a 32x48 map, under 2%
    at KITTI size): the comparison is made without them."""
    from raft_stereo_tpu.config import RaftStereoConfig
    from raft_stereo_tpu.models.raft_stereo import RAFTStereo

    model = dict(_model(name), mixed_precision=False, corr_backend="reg",
                 fused_gru="off")
    cfg = RaftStereoConfig.from_dict(model)
    h, w = (32 * 2 ** model["n_downsample"], 48 * 2 ** model["n_downsample"])
    iters = 3                                 # a 32x48 finest map for both
    net = RAFTStereo(cfg)
    tree = weights.nest(weights.make_weights(model, 1))
    img = np.zeros((1, h, w, 3), np.float32)

    def fwd(v, a, b):
        return net.apply(v, a, b, iters=iters, test_mode=True,
                         unroll_gru=True)[1]

    cost = jax.jit(fwd).lower(tree, img, img).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    ours = flops.forward_flops(model, h, w, iters, padding_taps=False)
    assert 0.97 * cost["flops"] < ours < 1.005 * cost["flops"]
    with_padding = flops.forward_flops(model, h, w, iters)
    assert ours < with_padding < 1.10 * ours


def test_flops_scale_with_iterations_and_pixels():
    model = _model(CONFIGS[0])
    a = flops.forward_flops(model, 384, 1248, 32)
    b = flops.forward_flops(model, 384, 1248, 16)
    c = flops.forward_flops(model, 192, 624, 32)
    assert 3.5e12 < a < 6e12                  # ~4 TFLOP a KITTI pair
    assert b < a < 2 * b
    assert 3.5 < a / c < 4.5
    work = flops.lookup_work(model, 384, 1248, 4)
    assert flops.least_seconds(work, harness.peaks_for("TPU v5 lite"))[1] \
        == "memory"
