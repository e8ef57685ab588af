"""The inference loops compute the convex-upsampling mask once, on the
loop's final finest hidden state, where training computes it every
iteration (models/raft_stereo.py, models/update.py, models/igev.py).

Parity: on the same weights a test-mode answer equals the last (or the
k-th) prediction of a training-mode forward, whose scan still upsamples
every iteration with that iteration's mask; batch norm is frozen in both,
so the two forwards are one function.  Every test-mode loop form is held
to it: the fixed scan, the exit-gated ``while`` (run to its cap and exited
early), both with the confidence maps, and the unrolled loop.

Structure: in a test-mode program's optimized HLO the mask head sits
outside the ``while`` and its channels ride no loop carry; the training
scan's body still holds it.  CPU, 64x96 pairs, float32.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu.config import IgevStereoConfig, RaftStereoConfig
from raft_stereo_tpu.kernels import corr_lookup
from raft_stereo_tpu.models import igev
from raft_stereo_tpu.models.raft_stereo import RAFTStereo
from raft_stereo_tpu.models.update import BasicMultiUpdateBlock
from raft_stereo_tpu.training.state import init_model_variables

HW = (64, 96)
ITERS = 3
CONFIGS = {
    "accuracy": RaftStereoConfig(),
    "realtime": dataclasses.replace(RaftStereoConfig.realtime(),
                                    mixed_precision=False),
}
# (exit-gated, exit_min_iters, confidence, unrolled, depth the answer has)
FORMS = {
    "scan": (False, 1, False, False, ITERS),
    "scan_confidence": (False, 1, True, False, ITERS),
    "while_to_cap": (True, ITERS, False, False, ITERS),
    "while_to_cap_confidence": (True, ITERS, True, False, ITERS),
    "while_early": (True, 2, False, False, 2),
    "while_early_confidence": (True, 2, True, False, 2),
    "unrolled": (False, 1, False, True, ITERS),
    "unrolled_confidence": (False, 1, True, True, ITERS),
}


def _pair():
    rng = np.random.default_rng(5)
    left = rng.uniform(0, 255, (1,) + HW + (3,)).astype(np.float32)
    return jnp.asarray(left), jnp.asarray(np.roll(left, -3, axis=2))


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def trained_answers(request):
    """A configuration, its weights, and the training-mode forward's
    prediction after each iteration."""
    cfg = CONFIGS[request.param]
    variables = init_model_variables(cfg, jax.random.PRNGKey(3),
                                     (1,) + HW + (3,))
    left, right = _pair()
    ups = jax.jit(lambda v, a, b: RAFTStereo(cfg).apply(
        v, a, b, iters=ITERS, test_mode=False))(variables, left, right)
    return cfg, variables, np.asarray(ups)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_answer_is_the_per_iteration_mask_answer(trained_answers, form):
    cfg, variables, ups = trained_answers
    gated, min_iters, confidence, unrolled, depth = FORMS[form]
    if gated:
        # a threshold no update falls under runs to the cap; one every
        # update falls under exits at ``exit_min_iters``
        cfg = dataclasses.replace(
            cfg, exit_threshold_px=1e-30 if depth == ITERS else 1e30,
            exit_min_iters=min_iters, exit_max_iters=ITERS)
    left, right = _pair()
    out = jax.jit(lambda v, a, b: RAFTStereo(cfg).apply(
        v, a, b, iters=ITERS, test_mode=True, unroll_gru=unrolled,
        return_confidence=confidence))(variables, left, right)
    if gated:
        assert int(out[2]) == depth
    flow_up = np.asarray(out[1])
    want = ups[depth - 1]
    assert np.abs(want).max() > 1.0           # the loop moved the answer
    np.testing.assert_allclose(flow_up, want, rtol=0, atol=1e-5)
    if confidence:
        _, conf_up = out[-1]
        assert conf_up.shape == flow_up.shape
        assert 0.0 <= float(conf_up.min()) and float(conf_up.max()) <= 1.0


def test_update_block_mask_head_is_the_full_calls_mask(rng):
    """``mask=False`` leaves the mask out and changes nothing else;
    ``mask_head`` of the new finest state is the full call's mask; the
    parameter tree is the one a full call creates."""
    cfg = RaftStereoConfig(hidden_dims=(32, 32, 32), fnet_dim=64)
    mk = lambda hh, ww, c: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, hh, ww, c)), jnp.float32)
    net = [mk(8 >> l, 12 >> l, 32) for l in range(3)]
    ctx = [tuple(mk(8 >> l, 12 >> l, 32) for _ in range(3))
           for l in range(3)]
    corr, flow = mk(8, 12, cfg.corr_channels), mk(8, 12, 2)
    ub = BasicMultiUpdateBlock(cfg)
    v = ub.init(jax.random.PRNGKey(0), net, ctx, corr, flow)
    assert {"mask_conv1", "mask_conv2", "flow_head"} <= set(v["params"])
    assert v["params"]["mask_conv2"]["kernel"].shape == (
        1, 1, 256, cfg.mask_channels)
    full_net, full_mask, full_delta = ub.apply(v, net, ctx, corr, flow)
    net2, delta2 = ub.apply(v, net, ctx, corr, flow, mask=False)
    np.testing.assert_array_equal(np.asarray(delta2), np.asarray(full_delta))
    for a, b in zip(net2, full_net):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    alone = ub.apply(v, net2[0], method=BasicMultiUpdateBlock.mask_head)
    np.testing.assert_allclose(np.asarray(alone), np.asarray(full_mask),
                               rtol=0, atol=1e-6)


# ---- structure of the compiled programs -----------------------------------

_CALLED = re.compile(
    r"(?:calls|body|condition|to_apply|branch_computations)="
    r"\{?%?([\w.\-]+(?:, %[\w.\-]+)*)\}?")


def _computations(text):
    """Computation name -> its lines, and the ENTRY's name, of an optimized
    HLO module's text."""
    comps, name, entry = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) .*\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(2)
            comps[name] = []
            entry = name if head.group(1) else entry
        elif name is not None and line.strip() != "}":
            comps[name].append(line)
    return comps, entry


def _reach(comps, roots, skip_while_bodies):
    """Computations reachable from ``roots``; a ``while``'s body and
    condition are not followed when ``skip_while_bodies``."""
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            if skip_while_bodies and " while(" in line:
                continue
            for group in _CALLED.findall(line):
                todo.extend(n.strip().lstrip("%") for n in group.split(","))
    return seen


def _split(text, scope):
    """(instructions in a while body, instructions outside every while)
    whose ``op_name`` holds ``scope``, and the while instructions."""
    comps, entry = _computations(text)
    outside = _reach(comps, [entry], skip_while_bodies=True)
    whiles = [ln for c in outside for ln in comps[c] if " while(" in ln]
    bodies = [m for ln in whiles
              for m in re.findall(r"body=%?([\w.\-]+)", ln)]
    inside = _reach(comps, bodies, skip_while_bodies=False)
    pick = lambda names: [ln for c in names for ln in comps[c]  # noqa: E731
                          if f"/{scope}/" in ln and "op_name=" in ln]
    return pick(inside), pick(outside), whiles


def _convolutions(lines):
    return [ln for ln in lines if " convolution(" in ln]


def _shapes(cfg, hw):
    """The variables' and one image's shapes: lowering needs no values."""
    return (jax.eval_shape(lambda: init_model_variables(
        cfg, jax.random.PRNGKey(0), (1,) + hw + (3,))),
        jax.ShapeDtypeStruct((1,) + hw + (3,), jnp.float32))


def _raft_text(cfg, test_mode):
    variables, img = _shapes(cfg, HW)
    fn = jax.jit(lambda v, a, b: RAFTStereo(cfg).apply(
        v, a, b, iters=ITERS, test_mode=test_mode))
    return fn.lower(variables, img, img).compile().as_text()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_test_mode_program_runs_the_mask_head_once_after_the_loop(name):
    cfg = CONFIGS[name]
    text = _raft_text(cfg, True)
    inside, outside, whiles = _split(text, "mask_conv1")
    assert whiles, "the refinement loop is a while"
    assert inside == []
    assert len(_convolutions(outside)) == 1
    assert _convolutions(_split(text, "flow_head")[0]), \
        "the disparity head stays in the loop"
    h, w = (d // cfg.downsample_factor for d in HW)
    wide = f"[1,{h},{w},{cfg.mask_channels}]"
    assert not any(wide in ln.split(" while(")[0] for ln in whiles)


def test_training_scan_keeps_the_mask_every_iteration():
    inside, outside, _ = _split(_raft_text(CONFIGS["accuracy"], False),
                                "mask_conv1")
    assert _convolutions(inside)
    assert not _convolutions(outside)


def test_igev_program_runs_the_mask_features_once_after_the_loop():
    cfg = IgevStereoConfig(max_disp=32)
    variables, img = _shapes(cfg, (64, 128))
    fn = jax.jit(lambda v, a, b: igev.IGEVStereo(cfg).apply(
        v, a, b, iters=2, test_mode=True))
    note = "upsampling mask: once after the loop (2 iterations)"
    before = corr_lookup.path_choices().get(note, 0)
    text = fn.lower(variables, img, img).compile().as_text()
    inside, outside, whiles = _split(text, "mask_feat_4")
    assert whiles and inside == []
    assert len(_convolutions(outside)) == 1
    assert corr_lookup.path_choices()[note] == before + 1


@pytest.mark.parametrize("form,note", [
    ("scan", f"upsampling mask: once after the loop ({ITERS} iterations)"),
    ("unrolled", f"upsampling mask: once after the loop ({ITERS} "
                 f"iterations, unrolled)"),
    ("while", f"upsampling mask: once after the loop (1 to {ITERS} "
              f"iterations, exit-gated)"),
])
def test_each_traced_inference_program_says_where_its_mask_runs(form, note):
    cfg = RaftStereoConfig(hidden_dims=(32, 32, 32), fnet_dim=64)
    if form == "while":
        cfg = dataclasses.replace(cfg, exit_threshold_px=0.01)
    variables, img = _shapes(cfg, HW)
    before = corr_lookup.path_choices()
    jax.eval_shape(lambda v, a, b: RAFTStereo(cfg).apply(
        v, a, b, iters=ITERS, test_mode=True, unroll_gru=form == "unrolled"),
        variables, img, img)
    said = corr_lookup.path_choices()
    new = [m for m, k in said.items() if k > before.get(m, 0)
           and m.startswith("upsampling mask")]
    assert new == [note]
    jax.eval_shape(lambda v, a, b: RAFTStereo(cfg).apply(
        v, a, b, iters=ITERS, test_mode=False), variables, img, img)
    assert corr_lookup.path_choices()[note] == said[note]
