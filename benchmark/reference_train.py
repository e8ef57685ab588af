"""The plain reference of a training step: RAFT-Stereo's training-mode
forward, the sequence loss, its gradient, the global-norm clip, AdamW and
the one-cycle schedule, in straight ``jax.numpy``, float32, every product
at ``highest`` precision.  ``reference.py``'s layers are imported; nothing
is imported from the program under test, and the optimizer is written from
the published description, not read off ``training/optimizer.py``.

Sources: Lipson, Teed, Deng, RAFT-Stereo, arXiv 2109.07547 §3.4 (the loss)
and §4 (the recipe); github.com/princeton-vl/RAFT-Stereo
``train_stereo.py:35-69`` (``sequence_loss``), ``:72-79`` (AdamW and
``OneCycleLR``), ``:174-177`` (the clip), ``core/raft_stereo.py:108-123``
(the training-mode loop).

This is the THIRD copy of ``reference.forward``'s loop body (after
``reference_staged.py``): ``reference.py`` could not be edited by the PR
that brought this file.  A ``benchmark`` PR that lets ``reference.forward``
yield per iteration dissolves both copies (PERF.md section 7, 0e).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import (conv, conv_gru, convex_upsample,
                                 corr_lookup, corr_pyramid, interp_to,
                                 pool2x, res_block, trunk)

# the recipe's constants that no cell changes (train_stereo.py:72-79, 174)
BETA1, BETA2 = 0.9, 0.999
PCT_START, DIV_FACTOR, FINAL_DIV_FACTOR = 0.01, 25.0, 1e4
SCHEDULE_EXTRA_STEPS = 100


# ----------------------------------------------------------------- forward
def predictions(cfg: dict, w: dict, image1, image2, iters: int):
    """(B, H, W, 3) images in 0..255, H and W multiples of 32, to the
    full-resolution x-flow of EVERY refinement, (iters, B, H, W): what the
    published training-mode forward returns as its list.  Batch norm runs
    on its stored statistics (the published recipe freezes it:
    ``train_stereo.py:151``)."""
    n, nd = cfg["n_gru_layers"], cfg["n_downsample"]
    if cfg["shared_backbone"] or cfg["slow_fast_gru"]:
        raise ValueError("reference_train: the training recipe's model has "
                         "neither a shared backbone nor slow-fast updates")
    im1 = 2.0 * (image1.astype(jnp.float32) / 255.0) - 1.0
    im2 = 2.0 * (image2.astype(jnp.float32) / 255.0) - 1.0
    cnorm = cfg["context_norm"]
    x = trunk(w, "cnet/trunk", cnorm, im1, nd)
    fmap = conv(w, "fnet/conv2",
                trunk(w, "fnet/trunk", cfg["fnet_norm"],
                      jnp.concatenate([im1, im2]), nd))
    f1, f2 = jnp.split(fmap, 2)

    def heads(tag, x, with_res=True):
        out = []
        for h in (0, 1):
            y = (res_block(w, f"cnet/outputs{tag}_{h}_res", cnorm, x, 1)
                 if with_res else x)
            out.append(conv(w, f"cnet/outputs{tag}_{h}_conv", y))
        return out

    levels = [heads("08", x)]
    if n >= 2:
        x16 = res_block(w, "cnet/layer4_1", cnorm,
                        res_block(w, "cnet/layer4_0", cnorm, x, 2), 1)
        levels.append(heads("16", x16))
    if n >= 3:
        x32 = res_block(w, "cnet/layer5_1", cnorm,
                        res_block(w, "cnet/layer5_0", cnorm, x16, 2), 1)
        levels.append(heads("32", x32, with_res=False))
    net = [jnp.tanh(lv[0]) for lv in levels]
    ctx = [tuple(jnp.split(conv(w, f"context_zqr_conv{l}",
                                jax.nn.relu(lv[1])), 3, axis=-1))
           for l, lv in enumerate(levels)]

    pyr = corr_pyramid(w, f1, f2, cfg["corr_levels"])
    b, h8, w8, _ = net[0].shape
    grid = jnp.broadcast_to(jnp.arange(w8, dtype=jnp.float32), (b, h8, w8))
    ub = "update_block"

    def refine(state, _):
        net, disp = state
        net = list(net)
        # the published loop detaches the coordinates at the head of every
        # iteration (``coords1 = coords1.detach()``): the gradient reaches
        # an iteration's update and mask, not the disparity it started from
        disp = lax.stop_gradient(disp)
        corr = corr_lookup(pyr, grid + disp, cfg["corr_radius"])
        flow2 = jnp.stack([disp, jnp.zeros_like(disp)], axis=-1)
        if n == 3:
            net[2] = conv_gru(w, f"{ub}/gru32", net[2], ctx[2],
                              pool2x(net[1]))
        if n >= 2:
            coupled = ([pool2x(net[0]), interp_to(net[2], net[1])]
                       if n == 3 else [pool2x(net[0])])
            net[1] = conv_gru(w, f"{ub}/gru16", net[1], ctx[1], *coupled)
        enc = f"{ub}/encoder"
        cor = jax.nn.relu(conv(w, f"{enc}/convc1", corr))
        cor = jax.nn.relu(conv(w, f"{enc}/convc2", cor))
        flo = jax.nn.relu(conv(w, f"{enc}/convf1", flow2))
        flo = jax.nn.relu(conv(w, f"{enc}/convf2", flo))
        out = jax.nn.relu(conv(w, f"{enc}/conv",
                               jnp.concatenate([cor, flo], axis=-1)))
        motion = jnp.concatenate([out, flow2], axis=-1)
        fine_in = [motion] + ([interp_to(net[1], net[0])] if n > 1 else [])
        net[0] = conv_gru(w, f"{ub}/gru08", net[0], ctx[0], *fine_in)
        delta = conv(w, f"{ub}/flow_head/conv2",
                     jax.nn.relu(conv(w, f"{ub}/flow_head/conv1", net[0])))
        mask = 0.25 * conv(w, f"{ub}/mask_conv2",
                           jax.nn.relu(conv(w, f"{ub}/mask_conv1", net[0])))
        # departure from the paper, shared with the published code: the
        # vertical component of the update is dropped
        disp = disp + delta[..., 0]
        return (tuple(net), disp), convex_upsample(disp, mask, 2 ** nd)

    disp0 = jnp.zeros((b, h8, w8), jnp.float32)
    _, ups = lax.scan(refine, (tuple(net), disp0), None, length=iters)
    return ups


# -------------------------------------------------------------------- loss
def loss_terms(preds, flow_gt, valid, gamma: float, max_flow: float):
    """``sequence_loss`` of ``train_stereo.py:35-69`` before its division:
    (sum over iterations of weight x sum of |prediction - truth| over the
    counted pixels, the count, the last prediction's summed error).  The
    weights are ``gamma ** (15 / (n - 1))`` to the power of the iterations
    still to come; a pixel counts where ``valid`` and |truth| < max_flow.
    The published loss divides by the count over the WHOLE batch, so the
    caller adds the terms of a batch's samples and divides once."""
    n = preds.shape[0]
    adjusted = gamma ** (15.0 / max(n - 1, 1))
    counted = ((valid >= 0.5) & (jnp.abs(flow_gt) < max_flow)
               ).astype(jnp.float32)
    err = jnp.abs(preds - flow_gt[None]) * counted[None]
    weights = adjusted ** jnp.arange(n - 1, -1, -1, dtype=jnp.float32)
    per_iter = jnp.sum(err, axis=(1, 2, 3))
    return jnp.sum(weights * per_iter), jnp.sum(counted), per_iter[-1]


def make_sample_grad(cfg: dict, recipe: dict, lower=None):
    """``fn(arrays, image1, image2, flow, valid, on=True)`` for ONE sample
    (each with a leading axis of 1): the gradient of its loss numerator by
    every ``params/`` array, the numerator, the count and the last
    prediction's summed error.  One sample at a time so that any batch fits
    a chip; the weights are an argument, so one compiled program serves
    every seed.  ``lower`` is the control's or the unit's hook
    (``control.py``, ``straight_through``), and ``on`` — an argument of the
    program, not a constant of it — says whether this call applies it: with
    ``on`` false every product sees its inputs untouched (``where`` picks
    them, bit for bit), so ONE program of 68 MB and ~190 s of compile gives
    both the plain replay and the unit's where two would not fit the chip
    tool's 192 MiB compile cache beside the train step (PERF.md section
    6, PR 32)."""
    iters = recipe["train_iters"]

    def numerator(params, stats, image1, image2, flow, valid, on):
        w = dict(params, **stats)
        if lower is not None:
            w["__lower__"] = lambda a, b: tuple(
                jnp.where(on, q, x) for q, x in zip(lower(a, b), (a, b)))
        preds = predictions(cfg, w, image1, image2, iters)
        num, count, last = loss_terms(preds, flow, valid,
                                      recipe["loss_gamma"],
                                      recipe["max_flow"])
        return num, (count, last)

    def fn(arrays, image1, image2, flow, valid, on=True):
        params = {k: v for k, v in arrays.items() if k.startswith("params/")}
        stats = {k: v for k, v in arrays.items()
                 if not k.startswith("params/")}
        (num, (count, last)), grads = jax.value_and_grad(
            numerator, has_aux=True)(params, stats, image1, image2, flow,
                                     valid, on)
        return grads, num, count, last

    return jax.jit(fn)


def spread_over(sample_grad, devices):
    """``sample_grad`` for ``len(devices)`` samples at once, one a device,
    their terms added: ONE program under ``shard_map``, in which every
    device runs the one-sample gradient on its own sample and a ``psum``
    adds the gradients, numerators and counts.  For a cell that holds
    several chips: its batch is larger and its replay, one sample at a time
    on one chip, would outlast the window.  The sum's order is the only
    thing that differs from the samples taken in turn (float32 rounding of
    eight terms)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("sample",))
    each, same = NamedSharding(mesh, P("sample")), NamedSharding(mesh, P())

    def on_a_device(arrays, image1, image2, flow, valid, on):
        return jax.tree_util.tree_map(
            lambda x: lax.psum(x, "sample"),
            sample_grad(arrays, image1, image2, flow, valid, on))

    mapped = jax.jit(jax.shard_map(
        on_a_device, mesh=mesh,
        in_specs=(P(), P("sample"), P("sample"), P("sample"), P("sample"),
                  P()), out_specs=P(),
        # the reference's loops start from constants, which the checker of
        # varying values would have cast by hand in every carry
        check_vma=False))

    def fn(arrays, image1, image2, flow, valid, on=True):
        return mapped(jax.device_put(arrays, same),
                      *jax.device_put((image1, image2, flow, valid), each),
                      on)

    return fn


def batch_grad(sample_grad, arrays: dict, batch: dict, on: bool = True,
               at_once: int = 1):
    """The batch's loss, its gradient and the last prediction's mean error:
    the samples' terms added, then ONE division by the batch's count.
    ``at_once``: the samples a call of ``sample_grad`` takes (1, or the
    devices of ``spread_over``; it has to divide the batch)."""
    total, num, count, last = None, 0.0, 0.0, 0.0
    n = batch["image1"].shape[0]
    if n % at_once:
        raise ValueError(f"a batch of {n} in calls of {at_once} samples")
    for i in range(0, n, at_once):
        g, n_i, c_i, l_i = sample_grad(
            arrays, *(jnp.asarray(batch[k][i:i + at_once])
                      for k in ("image1", "image2", "flow", "valid")), on)
        total = g if total is None else jax.tree_util.tree_map(
            jnp.add, total, g)
        num, count, last = num + n_i, count + c_i, last + l_i
    denom = jnp.maximum(count, 1.0)
    grads = jax.tree_util.tree_map(lambda x: x / denom, total)
    return grads, num / denom, last / denom


# --------------------------------------------------------------- optimizer
def one_cycle_lr(step: int, recipe: dict) -> float:
    """torch's ``OneCycleLR`` with ``anneal_strategy='linear'`` as
    ``train_stereo.py:77-78`` sets it: over ``num_steps + 100`` steps, from
    ``lr / 25`` up to ``lr`` at step ``0.01 x total - 1``, then down to
    ``lr / 25e4`` at the last.  ``step`` counts the updates already made
    (the first update runs at ``lr / 25``)."""
    total = recipe["num_steps"] + SCHEDULE_EXTRA_STEPS
    peak = recipe["lr"]
    start = peak / DIV_FACTOR
    final = start / FINAL_DIV_FACTOR
    up_end = PCT_START * total - 1.0
    down_end = total - 1.0
    if step <= up_end:
        return start + (peak - start) * step / up_end
    return peak + (final - peak) * min((step - up_end)
                                       / (down_end - up_end), 1.0)


@functools.partial(jax.jit, static_argnames=("clip", "eps", "wdecay"))
def _clip_adamw(params, grads, mu, nu, lr, t, clip, eps, wdecay):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    scale = jnp.minimum(1.0, clip / (norm + 1e-6))
    c1, c2 = 1.0 - BETA1 ** t, 1.0 - BETA2 ** t
    out, mu2, nu2 = {}, {}, {}
    for k, g in grads.items():
        g = g * scale
        mu2[k] = BETA1 * mu[k] + (1.0 - BETA1) * g
        nu2[k] = BETA2 * nu[k] + (1.0 - BETA2) * jnp.square(g)
        adam = (mu2[k] / c1) / (jnp.sqrt(nu2[k] / c2) + eps)
        out[k] = params[k] - lr * (adam + wdecay * params[k])
    return out, mu2, nu2, norm


def adamw_step(arrays: dict, grads: dict, mu: dict, nu: dict, step: int,
               recipe: dict):
    """One update of ``train_stereo.py:174-179``: the gradient clipped to a
    global norm of ``clip_grad_norm`` (torch's ``clip_grad_norm_``: scaled
    by ``max_norm / (norm + 1e-6)`` where that is under 1), then torch's
    AdamW (decoupled decay: the parameter shrinks by ``lr x wdecay`` of
    itself, apart from Adam's step), at the schedule's rate for the
    ``step`` updates already made.  Returns (arrays, mu, nu, the unclipped
    norm)."""
    params = {k: arrays[k] for k in grads}
    new, mu, nu, norm = _clip_adamw(
        params, grads, mu, nu, jnp.float32(one_cycle_lr(step, recipe)),
        jnp.float32(step + 1), clip=recipe["clip_grad_norm"],
        eps=recipe["epsilon"], wdecay=recipe["wdecay"])
    return dict(arrays, **new), mu, nu, norm


def replay(cfg: dict, recipe: dict, arrays: dict, batches, lower=None,
           devices=None):
    """``len(batches)`` steps from ``arrays`` (``weights.make_weights``'s
    table) on ``batches`` (dicts of image1, image2 uint8 or float 0..255,
    flow = minus the disparity, valid, each with the batch axis first).
    Returns the final table, Adam's first and second moments, and per step
    the loss, the last prediction's mean error and the gradient's norm.
    ``lower``: the control's or the unit's hook, applied to every
    product.  ``devices``: more than one takes that many samples at once
    (``spread_over``)."""
    return _replay(*_sample_grad_on(cfg, recipe, lower, devices), recipe,
                   arrays, batches, True)


def replay_pair(cfg: dict, recipe: dict, arrays: dict, batches, lower,
                devices=None):
    """``(replay(...), replay(..., lower))`` from ONE compiled program: the
    plain replay is the lowered one's program with its hook switched
    off."""
    sample_grad, at_once = _sample_grad_on(cfg, recipe, lower, devices)
    return tuple(_replay(sample_grad, at_once, recipe, arrays, batches, on)
                 for on in (False, True))


def _sample_grad_on(cfg: dict, recipe: dict, lower, devices):
    """(the gradient's program, the samples a call of it takes)."""
    sample_grad = make_sample_grad(cfg, recipe, lower)
    if len(devices or ()) < 2:
        return sample_grad, 1
    return spread_over(sample_grad, devices), len(devices)


def _replay(sample_grad, at_once: int, recipe: dict, arrays: dict, batches,
            on: bool):
    params = [k for k in arrays if k.startswith("params/")]
    mu = {k: jnp.zeros_like(arrays[k]) for k in params}
    nu = {k: jnp.zeros_like(arrays[k]) for k in params}
    steps = []
    with jax.default_matmul_precision("highest"):
        for step, batch in enumerate(batches):
            grads, loss, epe = batch_grad(sample_grad, arrays, batch, on,
                                          at_once)
            arrays, mu, nu, norm = adamw_step(arrays, grads, mu, nu, step,
                                              recipe)
            steps.append({"loss": float(loss), "epe": float(epe),
                          "grad_norm": float(norm)})
    return arrays, mu, nu, steps


# ----------------------------------------------------------------- control
def straight_through(lower):
    """``control.LOWER[...]``'s rounding with the identity's derivative:
    ``x + stop_gradient(q(x) - x)``.  ``control._fake_int8`` rounds, and a
    rounding's derivative is nought, so the plain hook would cut every
    gradient; with this one the forward sees the lowered inputs and the
    backward passes through them, as a lower-precision training step
    does."""
    def lowered(a, b):
        qa, qb = lower(a, b)
        return (a + lax.stop_gradient(qa - a), b + lax.stop_gradient(qb - b))
    return lowered
