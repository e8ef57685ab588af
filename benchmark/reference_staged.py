"""The plain reference for pairs that ``reference.disparity`` cannot hold in
one program: the same layer functions of ``reference.py`` in the same
order, run as four programs instead of one.

At 1984x2880 one jitted ``reference.disparity`` asks the v5e's compiler for
27.97 GB of HBM against 15.75 (my chip run, PR 28): the feature trunk runs
on both images at once at full image resolution, and five float32
activations of 64 channels (each padded to 128 lanes: 5.46 GB) are live
together.  Here each trunk is a program of its own on ONE image (the
context trunk on the left, the feature trunk on the left, then on the
right: 8.8e9 and 11.7e9 B by the compiler's own count), and what follows
them, at 1/4 resolution, is the fourth.  A trunk's instance norm is per
image and its batch norm per pixel, so an image by itself reads what it
reads in a batch of two.

``tests/test_fullres_config.py`` holds this module to ``reference.py``
on the CPU at a small size: the two must agree to float32's last bits.
Only the copy of ``reference.forward``'s second half below can drift from
it; that test is its guard.  Configurations with a shared backbone are not
taken (their feature maps come from the context trunk on both images).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import (conv, conv_gru, convex_upsample,
                                 corr_lookup, corr_pyramid, interp_to,
                                 pad_amounts, pool2x, res_block, trunk)


def _table(arrays: dict, lower):
    """The weight table as ``reference.py``'s layers take it: with the
    control's hook (``control.py``) where there is one."""
    return dict(arrays, __lower__=lower) if lower else arrays


def _after_trunks(cfg: dict, w: dict, x, t1, t2, iters: int):
    """``reference.forward`` from the trunks' outputs on: ``x`` the context
    trunk of the left image, ``t1`` / ``t2`` the feature trunks of the left
    and the right, each (1, H/f, W/f, 128)."""
    n, nd = cfg["n_gru_layers"], cfg["n_downsample"]
    cnorm = cfg["context_norm"]
    f1, f2 = jnp.split(conv(w, "fnet/conv2", jnp.concatenate([t1, t2])), 2)

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
    disp = jnp.zeros((b, h8, w8), jnp.float32)
    ub = "update_block"
    mask0 = jnp.zeros((b, h8, w8, 9 * (2 ** nd) ** 2), jnp.float32)

    def refine(_, state):
        net, disp, _mask = state
        net = list(net)
        corr = corr_lookup(pyr, grid + disp, cfg["corr_radius"])
        flow2 = jnp.stack([disp, jnp.zeros_like(disp)], axis=-1)
        if cfg["slow_fast_gru"]:
            if n == 3:
                net[2] = conv_gru(w, f"{ub}/gru32", net[2], ctx[2],
                                  pool2x(net[1]))
                net[2] = conv_gru(w, f"{ub}/gru32", net[2], ctx[2],
                                  pool2x(net[1]))
                net[1] = conv_gru(w, f"{ub}/gru16", net[1], ctx[1],
                                  pool2x(net[0]), interp_to(net[2], net[1]))
            elif n == 2:
                net[1] = conv_gru(w, f"{ub}/gru16", net[1], ctx[1],
                                  pool2x(net[0]))
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
        disp = disp + delta[..., 0]
        return tuple(net), disp, mask

    net, disp, mask = lax.fori_loop(0, iters, refine,
                                    (tuple(net), disp, mask0))
    return convex_upsample(disp, mask, 2 ** nd)


def make_disparity(cfg: dict, iters: int, lower=None):
    """``fn(arrays, left, right)``: one (H, W, 3) uint8 pair to its (H, W)
    float32 x-flow, as ``reference.disparity`` gives it, with the weights
    an argument of every program (one compiled reference serves every seed
    from the compile cache).  ``lower`` is ``control.LOWER[...]`` for the
    unit or the control, else None.  The caller does not jit ``fn``: it
    runs its four programs one after the other, and each trunk's
    full-resolution activations are freed before the next starts."""
    if cfg["shared_backbone"]:
        raise ValueError("reference_staged: no shared backbone (its "
                         "feature maps come from the context trunk)")
    nd = cfg["n_downsample"]

    def trunk_of(path: str, kind: str):
        def run(arrays, image):
            im = 2.0 * (image[None].astype(jnp.float32) / 255.0) - 1.0
            return trunk(_table(arrays, lower), path, kind, im, nd)
        return jax.jit(run)

    context = trunk_of("cnet/trunk", cfg["context_norm"])
    feature = trunk_of("fnet/trunk", cfg["fnet_norm"])
    rest = jax.jit(lambda arrays, x, t1, t2: _after_trunks(
        cfg, _table(arrays, lower), x, t1, t2, iters))

    def disparity(arrays, left, right):
        h, wd = left.shape[:2]
        t, bt, l, r = pad_amounts(h, wd)
        spec = ((t, bt), (l, r), (0, 0))
        p1 = jnp.pad(jnp.asarray(left), spec, mode="edge")
        p2 = jnp.pad(jnp.asarray(right), spec, mode="edge")
        outs = []
        for one_trunk, image in ((context, p1), (feature, p1),
                                 (feature, p2)):
            outs.append(jax.block_until_ready(one_trunk(arrays, image)))
        flow = rest(arrays, *outs)[0]
        return flow[t:t + h, l:l + wd]

    return disparity
