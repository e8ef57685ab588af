"""The plain reference: RAFT-Stereo's test-mode forward in straight
``jax.numpy``, float32, every product at ``highest`` precision.

Written from the paper's equations (Lipson, Teed, Deng: RAFT-Stereo,
arXiv 2109.07547, §3) and the published module layout; it imports nothing
of the program under test and takes nothing the program made: weights come
from ``weights.make_weights(cfg, seed)``.  No kernels, no cache, no batching: one
pair at a time, one plain loop over the iterations.

One definition serves both lookups the program has: the all-pairs volume
``C[h, w, v] = <f1[h, w], f2[h, v]> / sqrt(D)``, average-pooled along ``v``
into a pyramid and sampled linearly in a window of ``2r+1`` taps a level.
The program's no-volume lookup (``alt``) pools the right FEATURES and takes
the products per tap, which is the same number: pooling is linear.

Departure from the paper, shared with the published code: the vertical
component of every update is dropped (disparity is horizontal), so the
state is a single x-field.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.weights import trunk_strides

HI = lax.Precision.HIGHEST
EPS = 1e-5


# ------------------------------------------------------------------ layers
def _lowered(w, a, b):
    """The control's hook (``control.py``): where the weight table carries a
    ``__lower__`` function, both inputs of every product go through it
    first.  The reference itself never sets one."""
    lower = w.get("__lower__")
    return lower(a, b) if lower else (a, b)


def conv(w, path, x, stride=1):
    x, k = _lowered(w, x, w[f"params/{path}/kernel"])
    pad = [(k.shape[0] // 2,) * 2, (k.shape[1] // 2,) * 2]
    y = lax.conv_general_dilated(
        x, k, (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
    return y + w[f"params/{path}/bias"]


def norm(w, path, kind, x):
    if kind == "instance":
        mean = jnp.mean(x, axis=(1, 2), keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=(1, 2), keepdims=True)
        return (x - mean) / jnp.sqrt(var + EPS)
    # batch norm evaluated with its stored statistics
    inv = w[f"params/{path}/scale"] / jnp.sqrt(
        w[f"batch_stats/{path}/var"] + EPS)
    return (x - w[f"batch_stats/{path}/mean"]) * inv + w[f"params/{path}/bias"]


def res_block(w, path, kind, x, stride):
    y = jax.nn.relu(norm(w, f"{path}/norm1", kind,
                         conv(w, f"{path}/conv1", x, stride)))
    y = jax.nn.relu(norm(w, f"{path}/norm2", kind,
                         conv(w, f"{path}/conv2", y)))
    if f"params/{path}/downsample_conv/kernel" in w:
        x = norm(w, f"{path}/norm3", kind,
                 conv(w, f"{path}/downsample_conv", x, stride))
    return jax.nn.relu(x + y)


def trunk(w, path, kind, x, n_downsample):
    s = trunk_strides(n_downsample)
    x = jax.nn.relu(norm(w, f"{path}/norm1", kind,
                         conv(w, f"{path}/conv1", x, s[0])))
    for i in (1, 2, 3):
        x = res_block(w, f"{path}/layer{i}_0", kind, x, s[i])
        x = res_block(w, f"{path}/layer{i}_1", kind, x, 1)
    return x


def pool2x(x):
    """3x3 mean, stride 2, one pixel of zero padding, divisor 9."""
    s = lax.reduce_window(x, 0.0, lax.add, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    return s / 9.0


def _interp_axis(x, axis, dst):
    """Bilinear resize along one axis, corners aligned."""
    src = x.shape[axis]
    if src == dst:
        return x
    pos = jnp.arange(dst, dtype=jnp.float32) * ((src - 1) / max(dst - 1, 1))
    lo = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, src - 1)
    hi = jnp.clip(lo + 1, 0, src - 1)
    frac = (pos - lo).reshape([-1 if a == axis else 1 for a in range(x.ndim)])
    return (jnp.take(x, lo, axis=axis) * (1.0 - frac)
            + jnp.take(x, hi, axis=axis) * frac)


def interp_to(x, like):
    return _interp_axis(_interp_axis(x, 1, like.shape[1]), 2, like.shape[2])


def conv_gru(w, path, h, ctx, *xs):
    cz, cr, cq = ctx
    x = jnp.concatenate(xs, axis=-1)
    zr = conv(w, f"{path}/convzr", jnp.concatenate([h, x], axis=-1))
    hd = h.shape[-1]
    z = jax.nn.sigmoid(zr[..., :hd] + cz)
    r = jax.nn.sigmoid(zr[..., hd:] + cr)
    q = jnp.tanh(conv(w, f"{path}/convq",
                      jnp.concatenate([r * h, x], axis=-1)) + cq)
    return (1.0 - z) * h + z * q


# ------------------------------------------------------------- correlation
def corr_pyramid(w, f1, f2, levels):
    f1, f2 = _lowered(w, f1, f2)
    vol = jnp.einsum("bhwd,bhvd->bhwv", f1, f2, precision=HI)
    vol = vol / math.sqrt(f1.shape[-1])
    pyr = [vol]
    for _ in range(levels - 1):
        v = pyr[-1]
        n = (v.shape[-1] // 2) * 2
        pyr.append(0.5 * (v[..., 0:n:2] + v[..., 1:n:2]))
    return pyr


def sample_linear(vol, x):
    """``vol`` (..., W) at real positions ``x`` (..., K); nought outside."""
    wd = vol.shape[-1]
    x0 = jnp.floor(x)
    frac = x - x0
    i0 = x0.astype(jnp.int32)

    def tap(i):
        ok = (i >= 0) & (i <= wd - 1)
        v = jnp.take_along_axis(vol, jnp.clip(i, 0, wd - 1), axis=-1)
        return jnp.where(ok, v, 0.0)

    return tap(i0) * (1.0 - frac) + tap(i0 + 1) * frac


def corr_lookup(pyr, coords, radius):
    dx = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    return jnp.concatenate(
        [sample_linear(v, coords[..., None] / (2 ** i) + dx)
         for i, v in enumerate(pyr)], axis=-1)


# ---------------------------------------------------------------- upsample
def convex_upsample(disp, mask, f):
    b, h, w = disp.shape
    m = jax.nn.softmax(mask.reshape(b, h, w, 9, f, f), axis=3)
    dp = jnp.pad(disp * f, ((0, 0), (1, 1), (1, 1)))
    taps = jnp.stack([dp[:, ky:ky + h, kx:kx + w]
                      for ky in range(3) for kx in range(3)], axis=3)
    up = jnp.einsum("bhwkyx,bhwk->bhywx", m, taps, precision=HI)
    return up.reshape(b, h * f, w * f)


# ----------------------------------------------------------------- forward
def forward(cfg: dict, w: dict, image1, image2, iters: int):
    """(B, H, W, 3) images in 0..255, H and W multiples of 32, to the
    full-resolution x-flow (B, H, W) after ``iters`` refinements (the
    disparity is its negative)."""
    n, nd = cfg["n_gru_layers"], cfg["n_downsample"]
    im1 = 2.0 * (image1.astype(jnp.float32) / 255.0) - 1.0
    im2 = 2.0 * (image2.astype(jnp.float32) / 255.0) - 1.0
    cnorm = cfg["context_norm"]

    if cfg["shared_backbone"]:
        v = trunk(w, "cnet/trunk", cnorm, jnp.concatenate([im1, im2]), nd)
        fmap = conv(w, "conv2_out", res_block(w, "conv2_res", "instance",
                                              v, 1))
        f1, f2 = jnp.split(fmap, 2)
        x = v[: v.shape[0] // 2]
    else:
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
    disp = jnp.zeros((b, h8, w8), jnp.float32)
    ub = "update_block"
    mask0 = jnp.zeros((b, h8, w8, 9 * (2 ** nd) ** 2), jnp.float32)

    def refine(_, state):
        net, disp, _mask = state
        net = list(net)
        corr = corr_lookup(pyr, grid + disp, cfg["corr_radius"])
        flow2 = jnp.stack([disp, jnp.zeros_like(disp)], axis=-1)
        if cfg["slow_fast_gru"]:
            # extra updates of the coarser levels alone
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

    # one traced iteration, repeated: the compiler sees the body once
    net, disp, mask = lax.fori_loop(0, iters, refine,
                                    (tuple(net), disp, mask0))
    return convex_upsample(disp, mask, 2 ** nd)


# ------------------------------------------------------------ whole answer
def pad_amounts(h: int, w: int, divis_by: int = 32):
    """(top, bottom, left, right) of the published evaluation padder:
    replicate the edges up to the next multiple, split evenly."""
    ph = (-h) % divis_by
    pw = (-w) % divis_by
    return ph // 2, ph - ph // 2, pw // 2, pw - pw // 2


def disparity(cfg: dict, w: dict, left, right, iters: int):
    """One (H, W, 3) uint8 pair to its (H, W) float32 x-flow, as a client
    of the published evaluation gets it: pad, forward, crop."""
    h, wd = left.shape[:2]
    t, bt, l, r = pad_amounts(h, wd)
    spec = ((t, bt), (l, r), (0, 0))
    p1 = jnp.pad(jnp.asarray(left), spec, mode="edge")[None]
    p2 = jnp.pad(jnp.asarray(right), spec, mode="edge")[None]
    flow = forward(cfg, w, p1, p2, iters)[0]
    return flow[t:t + h, l:l + wd]
