"""IGEV-Stereo, test-mode inference (Xu, Wang, Yang, Zhang: "Iterative
Geometry Encoding Volume for Stereo Matching", CVPR 2023, arXiv 2303.06615;
github.com/gangweiX/IGEV core/igev_stereo.py, extractor.py, update.py,
geometry.py, submodule.py).

What it shares with RAFT-Stereo (models/raft_stereo.py): the context
encoder (``MultiBasicEncoder``, batch norm, 1/4), the context convs, the
``ConvGRU`` levels with their fused gate kernel, the 3x3 pool and the
align-corners interp between levels, the refinement loop as an ``nn.scan``
under the ``gru_iter`` scope, and the runner: ``eval/runner.make_forward``
calls both alike, and ``apply(..., test_mode=True)[1]`` is the
full-resolution x-flow, the NEGATIVE of the disparity, as RAFT-Stereo's.
Its own:

* a MobileNetV2 trunk (timm ``mobilenetv2_100``: stem and blocks 0-5,
  depthwise 3x3 convolutions, ReLU6) decoded top-down to 1/4 by
  transposed convolutions with instance norm, beside a two-conv stem at
  1/2 and 1/4 whose 1/4 output joins the trunk's (96 channels);
* the group-wise correlation volume of ``max_disp // 4`` bins, regularized
  by a 3-D hourglass with feature excitations into the geometry encoding
  volume (GEV), and the initial disparity regressed from it (soft-argmax);
* the combined lookup (``kernels/corr_lookup.lookup_geo_fused``): the GEV
  at the disparity and the all-pairs volume at ``x - d``, in float32;
* the update block's motion encoder for a 1-channel disparity, the
  disparity head and the 1/4 mask features, and the upsampler: a
  transposed conv to 1/2 joined with the stem's 1/2 output, a transposed
  conv to full resolution giving 9 weights a pixel, and the weighted sum of
  the 3x3 neighbourhood of ``4 d`` taken nearest.

Layouts: NHWC; the 3-D stage NDHWC.  A transposed convolution's kernel is
stored as the kernel of the equivalent input-dilated convolution (the
source's ``ConvTranspose`` weight flipped in space with its two channel
axes swapped: an importer's business; nothing imports IGEV weights yet).
Batch norm is evaluated with its stored statistics, as at test time.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from raft_stereo_tpu.config import IgevStereoConfig
from raft_stereo_tpu.kernels import corr_lookup
from raft_stereo_tpu.models.extractor import (MultiBasicEncoder, conv,
                                              kaiming_out)
from raft_stereo_tpu.models.norm import FrozenBatchNorm, InstanceNorm
from raft_stereo_tpu.models.update import ConvGRU
from raft_stereo_tpu.ops.pooling import pool2x
from raft_stereo_tpu.ops.resize import interp_like
from raft_stereo_tpu.profiling import annotate

# torch's ``nn.LeakyReLU()`` default, which the source's conv blocks use
LEAKY_SLOPE = 0.01
# timm mobilenetv2_100's blocks 0-5: (channels, repeats, first stride,
# expansion); the source takes the trunk's outputs after blocks 0, 1, 2,
# 4 and 5 (1/2 .. 1/32; blocks 3 and 4 are one stage)
MOBILENET_V2 = ((16, 1, 1, 1), (24, 2, 2, 6), (32, 3, 2, 6), (64, 4, 2, 6),
                (96, 3, 1, 6), (160, 3, 2, 6))


def _act(kind: Optional[str], x):
    if kind == "lrelu":
        return nn.leaky_relu(x, LEAKY_SLOPE)
    if kind == "relu":
        return nn.relu(x)
    if kind == "relu6":
        return jnp.clip(x, 0.0, 6.0)
    return x


class Conv(nn.Module):
    """A 2-D or 3-D convolution (as many spatial axes as ``kernel`` has),
    ``padding = k // 2``, or with ``deconv`` the transposed one of kernel 4,
    stride 2 and padding 1 (twice the input's size); then a norm
    ("batch" | "instance" | None) and an activation."""

    features: int
    kernel: Tuple[int, ...]
    stride: int = 1
    deconv: bool = False
    groups: int = 1
    bias: bool = False
    norm: Optional[str] = None
    act: Optional[str] = None
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        nd = len(self.kernel)
        dtype = self.dtype or x.dtype
        kernel = self.param(
            "kernel", kaiming_out,
            tuple(self.kernel) + (x.shape[-1] // self.groups, self.features))
        sp = "DHW"[-nd:]
        dn = ("N" + sp + "C", sp + "IO", "N" + sp + "C")
        if self.deconv:
            y = lax.conv_general_dilated(
                x.astype(dtype), kernel.astype(dtype), (1,) * nd,
                [(k - 2, k - 2) for k in self.kernel],
                lhs_dilation=(2,) * nd, dimension_numbers=dn)
        else:
            y = lax.conv_general_dilated(
                x.astype(dtype), kernel.astype(dtype), (self.stride,) * nd,
                [(k // 2, k // 2) for k in self.kernel],
                dimension_numbers=dn, feature_group_count=self.groups)
        if self.bias:
            y = y + self.param("bias", nn.initializers.zeros,
                               (self.features,)).astype(dtype)
        if self.norm == "batch":
            y = FrozenBatchNorm(dtype=dtype, name="bn")(y)
        elif self.norm == "instance":
            y = InstanceNorm(dtype=dtype, name="in")(y)
        return _act(self.act, y)


class InvertedResidual(nn.Module):
    """MobileNetV2's block: 1x1 expansion (none at expansion 1), depthwise
    3x3, 1x1 projection, each with batch norm; ReLU6 after the first two;
    the skip where the shape stays."""

    features: int
    stride: int
    expansion: int
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        cin, d = x.shape[-1], self.dtype
        y = x
        if self.expansion != 1:
            y = Conv(cin * self.expansion, (1, 1), norm="batch", act="relu6",
                     dtype=d, name="conv_pw")(y)
        y = Conv(y.shape[-1], (3, 3), self.stride, groups=y.shape[-1],
                 norm="batch", act="relu6", dtype=d, name="conv_dw")(y)
        y = Conv(self.features, (1, 1), norm="batch", dtype=d,
                 name="conv_pwl")(y)
        return x + y if self.stride == 1 and cin == self.features else y


class Conv2x(nn.Module):
    """Up one scale: a transposed conv, the skip concatenated, a 3x3 conv to
    twice the width (the source's ``Conv2x`` / ``Conv2x_IN``)."""

    features: int
    norm: str
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x, skip):
        x = Conv(self.features, (4, 4), deconv=True, norm=self.norm,
                 act="lrelu", dtype=self.dtype, name="conv1")(x)
        return Conv(2 * self.features, (3, 3), norm=self.norm, act="lrelu",
                    dtype=self.dtype, name="conv2")(
            jnp.concatenate([x, skip.astype(x.dtype)], axis=-1))


class Feature(nn.Module):
    """The MobileNetV2 trunk and its decoder: (x4 48, x8 64, x16 192,
    x32 160 channels)."""

    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        d = self.dtype
        x = Conv(32, (3, 3), 2, norm="batch", act="relu6", dtype=d,
                 name="conv_stem")(x)
        outs = []
        for b, (ch, repeats, stride, expansion) in enumerate(MOBILENET_V2):
            for j in range(repeats):
                x = InvertedResidual(ch, stride if j == 0 else 1, expansion,
                                     dtype=d, name=f"blocks_{b}_{j}")(x)
            if b != 3:
                outs.append(x)
        _, x4, x8, x16, x32 = outs
        x16 = Conv2x(96, "instance", d, name="deconv32_16")(x32, x16)
        x8 = Conv2x(32, "instance", d, name="deconv16_8")(x16, x8)
        x4 = Conv2x(24, "instance", d, name="deconv8_4")(x8, x4)
        x4 = Conv(48, (3, 3), norm="instance", act="lrelu", dtype=d,
                  name="conv4")(x4)
        return x4, x8, x16, x32


class Stem(nn.Module):
    """A stride-2 conv with instance norm and LeakyReLU, then a conv with
    instance norm and ReLU (the source's ``stem_2`` / ``stem_4``)."""

    features: int
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        x = Conv(self.features, (3, 3), 2, norm="instance", act="lrelu",
                 dtype=self.dtype, name="conv1")(x)
        return Conv(self.features, (3, 3), norm="instance", act="relu",
                    dtype=self.dtype, name="conv2")(x)


class FeatureAtt(nn.Module):
    """Guided excitation: the volume times sigmoid(1x1 -> BN LeakyReLU ->
    1x1 of the image features), broadcast over the disparity axis."""

    channels: int
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, cv, feat):
        a = Conv(feat.shape[-1] // 2, (1, 1), norm="batch", act="lrelu",
                 dtype=self.dtype, name="conv1")(feat)
        a = Conv(self.channels, (1, 1), bias=True, dtype=self.dtype,
                 name="conv2")(a)
        return jax.nn.sigmoid(a)[:, None] * cv


class Hourglass(nn.Module):
    """The 3-D cost aggregation (the source's ``hourglass(8)``): three
    stride-2 stages with excitations from the 1/8, 1/16 and 1/32 features,
    two transposed stages with their skips, and a last transposed conv back
    to 8 channels at 1/4."""

    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x, f8, f16, f32):
        d, c = self.dtype, x.shape[-1]

        def block(y, ch, k, stride, name):
            return Conv(ch, (k,) * 3, stride, norm="batch", act="lrelu",
                        dtype=d, name=name)(y)

        def up(y, ch, name):
            return Conv(ch, (4, 4, 4), deconv=True, norm="batch",
                        act="lrelu", dtype=d, name=name)(y)

        conv1 = block(block(x, 2 * c, 3, 2, "conv1_0"), 2 * c, 3, 1, "conv1_1")
        conv1 = FeatureAtt(2 * c, d, name="feature_att_8")(conv1, f8)
        conv2 = block(block(conv1, 4 * c, 3, 2, "conv2_0"), 4 * c, 3, 1,
                      "conv2_1")
        conv2 = FeatureAtt(4 * c, d, name="feature_att_16")(conv2, f16)
        conv3 = block(block(conv2, 6 * c, 3, 2, "conv3_0"), 6 * c, 3, 1,
                      "conv3_1")
        conv3 = FeatureAtt(6 * c, d, name="feature_att_32")(conv3, f32)
        conv2 = jnp.concatenate([up(conv3, 4 * c, "conv3_up"), conv2], -1)
        for i, k in enumerate((1, 3, 3)):
            conv2 = block(conv2, 4 * c, k, 1, f"agg_0_{i}")
        conv2 = FeatureAtt(4 * c, d, name="feature_att_up_16")(conv2, f16)
        conv1 = jnp.concatenate([up(conv2, 2 * c, "conv2_up"), conv1], -1)
        for i, k in enumerate((1, 3, 3)):
            conv1 = block(conv1, 2 * c, k, 1, f"agg_1_{i}")
        conv1 = FeatureAtt(2 * c, d, name="feature_att_up_8")(conv1, f8)
        return Conv(8, (4, 4, 4), deconv=True, dtype=d, name="conv1_up")(conv1)


def group_correlation(left, right, bins: int, groups: int):
    """(B, H, W, C) matching features → (B, bins, H, W, groups): the mean
    over each group's channels of ``left[x] * right[x - d]``, nought where
    ``x < d`` (the source's ``build_gwc_volume``)."""
    b, h, w, c = left.shape
    padded = jnp.pad(right, ((0, 0), (0, 0), (bins, 0), (0, 0)))

    def one(d):
        # one bin at a time: all bins at once hold (bins, C) products a
        # pixel, 276 MB a KITTI pair in bfloat16
        shifted = lax.dynamic_slice(padded, (0, 0, bins - d, 0),
                                    (b, h, w, c))
        return jnp.mean((left * shifted).reshape(b, h, w, groups,
                                                 c // groups), axis=-1)

    return jnp.moveaxis(lax.map(one, jnp.arange(bins)), 0, 1)


def _pool_last2(v):
    """Average of bin pairs along the second-to-last axis (floor)."""
    n = (v.shape[-2] // 2) * 2
    return 0.5 * (v[..., 0:n:2, :] + v[..., 1:n:2, :])


def _window_xla(vol, centres, radius: int):
    """``vol`` (..., N, W1) sampled along N at ``centres`` (..., W1) + k,
    k = -r..r: linear, nought outside → (..., 2r+1, W1).  The combined
    lookup where its kernel does not run (the CPU, or blocks too large)."""
    n = vol.shape[-2]
    x = centres[..., None, :] + jnp.arange(
        -radius, radius + 1, dtype=jnp.float32)[:, None]
    x0 = jnp.floor(x)
    a = x - x0
    i0 = x0.astype(jnp.int32)

    def tap(i):
        v = jnp.take_along_axis(vol, jnp.clip(i, 0, n - 1), axis=-2)
        return jnp.where((i >= 0) & (i < n), v, 0.0)

    return tap(i0) * (1.0 - a) + tap(i0 + 1) * a


def lookup_geo_xla(gev_pyramid, vol_pyramid, disp, radius: int):
    """The same numbers as ``corr_lookup.lookup_geo_fused``, by gathers."""
    b, h, w1 = disp.shape
    x = jnp.arange(w1, dtype=jnp.float32)
    out = []
    for i, (gev, vol) in enumerate(zip(gev_pyramid, vol_pyramid)):
        s = 1.0 / 2 ** i
        g = gev.shape[2]
        taps = _window_xla(gev, jnp.broadcast_to(
            (disp * s)[:, :, None], (b, h, g, w1)), radius)
        out.append(jnp.moveaxis(taps, -1, 2).reshape(b, h, w1, -1))
        taps = _window_xla(vol, (x - disp) * s, radius)
        out.append(jnp.swapaxes(taps, -1, -2))
    return jnp.concatenate(out, axis=-1)


class MotionEncoder(nn.Module):
    """Lookup features and the disparity into 128 motion channels (the
    source's ``BasicMotionEncoder``: ``convd1`` sees the 1-channel
    disparity)."""

    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, disp, corr):
        d = self.dtype
        cor = nn.relu(conv(64, 1, 1, dtype=d, name="convc1")(corr))
        cor = nn.relu(conv(64, 3, 1, dtype=d, name="convc2")(cor))
        dis = nn.relu(conv(64, 7, 1, dtype=d, name="convd1")(disp))
        dis = nn.relu(conv(64, 3, 1, dtype=d, name="convd2")(dis))
        out = nn.relu(conv(127, 3, 1, dtype=d, name="conv")(
            jnp.concatenate([cor, dis], axis=-1)))
        return jnp.concatenate([out, disp], axis=-1)


class UpdateBlock(nn.Module):
    """The source's ``BasicMultiUpdateBlock``: RAFT-Stereo's three coupled
    ConvGRUs (level 0 the finest: ``gru04`` at 1/4, ``gru08``, ``gru16``),
    the disparity head, and ``mask_features``: the 32 mask features at 1/4,
    which only the upsampler after the loop reads."""

    config: IgevStereoConfig
    dtype: Optional[Any] = None

    def setup(self):
        self.mask_feat_4 = conv(32, 3, 1, dtype=self.dtype, name=None)

    def mask_features(self, h):
        """The upsampler's features of the final finest hidden state."""
        return nn.relu(self.mask_feat_4(h))

    @nn.compact
    def __call__(self, net, context, corr, disp):
        hd, n, d = self.config.hidden_dims, self.config.n_gru_layers, self.dtype
        fused = self.config.fused_gru
        net = list(net)
        if n == 3:
            net[2] = ConvGRU(hd[2], dtype=d, fused=fused, name="gru16")(
                net[2], context[2], pool2x(net[1]))
        if n >= 2:
            coupled = [pool2x(net[0])] + (
                [interp_like(net[2], net[1])] if n == 3 else [])
            net[1] = ConvGRU(hd[1], dtype=d, fused=fused, name="gru08")(
                net[1], context[1], *coupled)
        motion = MotionEncoder(d, name="encoder")(disp, corr)
        fine = [motion] + ([interp_like(net[1], net[0])] if n > 1 else [])
        net[0] = ConvGRU(hd[0], dtype=d, fused=fused, name="gru04")(
            net[0], context[0], *fine)
        delta = conv(1, 3, 1, dtype=d, name="disp_head_conv2")(nn.relu(
            conv(256, 3, 1, dtype=d, name="disp_head_conv1")(net[0])))
        return net, delta


def _over_pairs(method):
    """``method(module, *arrays)`` run a pair at a time (an ``nn.scan`` over
    the batch axis), its results stacked back.  The 3-D stage's volumes have
    8 channels, the upsampler's weights 9: at a batch of 8 and more the v5e
    compiler lays such arrays out with the batch and the channels as the
    tiled minor pair, padded 16-fold (a batch-8 program needed 10.2e9 B, a
    batch-6 one 2.5e9: compile facts, PERF.md section 4); one pair at a time
    they keep the layouts of a small batch, whatever the batch."""

    def body(module, _, xs):
        out = method(module, *(x[None] for x in xs))
        return None, jax.tree_util.tree_map(lambda y: y[0], out)

    scan = nn.scan(body, variable_broadcast=("params", "batch_stats"),
                   split_rngs={"params": False})
    return lambda module, *xs: scan(module, None, xs)[1]


class IGEVStereo(nn.Module):
    config: IgevStereoConfig

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.config.mixed_precision else jnp.float32

    def setup(self):
        cfg, d = self.config, self.compute_dtype
        self.feature = Feature(d, name="feature")
        self.stem_2 = Stem(32, d, name="stem_2")
        self.stem_4 = Stem(48, d, name="stem_4")
        self.conv = Conv(96, (3, 3), norm="instance", act="lrelu", dtype=d,
                         name="conv")
        self.desc = Conv(96, (1, 1), bias=True, dtype=d, name="desc")
        self.corr_stem = Conv(cfg.gwc_groups, (3, 3, 3), norm="batch",
                              act="lrelu", dtype=d, name="corr_stem")
        self.corr_feature_att = FeatureAtt(cfg.gwc_groups, d,
                                           name="corr_feature_att")
        self.cost_agg = Hourglass(d, name="cost_agg")
        self.classifier = Conv(1, (3, 3, 3), dtype=d, name="classifier")
        self.cnet = MultiBasicEncoder(
            output_dims=(cfg.hidden_dims, cfg.hidden_dims),
            norm_fn=cfg.context_norm, downsample=cfg.n_downsample,
            num_layers=cfg.n_gru_layers, dtype=d, name="cnet")
        self.context_zqr_convs = [
            conv(3 * cfg.hidden_dims[l], 3, 1, dtype=d,
                 name=f"context_zqr_conv{l}")
            for l in range(cfg.n_gru_layers)]
        self.update_block = UpdateBlock(cfg, d, name="update_block")
        self.spx_2_gru = Conv2x(32, "batch", d, name="spx_2_gru")
        self.spx_gru = Conv(9, (4, 4), deconv=True, bias=True, dtype=d,
                            name="spx_gru")

    def __call__(self, image1, image2, iters: int = 12,
                 test_mode: bool = True):
        """(B, H, W, 3) images in 0..255, H and W multiples of 32 →
        ``(flow at 1/4, flow at full resolution)``, each the negative of
        the disparity, after ``iters`` refinements.  Test mode only."""
        if not test_mode:
            raise ValueError("IGEV-Stereo runs in test mode only here: "
                             "nothing in training/ builds it")
        cfg, dtype = self.config, self.compute_dtype
        f32 = jnp.float32
        b = image1.shape[0]
        with annotate("prep"):
            images = jnp.concatenate([image1, image2]).astype(f32)
            images = (2.0 * (images / 255.0) - 1.0).astype(dtype)
        with annotate("features"):
            x4, x8, x16, x32 = self.feature(images)
            stem2 = self.stem_2(images)
            f4 = jnp.concatenate([x4, self.stem_4(stem2)], axis=-1)
            match = self.desc(self.conv(f4))
        bins, groups = cfg.disp_bins, cfg.gwc_groups
        with annotate("gev"):
            ml, mr = match[:b], match[b:]
            corr_lookup.log_path_once(
                f"IGEV 3-D stage {jnp.dtype(dtype).name}: {bins} bins x "
                f"{groups} groups at {ml.shape[1]}x{ml.shape[2]}, a pair at "
                f"a time")
            disp, gev_pyr, vol_pyr = _over_pairs(IGEVStereo._volumes)(
                self, ml, mr, f4[:b], x8[:b], x16[:b], x32[:b])
        with annotate("gru_iter"):
            lookup = self._lookup(gev_pyr, vol_pyr)

        with annotate("cnet"):
            levels, _ = self.cnet(images[:b])
            net = [jnp.tanh(lv[0]) for lv in levels]
            context = [tuple(jnp.split(zqr(nn.relu(lv[1])), 3, axis=-1))
                       for zqr, lv in zip(self.context_zqr_convs, levels)]

        def body(module, carry, _):
            net, disp = carry
            with annotate("gru_iter"):
                corr = lookup(lax.stop_gradient(disp)).astype(dtype)
                net, delta = module.update_block(
                    net, context, corr, disp[..., None].astype(dtype))
                disp = disp + delta[..., 0].astype(f32)
            return (tuple(net), disp), None

        scan = nn.scan(body, variable_broadcast=("params", "batch_stats"),
                       split_rngs={"params": False}, length=iters)
        with annotate("gru_iter"):
            (net, disp), _ = scan(self, (tuple(net), disp), None)
        if not self.is_initializing():
            corr_lookup.log_path_once(f"upsampling mask: once after the "
                                      f"loop ({iters} iterations)")
        with annotate("upsample"):
            mask = self.update_block.mask_features(net[0])
            up = _over_pairs(IGEVStereo._upsample)(self, disp, mask,
                                                    stem2[:b])
            return -disp, -up

    def _volumes(self, ml, mr, f4, f8, f16, f32):
        """One pair's steps 3-7: the group-wise volume, the 3-D stage, the
        initial disparity, and the lookup's two pyramids, float32 as the
        source casts them: the GEV as (1, H, G, D, W) and the all-pairs
        volume transposed, (1, H, W2, W1), each pooled along its bins."""
        cfg, f32_ = self.config, jnp.float32
        bins = cfg.disp_bins
        gwc = group_correlation(ml, mr, bins, cfg.gwc_groups).astype(
            self.compute_dtype)
        gwc = self.corr_feature_att(self.corr_stem(gwc), f4)
        gev = self.cost_agg(gwc, f8, f16, f32)
        prob = jax.nn.softmax(self.classifier(gev)[..., 0].astype(f32_),
                              axis=1)
        disp = jnp.sum(prob * jnp.arange(bins, dtype=f32_)[:, None, None],
                       axis=1)
        gev_pyr = [jnp.transpose(gev.astype(f32_), (0, 2, 4, 1, 3))]
        vol_pyr = [jnp.einsum("bhvc,bhwc->bhvw", mr.astype(f32_),
                              ml.astype(f32_),
                              precision=lax.Precision.HIGHEST)]
        for _ in range(cfg.corr_levels - 1):
            gev_pyr.append(_pool_last2(gev_pyr[-1]))
            vol_pyr.append(_pool_last2(vol_pyr[-1]))
        return disp, gev_pyr, vol_pyr

    def _lookup(self, gev_pyr, vol_pyr):
        """The combined lookup of one iteration, by the kernel where it
        runs and its blocks fit, else by XLA gathers; the choice is said
        to ``log_path_once``."""
        r = self.config.corr_radius
        bins = tuple(v.shape[3] for v in gev_pyr)
        widths = tuple(v.shape[2] for v in vol_pyr)
        groups = gev_pyr[0].shape[2]
        what = (f"IGEV combined lookup G={groups} "
                f"D={'/'.join(map(str, bins))} "
                f"W2={'/'.join(map(str, widths))} float32")
        rb = (corr_lookup.geo_row_block(bins, widths, groups, r)
              if corr_lookup.fused_lookup_available() else 0)
        if rb:
            corr_lookup.log_path_once(f"{what}: one launch, row block {rb}")
            return lambda d: corr_lookup.lookup_geo_fused(gev_pyr, vol_pyr,
                                                          d, r, rb)
        corr_lookup.log_path_once(
            f"{what}: XLA gathers ("
            + ("the blocks of one row exceed the VMEM budget)"
               if corr_lookup.fused_lookup_available()
               else "no Pallas backend here)"))
        return lambda d: lookup_geo_xla(gev_pyr, vol_pyr, d, r)

    def _upsample(self, disp, mask, stem2):
        """9 softmax weights a full-resolution pixel from the 1/4 mask
        features and the stem's 1/2 output, over the 3x3 neighbourhood of
        ``4 d`` (zero beyond the border) taken nearest (the source's
        ``upsample_disp`` and ``context_upsample``)."""
        w = self.spx_gru(self.spx_2_gru(mask, stem2))
        w = jax.nn.softmax(w.astype(jnp.float32), axis=-1)
        b, h, wd = disp.shape
        dp = jnp.pad(4.0 * disp, ((0, 0), (1, 1), (1, 1)))
        nb = jnp.stack([dp[:, ky:ky + h, kx:kx + wd]
                        for ky in range(3) for kx in range(3)], axis=-1)
        nb = jnp.repeat(jnp.repeat(nb, 4, axis=1), 4, axis=2)
        return jnp.sum(w * nb, axis=-1)
