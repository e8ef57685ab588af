"""Feature / context encoders (reference: core/extractor.py).

TPU-first re-design notes:
* NHWC layout throughout (TPU-native), params fp32 with a configurable compute
  dtype (bf16 under mixed precision — replaces torch autocast).
* Explicit symmetric padding tuples so strided convs match torch's
  ``padding=k//2`` exactly (XLA ``SAME`` splits padding asymmetrically for
  even inputs).
* Kaiming-normal(fan_out) conv init mirroring core/extractor.py:155-162;
  biases init to zero.
* The reference's list-input batching trick (core/extractor.py:176-179) is the
  caller's job here: concatenate the two images along batch before calling.
* ``BottleneckBlock`` (core/extractor.py:64-120) is dead code in the reference
  and intentionally not rebuilt (SURVEY.md §2 "dead code").
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_stereo_tpu.kernels.corr_lookup import log_path_once
from raft_stereo_tpu.models.norm import apply_norm, make_norm
from raft_stereo_tpu.quant.core import is_quantized_leaf
from raft_stereo_tpu.quant.matmul import QuantConv

# torch kaiming_normal_(mode='fan_out', nonlinearity='relu')
kaiming_out = nn.initializers.variance_scaling(2.0, "fan_out", "normal")


def conv(features, kernel, stride=1, *, dtype, name):
    # QuantConv IS nn.Conv when the kernel arrives fp (same params,
    # same program); with a {q8, qscale} pack it runs the int8 MXU
    # path (quant/matmul.py) — the encoder surface is exactly the set
    # of convs this factory builds.
    k = (kernel, kernel) if isinstance(kernel, int) else kernel
    pad = tuple((s // 2, s // 2) for s in k)
    return QuantConv(features, k, strides=(stride, stride), padding=pad,
                     dtype=dtype, kernel_init=kaiming_out,
                     bias_init=nn.initializers.zeros, name=name)


class ResidualBlock(nn.Module):
    """Two 3×3 convs + norm + skip (reference: core/extractor.py:6-60)."""

    planes: int
    norm_fn: str = "group"
    stride: int = 1
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        in_planes = x.shape[-1]
        y = conv(self.planes, 3, self.stride, dtype=self.dtype, name="conv1")(x)
        y = apply_norm(make_norm(self.norm_fn, self.planes, self.dtype, "norm1"), y)
        y = nn.relu(y)
        y = conv(self.planes, 3, 1, dtype=self.dtype, name="conv2")(y)
        y = apply_norm(make_norm(self.norm_fn, self.planes, self.dtype, "norm2"), y)
        y = nn.relu(y)

        if not (self.stride == 1 and in_planes == self.planes):
            x = conv(self.planes, 1, self.stride, dtype=self.dtype,
                     name="downsample_conv")(x)
            x = apply_norm(
                make_norm(self.norm_fn, self.planes, self.dtype, "norm3"), x)
        return nn.relu(x + y)


# The v5e compiler lays the stem's and layer1's [N, H, W, 64] activations out
# with the 64 channels on the 128 lanes, half of them empty, while the batch
# is small (32 images at 192x624 in bf16), and with the batch on the lanes
# once it is large (128 and 256 images).  Below this many images the stage
# runs two images a lane row; from it on there are no empty lanes to fill.
LANE_BATCH_IMAGES = 128


def packs_two_images(n_images: int) -> bool:
    """Whether the trunk's 64-channel stage runs two images a lane row."""
    return n_images % 2 == 0 and 0 < n_images < LANE_BATCH_IMAGES


def _remapped(fn):
    """A ``nn.map_variables`` input map: ``fn(path, leaf)`` on every variable,
    ``path`` the names from the collection down."""
    def remap(variables):
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: fn(tuple(k.key for k in path), leaf), variables)
    return remap


@_remapped
def _twice(path, leaf):
    """The stem with each output channel twice (its norm's vectors too)."""
    if path[1] in ("conv1", "norm1"):
        return jnp.concatenate([leaf, leaf], -1)
    return leaf


@_remapped
def _block_diagonal(path, leaf):
    """The stage for two images side by side on the channels: each kernel
    ``[[W, 0], [0, W]]``, each per-channel vector twice."""
    if path[1] not in ("conv1", "norm1", "layer1_0", "layer1_1"):
        return leaf
    if leaf.ndim == 4:                           # HWIO kernel
        zero = jnp.zeros_like(leaf)
        return jnp.concatenate([jnp.concatenate([leaf, zero], -1),
                                jnp.concatenate([zero, leaf], -1)], -2)
    return jnp.concatenate([leaf, leaf])


def _reading_half(half):
    """layer2_0 reading one half of the packed stage: the two kernels that
    read it (``conv1``, ``downsample_conv``) zero on the other half's
    channels."""
    @_remapped
    def remap(path, leaf):
        if path[1:] not in (("layer2_0", "conv1", "kernel"),
                            ("layer2_0", "downsample_conv", "kernel")):
            return leaf
        zero = jnp.zeros_like(leaf)
        return jnp.concatenate([leaf, zero] if half == 0 else [zero, leaf],
                               -2)
    return remap


def _mapped(method, remap):
    return nn.map_variables(method, ("params", "batch_stats"),
                            trans_in_fn=remap, mutable=False)


class _Trunk(nn.Module):
    """Shared stem + 3 residual stages (64 → 96 → 128) at 1/2^downsample res.

    When ``packs_two_images`` holds for the batch, the 64-channel stage runs
    the batch's two halves side by side on 128 channels, image i beside
    image N/2 + i, with the stored kernels made block-diagonal at apply time;
    layer2_0 runs once a half, reading its half through entry kernels that
    are zero on the other (so no copy unpacks it).  A stride-2 stem instead
    computes each image's 64 channels twice, and a lane select pairs them.
    The zero blocks add exact zeros, so only the accumulation order differs,
    and the parameter tree is the plain one."""

    norm_fn: str
    downsample: int
    dtype: Optional[Any] = None

    def _stem(self, x, channels):
        x = conv(channels, 7, 1 + (self.downsample > 2), dtype=self.dtype,
                 name="conv1")(x)
        return nn.relu(apply_norm(
            make_norm(self.norm_fn, channels, self.dtype, "norm1"), x))

    def _layer1(self, x, channels):
        for j in range(2):
            x = ResidualBlock(channels, self.norm_fn, 1, dtype=self.dtype,
                              name=f"layer1_{j}")(x)
        return x

    def _layer2_0(self, x):
        return ResidualBlock(96, self.norm_fn, 1 + (self.downsample > 1),
                             dtype=self.dtype, name="layer2_0")(x)

    def _packs(self, x) -> bool:
        """The stage's choice for this batch, recorded as the kernels record
        theirs."""
        if self.is_initializing():
            return False                # init makes the stored 64-wide tree
        n, h, w, _ = x.shape
        s = 1 + (self.downsample > 2)
        what = f"trunk stage {-(-h // s)}x{-(-w // s)} x{n} images"
        if self.is_mutable_collection("intermediates"):
            why = "a capture reads each convolution's own input"
        elif any(is_quantized_leaf(leaf) for leaf in jax.tree_util.tree_leaves(
                self.variables["params"], is_leaf=is_quantized_leaf)):
            why = "int8 kernels"
        elif not packs_two_images(n):
            why = ("an odd count" if n % 2 else
                   f"from {LANE_BATCH_IMAGES} images the batch takes the lanes")
        else:
            log_path_once(f"{what}: two images a lane row")
            return True
        log_path_once(f"{what}: plain ({why})")
        return False

    @nn.compact
    def __call__(self, x):
        if self._packs(x):
            half = x.shape[0] // 2
            # Pairing a stride-2 stem's 3-channel input costs its relayout
            # and the stem more than the select costs; at full resolution
            # the select's pass over the stem's output costs more (TPU v5e,
            # a forward of 16 realtime pairs: 86.2 ms with the select, 88.0
            # pairing the input, 89.8 plain; of 8 accuracy pairs at
            # precision high: 1597.6, 1503.6 and 1558.0 ms).  Those two
            # forwards differ in resolution and precision too: the stride
            # as the key is the simplest reading of one forward a side.
            if self.downsample > 2:
                x = _mapped(_Trunk._stem, _twice)(self, x, 128)
                x = jnp.where(jnp.arange(128) < 64, x[:half], x[half:])
            else:
                x = _mapped(_Trunk._stem, _block_diagonal)(
                    self, jnp.concatenate([x[:half], x[half:]], axis=-1), 128)
            x = _mapped(_Trunk._layer1, _block_diagonal)(self, x, 128)
            x = jnp.concatenate([
                _mapped(_Trunk._layer2_0, _reading_half(h))(self, x)
                for h in (0, 1)], axis=0)
        else:
            x = self._layer2_0(self._layer1(self._stem(x, 64), 64))
        x = ResidualBlock(96, self.norm_fn, 1, dtype=self.dtype,
                          name="layer2_1")(x)
        x = ResidualBlock(128, self.norm_fn, 1 + (self.downsample > 0),
                          dtype=self.dtype, name="layer3_0")(x)
        return ResidualBlock(128, self.norm_fn, 1, dtype=self.dtype,
                             name="layer3_1")(x)


class BasicEncoder(nn.Module):
    """fnet: trunk + 1×1 projection (reference: core/extractor.py:122-197)."""

    output_dim: int = 128
    norm_fn: str = "instance"
    downsample: int = 3
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x, trunk_out=None):
        # ``trunk_out`` lets the banded executor (models/banded.py) supply
        # the trunk output computed stream-wise on the SAME parameter tree;
        # only ever passed at apply time, so init still creates all params.
        if trunk_out is None:
            trunk_out = _Trunk(self.norm_fn, self.downsample, self.dtype,
                               name="trunk")(x)
        return conv(self.output_dim, 1, 1, dtype=self.dtype,
                    name="conv2")(trunk_out)


class MultiBasicEncoder(nn.Module):
    """cnet: trunk + two extra stride-2 stages + per-resolution output heads
    (reference: core/extractor.py:199-300).

    ``output_dims`` is a sequence of per-head channel tuples, each ordered
    FINE → COARSE (our convention; the reference indexes ``dim[2]`` for the
    finest head — core/extractor.py:231).  Head h at level l emits
    ``output_dims[h][l]`` channels.

    Returns ``(levels, v)`` where ``levels[l]`` is a list over heads of
    features at 1/2^(downsample+l) resolution (only ``num_layers`` levels),
    and ``v`` is the full-batch trunk output (for ``shared_backbone``;
    reference's ``dual_inp`` — core/extractor.py:283-285).
    """

    output_dims: Sequence[Tuple[int, ...]] = ((128, 128, 128),)
    norm_fn: str = "batch"
    downsample: int = 3
    num_layers: int = 3
    dual_inp: bool = False
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x, trunk_out=None):
        # see BasicEncoder.__call__: banded-executor entry point
        if trunk_out is None:
            trunk_out = _Trunk(self.norm_fn, self.downsample, self.dtype,
                               name="trunk")(x)
        x = trunk_out
        v = x
        if self.dual_inp:
            x = x[: x.shape[0] // 2]

        levels = []
        # level 0 (finest, 1/2^downsample): ResidualBlock + 3×3 conv heads
        outs = []
        for h, dims in enumerate(self.output_dims):
            y = ResidualBlock(128, self.norm_fn, 1, dtype=self.dtype,
                              name=f"outputs08_{h}_res")(x)
            outs.append(conv(dims[0], 3, 1, dtype=self.dtype,
                             name=f"outputs08_{h}_conv")(y))
        levels.append(outs)

        if self.num_layers >= 2:
            x16 = ResidualBlock(128, self.norm_fn, 2, dtype=self.dtype,
                                name="layer4_0")(x)
            x16 = ResidualBlock(128, self.norm_fn, 1, dtype=self.dtype,
                                name="layer4_1")(x16)
            outs = []
            for h, dims in enumerate(self.output_dims):
                y = ResidualBlock(128, self.norm_fn, 1, dtype=self.dtype,
                                  name=f"outputs16_{h}_res")(x16)
                outs.append(conv(dims[1], 3, 1, dtype=self.dtype,
                                 name=f"outputs16_{h}_conv")(y))
            levels.append(outs)

        if self.num_layers >= 3:
            x32 = ResidualBlock(128, self.norm_fn, 2, dtype=self.dtype,
                                name="layer5_0")(x16)
            x32 = ResidualBlock(128, self.norm_fn, 1, dtype=self.dtype,
                                name="layer5_1")(x32)
            outs = [conv(dims[2], 3, 1, dtype=self.dtype,
                         name=f"outputs32_{h}_conv")(x32)
                    for h, dims in enumerate(self.output_dims)]
            levels.append(outs)

        return levels, v
