"""Normalization layers with the reference's exact semantics.

The reference's four norm choices (reference: core/extractor.py:16-38):

* ``batch``   — ``nn.BatchNorm2d`` that is ALWAYS run in eval mode during
  training (``freeze_bn`` at train_stereo.py:151,193): normalization uses the
  stored running statistics (identity stats when training from scratch), while
  the affine scale/bias remain trainable.  We model this exactly as
  ``FrozenBatchNorm``: ``mean``/``var`` live in the non-trainable
  ``batch_stats`` collection, ``scale``/``bias`` in ``params``.
* ``instance`` — ``nn.InstanceNorm2d`` defaults: per-sample per-channel over
  (H, W), biased variance, eps 1e-5, NO affine parameters.
* ``group``    — ``nn.GroupNorm(planes // 8, planes)``, eps 1e-5, affine.
* ``none``     — identity.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


class FrozenBatchNorm(nn.Module):
    """BatchNorm evaluated with stored statistics; affine params trainable."""

    dtype: Optional[jnp.dtype] = None
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        mean = self.variable("batch_stats", "mean",
                             lambda: jnp.zeros((c,), jnp.float32)).value
        var = self.variable("batch_stats", "var",
                            lambda: jnp.ones((c,), jnp.float32)).value
        dtype = self.dtype or x.dtype
        inv = (scale / jnp.sqrt(var + self.eps)).astype(dtype)
        shift = (bias - mean * scale / jnp.sqrt(var + self.eps)).astype(dtype)
        return x * inv + shift


class InstanceNorm(nn.Module):
    """Per-sample, per-channel normalization over (H, W); no affine."""

    dtype: Optional[jnp.dtype] = None
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        # Materialize the input before the spatial reductions: without the
        # barrier XLA duplicates the producer convolution into each
        # reduction fusion (mean, var, normalize = 3 consumers), tripling
        # conv work — measured 4.3ms vs 1.9ms per residual block at
        # (2,192,624,64) on a v5e chip, ~60ms across the fp32 fnet.
        x = jax.lax.optimization_barrier(x)
        # Compute statistics in fp32 for stability, return in input dtype.
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=(1, 2), keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=(1, 2), keepdims=True)
        y = (xf - mean) * (1.0 / jnp.sqrt(var + self.eps))
        return y.astype(x.dtype)


def make_norm(norm_fn: str, channels: int, dtype=None, name: str = "norm"):
    """Factory mirroring the reference's norm switch (core/extractor.py:16-38)."""
    if norm_fn == "batch":
        return FrozenBatchNorm(dtype=dtype, name=name)
    if norm_fn == "instance":
        return InstanceNorm(dtype=dtype, name=name)
    if norm_fn == "group":
        return nn.GroupNorm(num_groups=max(channels // 8, 1), epsilon=1e-5,
                            dtype=dtype, name=name)
    if norm_fn == "none":
        return None
    raise ValueError(f"unknown norm_fn {norm_fn!r}")


def apply_norm(norm, x):
    return x if norm is None else norm(x)
