"""On-device photometric augmentation (opt-in input-pipeline offload).

The host ``ColorJitter`` (data/augment.py) costs ~63 ms/sample at SceneFlow
frame sizes — 78% of the whole per-sample host budget on a one-core host
(measured on an earlier host, round 4) — while the chip absorbs the same
elementwise work in single-digit milliseconds inside the already
memory-bound train step.  This module replicates torchvision ColorJitter
semantics (reference: core/utils/augmentor.py:73-93 — brightness/contrast/
saturation blends + hue shift, ops in random order, symmetric-or-asymmetric
across the stereo pair, optional gamma) in pure ``jnp`` with per-sample
factors drawn from a step-folded JAX PRNG key, so the augmentation stream
is a deterministic function of (seed, step) and survives exact resume.

Documented deviations from the host path (the host path remains the
reference-faithful default; this mode trades bit-parity for host CPU):

* runs AFTER spatial crop/resize (inside the train step), so contrast/
  saturation reference means are over the crop, not the full frame;
* float32 throughout with a clip after each op — no uint8 rounding between
  ops, and hue shifts are not quantized to cv2's 1/180-turn grid;
* the occlusion eraser stays on the host (it is ~free there and needs
  pre-crop geometry).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class JitterParams:
    """Factor ranges, defaulting to the dense-augmentor profile
    (data/augment.py DenseAugmentor; reference: core/utils/augmentor.py:85)."""

    brightness: float = 0.4
    contrast: float = 0.4
    saturation: Tuple[float, float] = (0.6, 1.4)
    hue: float = 0.5 / 3.14
    # (gamma_min, gamma_max, gain_min, gain_max); (1,1,1,1) = off
    gamma: Tuple[float, float, float, float] = (1.0, 1.0, 1.0, 1.0)
    asymmetric_prob: float = 0.2


# ------------------------------------------------------------- fixed-factor ops
# Each mirrors its uint8 host twin in data/augment.py; factors are explicit
# so tests can compare host vs device op-by-op.  Images are float32 0..255.

def adjust_brightness(img: jnp.ndarray, factor) -> jnp.ndarray:
    return jnp.clip(img * factor, 0.0, 255.0)


def adjust_contrast(img: jnp.ndarray, factor, mean) -> jnp.ndarray:
    """``mean`` is the gray mean to blend toward — per-sample scalar,
    passed in because symmetric stereo jitter uses the PAIR's joint mean
    (host: jitter of the stacked pair, augment.py DenseAugmentor._color)."""
    return jnp.clip(img * factor + (1.0 - factor) * mean, 0.0, 255.0)


def adjust_saturation(img: jnp.ndarray, factor) -> jnp.ndarray:
    luma = img @ jnp.asarray([0.299, 0.587, 0.114], img.dtype)
    return jnp.clip(img * factor + (1.0 - factor) * luma[..., None],
                    0.0, 255.0)


def adjust_hue(img: jnp.ndarray, shift) -> jnp.ndarray:
    """``shift`` in turns of the hue circle, like the host op."""
    x = img * (1.0 / 255.0)
    mx = jnp.max(x, axis=-1)
    mn = jnp.min(x, axis=-1)
    c = mx - mn
    r, g, b = x[..., 0], x[..., 1], x[..., 2]
    safe_c = jnp.where(c > 0, c, 1.0)
    h = jnp.where(
        c <= 0, 0.0,
        jnp.where(mx == r, ((g - b) / safe_c) % 6.0,
                  jnp.where(mx == g, (b - r) / safe_c + 2.0,
                            (r - g) / safe_c + 4.0))) / 6.0
    h = (h + shift) % 1.0
    # HSV -> RGB with v = mx, s*v = c
    k = (jnp.stack([jnp.full_like(h, 5.0), jnp.full_like(h, 3.0),
                    jnp.full_like(h, 1.0)], axis=-1) + h[..., None] * 6.0) % 6.0
    out = mx[..., None] - c[..., None] * jnp.clip(
        jnp.minimum(k, 4.0 - k), 0.0, 1.0)
    return jnp.clip(out * 255.0, 0.0, 255.0)


def adjust_gamma(img: jnp.ndarray, gamma, gain) -> jnp.ndarray:
    x = img * (1.0 / 255.0)
    return jnp.clip(255.0 * gain * jnp.power(x, gamma), 0.0, 255.0)


def _gray_mean(img: jnp.ndarray) -> jnp.ndarray:
    """Per-sample scalar: mean over channels then pixels (host twin:
    augment.adjust_contrast's fp32 accumulation)."""
    return jnp.mean(img, axis=(-3, -2, -1))


# ----------------------------------------------------------------- pair jitter
def apply_photometric(img1: jnp.ndarray, img2: jnp.ndarray, key,
                      params: JitterParams
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Jitter a stereo batch: (B,H,W,3) uint8/float 0..255 -> float32.

    Per sample: draw factors + a random op order for view 1; with
    probability ``asymmetric_prob`` view 2 gets independent factors AND an
    independent order (host: two separate ``jitter()`` calls), otherwise it
    shares view 1's factors/order and the contrast op blends toward the
    JOINT mean of both views (host: jitter of the vertically stacked pair).
    """
    b = img1.shape[0]
    img1 = img1.astype(jnp.float32)
    img2 = img2.astype(jnp.float32)

    k_f1, k_f2, k_o1, k_o2, k_asym, k_gamma = jax.random.split(key, 6)

    def draw_factors(k):
        kb, kc, ks, kh = jax.random.split(k, 4)
        p = params
        return {
            "b": jax.random.uniform(kb, (b,), minval=max(0.0, 1 - p.brightness),
                                    maxval=1 + p.brightness),
            "c": jax.random.uniform(kc, (b,), minval=max(0.0, 1 - p.contrast),
                                    maxval=1 + p.contrast),
            "s": jax.random.uniform(ks, (b,), minval=p.saturation[0],
                                    maxval=p.saturation[1]),
            "h": jax.random.uniform(kh, (b,), minval=-p.hue, maxval=p.hue),
        }

    f1 = draw_factors(k_f1)
    f2i = draw_factors(k_f2)
    asym = jax.random.bernoulli(k_asym, params.asymmetric_prob, (b,))
    f2 = {k: jnp.where(asym, f2i[k], f1[k]) for k in f1}

    # op order: per-sample permutation of {brightness, contrast, saturation,
    # hue} via argsort of uniforms (torchvision: torch.randperm per call)
    perm1 = jnp.argsort(jax.random.uniform(k_o1, (b, 4)), axis=-1)
    perm2i = jnp.argsort(jax.random.uniform(k_o2, (b, 4)), axis=-1)
    perm2 = jnp.where(asym[:, None], perm2i, perm1)

    bc = lambda v: v[:, None, None, None]  # (B,) -> broadcast over H,W,C

    def position(img1, img2, k):
        """Apply the k-th op of each sample's order to both views.  All four
        ops are computed and selected per sample (the order is data-
        dependent); 4 positions x 4 ops = 16 elementwise passes, ~ms on
        chip vs 63 ms/sample on host."""
        op1 = perm1[:, k]
        op2 = perm2[:, k]
        m1 = _gray_mean(img1)
        m2 = _gray_mean(img2)
        joint = 0.5 * (m1 + m2)
        # symmetric pairs share op history, so the joint mean is exact
        cmean1 = jnp.where(asym, m1, joint)
        cmean2 = jnp.where(asym, m2, joint)

        def all_ops(img, f, cmean):
            return jnp.stack([
                adjust_brightness(img, bc(f["b"])),
                adjust_contrast(img, bc(f["c"]), bc(cmean)),
                adjust_saturation(img, bc(f["s"])),
                adjust_hue(img, f["h"][:, None, None]),
            ])

        sel1 = jnp.take_along_axis(
            all_ops(img1, f1, cmean1), op1[None, :, None, None, None],
            axis=0)[0]
        sel2 = jnp.take_along_axis(
            all_ops(img2, f2, cmean2), op2[None, :, None, None, None],
            axis=0)[0]
        return sel1, sel2

    for k in range(4):
        img1, img2 = position(img1, img2, k)

    gmin, gmax, gainmin, gainmax = params.gamma
    if (gmin, gmax, gainmin, gainmax) != (1.0, 1.0, 1.0, 1.0):
        kg1, kg2 = jax.random.split(k_gamma)
        g = jax.random.uniform(kg1, (b,), minval=gmin, maxval=gmax)
        gain = jax.random.uniform(kg2, (b,), minval=gainmin, maxval=gainmax)
        # gamma is drawn once per host jitter() call; symmetric pairs share
        # it (stacked-pair path), asymmetric pairs draw independently
        g2i = jax.random.uniform(jax.random.fold_in(kg1, 1), (b,),
                                 minval=gmin, maxval=gmax)
        gain2i = jax.random.uniform(jax.random.fold_in(kg2, 1), (b,),
                                    minval=gainmin, maxval=gainmax)
        img1 = adjust_gamma(img1, bc(g), bc(gain))
        img2 = adjust_gamma(img2, bc(jnp.where(asym, g2i, g)),
                            bc(jnp.where(asym, gain2i, gain)))
    return img1, img2


def params_for_datasets(train_datasets, saturation_range=None,
                        img_gamma=None) -> JitterParams:
    """Derive the jitter profile from the training mixture the way
    ``build_training_mixture`` parameterizes the host augmentors.

    Dense-GT families use the dense profile (0.4/0.4/(0.6,1.4)/0.5÷3.14),
    sparse-GT families the sparse one (0.3/0.3/(0.7,1.3)/0.3÷3.14) —
    data/augment.py Dense/SparseAugmentor defaults.  A mixture spanning
    both profiles cannot share one device-jitter parameterization: raise,
    keep host jitter there."""
    dense = {"sceneflow", "falling_things"}
    is_dense = [name in dense or name.startswith("tartan_air")
                for name in train_datasets]
    if all(is_dense):
        p = JitterParams()
    elif not any(is_dense):
        # sparse host jitter is ALWAYS symmetric (augment.py
        # SparseAugmentor.__call__ jitters the stacked pair
        # unconditionally), so asymmetric_prob must be 0 here
        p = JitterParams(brightness=0.3, contrast=0.3, saturation=(0.7, 1.3),
                         hue=0.3 / 3.14, asymmetric_prob=0.0)
    else:
        raise ValueError(
            f"device_photometric cannot serve a mixture of dense and "
            f"sparse jitter profiles ({list(train_datasets)}); train with "
            f"host-side augmentation there")
    if saturation_range is not None:
        p = dataclasses.replace(p, saturation=tuple(saturation_range))
    if img_gamma is not None:
        g = tuple(img_gamma)
        p = dataclasses.replace(
            p, gamma=g if len(g) == 4 else (g[0], g[1], 1.0, 1.0))
    return p
