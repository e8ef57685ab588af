"""Seeded stereo pairs with something to match.

A left image of coarse random blocks under fine noise (texture at two
scales, so both the 7x7 stem and the 1/4- or 1/8-resolution features see
structure), and the right view of the same scene under a smooth positive
disparity that grows down the image, as a road scene's does.  uint8, NumPy
only: the load generator that sends these never touches jax.
"""

from __future__ import annotations

import numpy as np


def make_pair(rng: np.random.Generator, hw):
    h, w = hw
    block = int(rng.integers(6, 13))
    coarse = rng.uniform(0, 215, (-(-h // block), -(-w // block), 3))
    left = np.kron(coarse, np.ones((block, block, 1)))[:h, :w]
    left = left + rng.integers(0, 40, (h, w, 3))
    near = float(rng.uniform(16, 48))
    disp = (4 + near * np.linspace(0, 1, h) ** 1.5)[:, None].astype(int)
    cols = np.clip(np.arange(w)[None, :] + disp, 0, w - 1)
    right = np.take_along_axis(left, cols[:, :, None].repeat(3, 2), axis=1)
    return left.astype(np.uint8), right.astype(np.uint8)


def make_pairs(seed: int, n: int, hw):
    """``n`` distinct pairs from ``seed``."""
    rng = np.random.default_rng([seed, 0x5CE])
    return [make_pair(rng, hw) for _ in range(n)]
