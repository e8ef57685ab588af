"""A seeded SceneFlow-layout tree for a training cell: ``pool_pairs`` pairs
of ``scenes.make_pair``'s recipe at FlyingThings3D's 540x960, written
where ``raft_stereo_tpu.data.datasets.SceneFlow`` looks for them
(``FlyingThings3D/frames_cleanpass/TRAIN/<A|B|C>/<scene>/{left,right}/
<frame>.png`` and ``FlyingThings3D/disparity/.../left/<frame>.pfm``), so
the job reads its data through the program's own decode, augment and batch
path.  NumPy and PIL only; the PFM is written here, not by the program's
writer (the program's reader is what is exercised).

No other part of the published mixture is written: the final pass, Monkaa
and Driving stay empty, which the program's mixture builder takes (it adds
nothing from them).
"""

from __future__ import annotations

import os

import numpy as np
from PIL import Image

HW = (540, 960)             # a FlyingThings3D frame


def make_pair(rng: np.random.Generator, hw=HW):
    """``scenes.make_pair``'s recipe, with the disparity it applies: the
    left image, the right one, and the (H, W) float32 disparity of the left
    view, positive, constant along a row."""
    h, w = hw
    block = int(rng.integers(6, 13))
    coarse = rng.uniform(0, 215, (-(-h // block), -(-w // block), 3))
    left = np.kron(coarse, np.ones((block, block, 1)))[:h, :w]
    left = left + rng.integers(0, 40, (h, w, 3))
    near = float(rng.uniform(16, 48))
    disp = (4 + near * np.linspace(0, 1, h) ** 1.5)[:, None].astype(int)
    cols = np.clip(np.arange(w)[None, :] + disp, 0, w - 1)
    right = np.take_along_axis(left, cols[:, :, None].repeat(3, 2), axis=1)
    return (left.astype(np.uint8), right.astype(np.uint8),
            np.broadcast_to(disp, (h, w)).astype(np.float32))


def _write_pfm(path: str, array: np.ndarray) -> None:
    """One-channel Portable Float Map: rows bottom-up, a negative scale
    for little-endian."""
    h, w = array.shape
    with open(path, "wb") as f:
        f.write(f"Pf\n{w} {h}\n-1.0\n".encode())
        f.write(np.ascontiguousarray(array[::-1], "<f4").tobytes())


def write_tree(root: str, seed: int, n: int, hw=HW) -> int:
    """``n`` pairs from ``seed`` under ``root``; returns the bytes written."""
    rng = np.random.default_rng([seed, 0x7EEE])
    things = os.path.join(root, "FlyingThings3D")
    written = 0
    for i in range(n):
        left, right, disp = make_pair(rng, hw)
        scene = os.path.join("TRAIN", "ABC"[i % 3], f"{i // 3:04d}")
        frame = f"{6 + i:04d}"
        for side, image in (("left", left), ("right", right)):
            d = os.path.join(things, "frames_cleanpass", scene, side)
            os.makedirs(d, exist_ok=True)
            path = os.path.join(d, frame + ".png")
            # level 1: the decode is the program's work, the encode is not
            Image.fromarray(image).save(path, compress_level=1)
            written += os.path.getsize(path)
        d = os.path.join(things, "disparity", scene, "left")
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, frame + ".pfm")
        _write_pfm(path, disp)
        written += os.path.getsize(path)
    return written
