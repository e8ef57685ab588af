"""Operations and bytes of a TRAINING step's work, counted from the
configuration's shapes (``flops.py`` says why never from the implementation
nor from ``cost_analysis``).

**The factor 3.**  A trained pair costs the forward's products once and the
backward's twice: for every product ``y = w x`` of the forward the backward
forms the gradient by the activations (``w^T dy``) and the gradient by the
weights (``dy x^T``), each as large as the forward's.  So a step is
``3 x forward_flops`` a pair, the usual convention for model FLOPs.
**Recomputation is not counted.**  The program rebuilds each refinement's
activations in the backward (``remat_gru``: a fourth pass over the loop's
convolutions, less the saved lookup); that is the implementation's way of
fitting the chip, not work the gradient needs, so a utilisation counted
with it would rise when the program wastes more.  ``forward_flops`` also
counts the upsampling mask and the upsampling ONCE, as an answer needs
them; the training loss needs them at every iteration (about a tenth more
at 22 iterations), and they get no credit here either: the count stays the
one ``step_mfu_pct`` uses everywhere, an under-count, never an over-count.
"""

from __future__ import annotations

from benchmark import flops

TRAIN_FACTOR = 3.0


def trained_pair_flops(cfg: dict, h: int, w: int, iters: int) -> float:
    """Model FLOPs of one pair's forward and backward at crop (h, w)."""
    return TRAIN_FACTOR * flops.forward_flops(cfg, h, w, iters)


def lookup_bwd_level0_elements(cfg: dict, h: int, w: int) -> int:
    """Elements of the finest level's volume cotangent for one pair: what
    one backward launch of the all-levels lookup kernel writes as the first
    of its results, so elements written over this is pair-lookups."""
    h8, w8 = flops.feature_hw(cfg, h, w)
    return h8 * w8 * w8


def lookup_bwd_work(cfg: dict, h: int, w: int, itemsize: int) -> dict:
    """The backward of one pyramid lookup of one pair, as the algorithm
    needs it — the mirror of ``flops.lookup_work``: each pixel of the 1/f
    map reads its position and the cotangent of its 2r+1 taps at each
    level, and adds to the 2r+2 volume entries a level that those taps
    touched.  The DENSE cotangent the kernel writes (every entry of every
    level, each iteration, summed by XLA afterwards) is the
    implementation's, not the algorithm's: 337 entries a pixel against
    40."""
    h8, w8 = flops.feature_hw(cfg, h, w)
    lv, r = cfg["corr_levels"], cfg["corr_radius"]
    taps = lv * (2 * r + 1)
    return {"flops": 3.0 * h8 * w8 * taps,
            "bytes": h8 * w8 * (lv * (2 * r + 2) * itemsize + 4
                                + taps * itemsize)}
