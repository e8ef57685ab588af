"""Banded (streaming) trunk execution — the full-resolution memory ceiling.

With ``n_downsample=2`` the encoder stem runs at FULL image resolution
(matching the reference's stride gate, core/extractor.py:140), and its
activations — not the correlation volume — set peak HBM at high resolution
(an earlier runtime read 8.5 GiB for a 1984×2880 frame AFTER the
sequential-fnet fix).  This module executes the full-resolution segment of
``_Trunk`` (stem + layer1 + layer2_0's stride-2 entry convs) in horizontal
BANDS with halo rows, so only band-sized tensors ever exist:

* Convolutions are exact: each band carries ``_HALO`` extra rows on both
  sides (≥ the segment's receptive-field half-width), runs the same conv
  arithmetic on the same parameters, and crops the halo — interior rows
  match the full-image conv, and every activation is masked to the true
  image rows so image borders see the identical zero padding.
* Frozen batch norm / 'none' are elementwise → a single sweep suffices.
* Instance norm needs GLOBAL per-(sample, channel) statistics over (H, W),
  so each of the segment's 5 instance norms adds a stats sweep: sweep k
  recomputes bands through the already-known stats 1..k-1 and accumulates
  sum/sum² of norm k's input.  6 sweeps total ≈ 3.5× the segment's FLOPs —
  the alt-backend trade (recompute for memory) applied to the encoder, and
  the stereo analog of blockwise/ring attention: stream over the long axis,
  keep only a tile resident, pay recompute for the global reductions.

Everything from layer2_0's norms onward runs unbanded at ≤1/2 resolution on
the same parameter tree, so checkpoints are untouched.  All math here is
raw ``lax`` ops on parameter subtrees (constructing flax submodules inside
another module's compact call is illegal), mirroring ``nn.Conv`` /
``models.norm`` semantics exactly.  Supported: downsample=2 trunks with
norm_fn in {instance, batch, none} — the published fnet/cnet
configurations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import nn as jnn

_EPS = 1e-5  # norm epsilon (models/norm.py)
# receptive-field half-width of the banded segment: 7×7 stem (3) + four 3×3
# convs (1 each) + layer2_0's 3×3 entry (1) = 8; kept even for stride-2
# alignment
_HALO = 8


def _conv(p, x, stride, dtype):
    """``nn.Conv`` semantics (models/extractor.py conv factory): NHWC/HWIO,
    symmetric k//2 padding, compute in ``dtype``."""
    k = p["kernel"].astype(dtype)
    kh, kw = k.shape[0], k.shape[1]
    out = jax.lax.conv_general_dilated(
        x.astype(dtype), k, (stride, stride),
        padding=((kh // 2, kh // 2), (kw // 2, kw // 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return out + p["bias"].astype(dtype)


def _frozen_bn(p, b, x, dtype):
    """models/norm.py FrozenBatchNorm math on a params/batch_stats pair."""
    inv = (p["scale"] / jnp.sqrt(b["var"] + _EPS)).astype(dtype)
    shift = (p["bias"] - b["mean"] * p["scale"]
             / jnp.sqrt(b["var"] + _EPS)).astype(dtype)
    return x * inv + shift


def _instance_norm_full(x):
    """models/norm.py InstanceNorm math (full-tensor, used for the ≤1/2-res
    tail)."""
    x = jax.lax.optimization_barrier(x)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(1, 2), keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=(1, 2), keepdims=True)
    return ((xf - mean) * (1.0 / jnp.sqrt(var + _EPS))).astype(x.dtype)


def _subtree(tree, path):
    for k in path:
        tree = tree[k] if tree else {}
    return tree


def masked_moments(t, m, width):
    """Per-(sample, channel) mean and sum of squared deviations of ``t``
    (N, rows, W, C) over the rows selected by broadcastable bool mask ``m``,
    plus the element count.  The two-pass (mean, then M2) form — the
    one-pass E[x²]−mean² formula cancels catastrophically at many-MPix
    pixel counts in fp32."""
    t = jnp.where(m, t.astype(jnp.float32), 0.0)
    n = jnp.sum(m.astype(jnp.float32)) * width
    mean = jnp.sum(t, axis=(1, 2)) / n                       # (N, C)
    dev = jnp.where(m, t - mean[:, None, None, :], 0.0)
    m2 = jnp.sum(dev * dev, axis=(1, 2))
    return mean, m2, n


def chan_combine(means, m2s, ns):
    """Chan's parallel-variance combination of stacked per-chunk moments
    (k, N, C)/(k, N, C)/(k,) → global ``(mean, var)`` of shape (N, C).
    Shared by the banded executor (chunks = bands) and the row-sharded
    executor (chunks = devices, via all_gather) so the numerically
    delicate combination can never diverge between them."""
    total = jnp.sum(ns)
    mean = jnp.sum(means * ns[:, None, None], axis=0) / total
    m2 = (jnp.sum(m2s, axis=0)
          + jnp.sum(ns[:, None, None]
                    * jnp.square(means - mean[None]), axis=0))
    return mean, m2 / total


def _norm(norm_fn, tp, batch_stats, path, dtype, inst_stats, x):
    """Norm at ``path``: instance uses ``inst_stats`` when given (banded
    segment) else full-tensor stats; batch/none are elementwise."""
    if norm_fn == "instance":
        if inst_stats is None:
            return _instance_norm_full(x)
        mean, var = inst_stats  # (N, 1, 1, C) fp32
        xf = x.astype(jnp.float32)
        return ((xf - mean) * (1.0 / jnp.sqrt(var + _EPS))).astype(x.dtype)
    if norm_fn == "batch":
        return _frozen_bn(_subtree(tp, path), _subtree(batch_stats, path),
                          x, dtype)
    if norm_fn == "none":
        return x
    raise NotImplementedError(
        f"banded trunk does not support norm_fn={norm_fn!r}")


def _segment(tp, batch_stats, xb, norm_fn, dtype, stats, upto, row_mask):
    """The full-resolution segment on one (haloed) band.

    ``upto`` ∈ 1..5 returns instance-norm input t_upto (a stats sweep);
    ``upto`` = 6 returns layer2_0's two stride-2 conv outputs (final sweep).
    ``stats``: per-norm (mean, var) tuples (instance norm only), OR a
    callable ``stats(k, t) -> (mean, var)`` computing norm ``k``'s global
    statistics from its input on the fly (the row-sharded executor — each
    device holds its whole slab, so a single pass pausing per norm for a
    tiny cross-device moment exchange replaces banded's recompute sweeps).
    ``row_mask``: True where the band row lies INSIDE the image.  Every
    activation is masked with it: at image borders the halo rows would
    otherwise carry leaked conv outputs where the full-image computation
    sees SAME zero padding (interior band boundaries carry true neighbor
    values and are exact without it).
    """
    m = row_mask[None, :, None, None]

    def norm(i, path, t):
        if callable(stats):
            s = stats(i, t)
        else:
            s = stats[i] if stats else None
        return _norm(norm_fn, tp, batch_stats, path, dtype, s, t)

    t1 = _conv(tp["conv1"], xb, 1, dtype)
    if upto == 1:
        return t1
    a1 = jnp.where(m, jnn.relu(norm(0, ("norm1",), t1)), 0)
    t2 = _conv(tp["layer1_0"]["conv1"], a1, 1, dtype)
    if upto == 2:
        return t2
    a2 = jnp.where(m, jnn.relu(norm(1, ("layer1_0", "norm1"), t2)), 0)
    t3 = _conv(tp["layer1_0"]["conv2"], a2, 1, dtype)
    if upto == 3:
        return t3
    b1 = jnp.where(m, jnn.relu(a1 + jnn.relu(
        norm(2, ("layer1_0", "norm2"), t3))), 0)
    t4 = _conv(tp["layer1_1"]["conv1"], b1, 1, dtype)
    if upto == 4:
        return t4
    a4 = jnp.where(m, jnn.relu(norm(3, ("layer1_1", "norm1"), t4)), 0)
    t5 = _conv(tp["layer1_1"]["conv2"], a4, 1, dtype)
    if upto == 5:
        return t5
    b2 = jnp.where(m, jnn.relu(b1 + jnn.relu(
        norm(4, ("layer1_1", "norm2"), t5))), 0)
    u = _conv(tp["layer2_0"]["conv1"], b2, 2, dtype)
    v = _conv(tp["layer2_0"]["downsample_conv"], b2, 2, dtype)
    return u, v


_N_INSTANCE_STATS = 5  # norm1 + 2 per layer1 residual block


def _residual_block(tp, batch_stats, x, name, stride, norm_fn, dtype):
    """models/extractor.py ResidualBlock math on the parameter subtree."""
    p = tp[name]
    b = _subtree(batch_stats, (name,))

    def n(which, t):
        return _norm(norm_fn, p, b, (which,), dtype, None, t)

    y = jnn.relu(n("norm1", _conv(p["conv1"], x, stride, dtype)))
    y = jnn.relu(n("norm2", _conv(p["conv2"], y, 1, dtype)))
    if "downsample_conv" in p:
        x = n("norm3", _conv(p["downsample_conv"], x, stride, dtype))
    return jnn.relu(x + y)


# Peak-HBM bytes one band of the streaming segment adds per
# (row x width-pixel x batch-sample).  Measured on an earlier runtime and
# not re-measured on the v5e (no cell turns banded_encoder on): peak-HBM slope
# in band height at 1984x2880 = 231.7 B/(row*width-pixel); the overall peak is
# nearly FLAT in the band (3.93-4.20 GiB for bands 128-512) because the
# off-band stages dominate, so the choice is low-stakes within the clamp.
_BAND_BYTES_PER_ROW_PIXEL = 232
# Fraction of device HBM the resident band working set may occupy — ~1%,
# which reproduces the band=256 that carried the round-2 full-resolution
# measurements at the 2880-wide calibration shape on a
# 16 GiB chip; the rest stays for the off-band stages (1/2-res tail,
# correlation, GRU state) that coexist with the streamed stem.
_BAND_HBM_FRACTION = 1 / 96
_BAND_MIN, _BAND_MAX = 64, 1024


def default_band_rows(n: int, w: int) -> int:
    """Band height derived from device HBM: the largest even band whose
    working set (``n * w * band * _BAND_BYTES_PER_ROW_PIXEL``) stays under
    ``_BAND_HBM_FRACTION`` of HBM, clamped to [64, 1024].  At W=2880 on a
    16 GiB chip this lands at 266 rows — within 5% of the band=256 that
    carried the round-2 full-resolution measurements,
    whose peak HBM the calibration run measured as nearly flat in the
    band height anyway (not re-measured on the v5e)."""
    from raft_stereo_tpu.profiling import device_hbm_bytes
    budget = _BAND_HBM_FRACTION * device_hbm_bytes()
    band = int(budget // (max(n, 1) * w * _BAND_BYTES_PER_ROW_PIXEL))
    return max(_BAND_MIN, min(_BAND_MAX, band - band % 2))


def banded_trunk_apply(trunk_params, batch_stats, x, norm_fn, dtype,
                       band=None):
    """``_Trunk`` (downsample=2) on the same parameter tree, full-resolution
    stages streamed in bands.  Returns the 1/4-resolution trunk output.
    ``band=None`` derives the band height from device HBM
    (:func:`default_band_rows`)."""
    n, h, w, _ = x.shape
    if band is None:
        band = default_band_rows(n, w)
    assert band % 2 == 0, "band must be even for stride-2 alignment"
    nb = -(-h // band)
    xp = jnp.pad(x, ((0, 0), (_HALO, nb * band - h + _HALO), (0, 0), (0, 0)))
    bands = jnp.stack([xp[:, i * band: i * band + band + 2 * _HALO]
                       for i in range(nb)])
    band_idx = jnp.arange(nb)

    def row_mask_for(bi):
        g = jnp.arange(band + 2 * _HALO) + bi * band - _HALO  # global rows
        return (g >= 0) & (g < h)

    stats = []
    if norm_fn == "instance":
        for i in range(1, _N_INSTANCE_STATS + 1):
            # remat: under jax.grad the map would otherwise stack every
            # band's conv intermediates as residuals (= full-resolution
            # activations per sweep), inverting the memory saving; with
            # checkpoint the backward recomputes each band.
            @jax.checkpoint
            def stat_band(args, i=i):
                xb, bi = args
                t = _segment(trunk_params, batch_stats, xb, norm_fn, dtype,
                             stats, upto=i, row_mask=row_mask_for(bi))
                t = t[:, _HALO:_HALO + band]
                rows = jnp.arange(band)
                m = ((rows + bi * band) < h)[None, :, None, None]
                return masked_moments(t, m, w)
            bmeans, m2s, ns = jax.lax.map(stat_band, (bands, band_idx))
            mean, var = chan_combine(bmeans, m2s, ns)  # Σns = h*w
            stats.append((mean[:, None, None, :], var[:, None, None, :]))

    @jax.checkpoint
    def final_band(args):
        xb, bi = args
        u, v = _segment(trunk_params, batch_stats, xb, norm_fn, dtype,
                        stats, upto=6, row_mask=row_mask_for(bi))
        crop = slice(_HALO // 2, _HALO // 2 + band // 2)
        return u[:, crop], v[:, crop]

    u_b, v_b = jax.lax.map(final_band, (bands, band_idx))
    h2 = -(-h // 2)  # SAME stride-2 output height

    def unband(t):  # (nb, N, band//2, W/2, C) -> (N, ceil(H/2), W/2, C)
        t = jnp.moveaxis(t, 0, 1)
        return t.reshape(n, nb * (band // 2), *t.shape[3:])[:, :h2]

    u, v = unband(u_b), unband(v_b)
    return trunk_tail(trunk_params, batch_stats, u, v, norm_fn, dtype)


def trunk_tail(trunk_params, batch_stats, u, v, norm_fn, dtype):
    """layer2_0 tail + layer2_1 + layer3 at <= 1/2 resolution, from the
    full-resolution segment's two stride-2 outputs (``_segment`` upto=6).
    Shared by the banded executor above and the row-sharded executor
    (parallel/rows_sharded.py) — both stream/shard only the full-res
    segment and run this cheap tail on the assembled 1/2-res tensors."""
    l20 = trunk_params["layer2_0"]
    l20_b = _subtree(batch_stats, ("layer2_0",))

    def tail_norm(which, t):
        return _norm(norm_fn, l20, l20_b, (which,), dtype, None, t)

    y = jnn.relu(tail_norm("norm1", u))
    y = jnn.relu(tail_norm("norm2", _conv(l20["conv2"], y, 1, dtype)))
    x2 = jnn.relu(tail_norm("norm3", v) + y)

    x2 = _residual_block(trunk_params, batch_stats, x2, "layer2_1", 1,
                         norm_fn, dtype)
    x3 = _residual_block(trunk_params, batch_stats, x2, "layer3_0", 2,
                         norm_fn, dtype)
    return _residual_block(trunk_params, batch_stats, x3, "layer3_1", 1,
                           norm_fn, dtype)


def banded_supported(norm_fn: str, downsample: int) -> bool:
    return downsample == 2 and norm_fn in ("instance", "batch", "none")
