"""Pallas TPU kernel: fused no-volume ("alt") correlation lookup.

TPU-native replacement for the reference's on-the-fly correlation backend
(reference: core/corr.py:64-107 PytorchAlternateCorrBlock1D), which exists so
full-resolution inputs never materialize the O(B·H·W1·W2) volume (reference:
README.md:121 recommends it for Middlebury-F).  The reference samples right-
feature windows with ``grid_sample`` and dots them with left features; on TPU
both the gather and the tiny dot products are hostile.

This kernel uses the algebraic identity

    out[w, k] = Σ_d f1[w, d] · interp_k(f2)[w, d]
              = hat_k ⊛ (f1 · f2ᵀ)[w, :]

i.e. a linear-interpolated feature dot product IS a hat-function reduction of
one row-block of the correlation volume.  So each (row, W1-block) tile, a
row at a time:

  1. computes its volume tile TRANSPOSED,  vT = f2_row @ f1_tileᵀ / √D,
     (W2, W1B) on the MXU: the right image's bins on the sublanes (padded
     to whole registers of 8, not to 128 lanes), the tile's 128 pixels on
     the lanes; entirely in VMEM (never written to HBM — the fusion of
     SURVEY.md §7's kernels 9b and 9c), then
  2. samples vT along the sublanes with the stored-volume kernel's own
     ``sublane_sample`` (kernels/corr_lookup.py; tests/test_corr_alt.py
     holds it to the plain ``hat_sample``): whole-register compares,
     selects and adds and one eight-sublane reduction per window bin — no
     reduction across lanes — and
  3. stores the taps as dense rows of a (K, W1B) block, pixels on the
     lanes; the result array is (rows, K, W1) and XLA takes the
     ``swapaxes`` to (rows, W1, K) as a layout of the next convolution's
     operand (a bitcast in the compiled realtime program, PR 29).

Per iteration this recomputes the volume tile (alt's memory/compute trade);
across ``corr_levels`` the right features come from the W-pooled pyramid the
XLA side builds once.

Backward (custom VJP, mirroring the identity):
    dv[w, x] = Σ_k g[w, k] · hat_k(x)        (``corr_lookup.hat_scatter``)
    df1      = dv @ f2
    df2      = dvᵀ @ f1
both matmuls fused into the same tile pass, so the backward never
materializes the volume either.  No coordinate gradient (RAFT detaches
coords each iteration — reference core/raft_stereo.py:109).
"""

from __future__ import annotations

import functools
import math
from typing import List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_stereo_tpu.kernels.corr_lookup import (ROW_BLK, SUBLANES,
                                                 VMEM_BUDGET, W1_BLK,
                                                 log_launch_choice,
                                                 fused_lookup_available,
                                                 hat_scatter, row_blk_for,
                                                 sublane_sample, w2_rows,
                                                 interpret_enabled as
                                                 _interpret)


def alt_fused_available() -> bool:
    return fused_lookup_available()


def alt_fused_fits(w2: int, d: int, itemsize: int, radius: int) -> bool:
    """False when even a ONE-row block of the (larger) backward launch
    exceeds the VMEM budget — row_blk_for cannot shrink below 1, so callers
    must fall back to the XLA path (make_corr_fn_alt) instead of hitting a
    Mosaic compile failure (e.g. W2 beyond ~4k at d=256 fp32)."""
    fp32 = 4
    bwd_row = (_fwd_row_bytes(W1_BLK, w2, d, itemsize, radius)
               + W1_BLK * d * fp32      # df1 tile
               + w2 * d * fp32          # df2 accumulator tile
               + W1_BLK * w2 * fp32)    # dv tile
    return bwd_row <= VMEM_BUDGET


# ------------------------------------------------------------------ kernels
@functools.partial(jax.jit, static_argnames=("radius", "w2", "inv_sqrt_d",
                                             "precision"))
def _row_taps(f1, f2, centers, *, radius: int, w2: int, inv_sqrt_d: float,
              precision):
    """One row of one level: (W1B, D) left tile, (W2p, D) right row and
    (1, W1B) centers → the taps as rows of (1, W1B).  The volume tile is
    produced transposed, (W2p, W1B), on the MXU and lives in VMEM only.
    Jitted so that a kernel's unrolled rows share ONE trace of it (the
    Mosaic lowering inlines the calls)."""
    vt = jax.lax.dot_general(f2, f1, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=precision) * inv_sqrt_d
    return sublane_sample(vt, centers, radius, w2)


def _fwd_kernel(f1_ref, *refs, radius: int, widths, scales,
                inv_sqrt_d: float, precision):
    """(R, W1B, D) left tile + per level (R, W2p, D) right rows +
    (R, 1, W1B) centers → (R, levels·K, W1B) window correlations, taps on
    the sublanes.  One level a launch, or every level of the pyramid."""
    *f2_refs, coords_ref, out_ref = refs
    for r in range(f1_ref.shape[0]):
        f1 = f1_ref[r].astype(jnp.float32)
        centers0 = coords_ref[r].astype(jnp.float32)
        taps = []
        for f2_ref, w2, scale in zip(f2_refs, widths, scales):
            taps += _row_taps(f1, f2_ref[r].astype(jnp.float32),
                              centers0 * scale, radius=radius, w2=w2,
                              inv_sqrt_d=inv_sqrt_d, precision=precision)
        out_ref[r] = jnp.concatenate(taps, axis=0).astype(out_ref.dtype)


def _bwd_kernel(f1_ref, f2_ref, coords_ref, g_ref, df1_ref, df2_ref, *,
                radius: int, scale: float, inv_sqrt_d: float,
                rows_total: int, w1_total: int, precision):
    """Tile transpose: reconstruct dv from the output cotangent with hat
    weights, then both feature gradients as matmuls of dv.

    df2 is accumulated over W1 blocks (grid dim 1): each block owns the same
    (R, W2, D) df2 tile, so the kernel adds into it after zeroing on the
    first block — Pallas TPU grids execute sequentially per core, making the
    accumulation race-free.

    dv is masked to the logical (rows, W1) extent: df2 reduces over the W1
    axis, so block-padding garbage (NaN in interpret mode) would otherwise
    contaminate every output element.
    """
    f1 = f1_ref[:].astype(jnp.float32)
    f2 = f2_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)          # (R, W1B, K)
    w2 = f2_ref.shape[1]
    centers = coords_ref[:, :, 0].astype(jnp.float32) * scale
    dv = hat_scatter(g, centers, w2, radius)   # (R, W1B, W2)
    r_blk, w1_blk = centers.shape
    row_idx = (pl.program_id(0) * r_blk
               + jax.lax.broadcasted_iota(jnp.int32, (r_blk, w1_blk, 1), 0))
    col_idx = (pl.program_id(1) * w1_blk
               + jax.lax.broadcasted_iota(jnp.int32, (r_blk, w1_blk, 1), 1))
    valid = (row_idx < rows_total) & (col_idx < w1_total)
    dv = jnp.where(valid, dv * inv_sqrt_d, 0.0)
    # df2 contracts over W1, so f1's padding must be zeroed as well:
    # 0 (masked dv) x NaN (padded f1) would still poison the reduction.
    f1 = jnp.where(valid, f1, 0.0)
    # df1[r, w1, d] = Σ_x dv[r, w1, x] f2[r, x, d]
    df1_ref[:] = jax.lax.dot_general(
        dv, f2, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=precision).astype(df1_ref.dtype)
    # df2[r, x, d] = Σ_w1 dv[r, w1, x] f1[r, w1, d], accumulated over blocks
    contrib = jax.lax.dot_general(
        dv, f1, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=precision)

    @pl.when(pl.program_id(1) == 0)
    def _zero():
        df2_ref[:] = jnp.zeros_like(df2_ref)

    df2_ref[:] += contrib.astype(df2_ref.dtype)


# ------------------------------------------------------------------- launch
def _precision_for(dtype) -> jax.lax.Precision:
    """fp32 features pay for exact (HIGHEST) MXU passes, matching the reg
    backend bit-for-bit; bf16 features take the fast single-pass path (the
    same trade the reference's fp16 CUDA kernel makes)."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


# Mosaic fails to compile (not fall back) when a program's live set exceeds
# VMEM, and at Middlebury-F scale (w2=720, d=256) the default ROW_BLK=8
# working set is ~23 MB before double buffering — so large shapes shrink the
# row block via the package-shared budget (corr_lookup.row_blk_for).
def _fwd_row_bytes(w1_blk, w2, d, itemsize, radius):
    """What sizes a row block, forward and (with its own tiles added)
    backward.  The last two terms are the backward's; since PR 29 the
    forward samples a row's tile out of the registers and holds neither,
    and keeps the row blocks this sum gave it (2/4/8/8 at W2
    720/360/180/90, D 256): the launch plan was not that PR's to move."""
    fp32 = 4
    return (w2 * d * (itemsize + fp32)          # f2 rows: input + upcast
            + w1_blk * d * (itemsize + fp32)    # f1 tile: input + upcast
            + w1_blk * w2 * fp32                # volume tile
            + w1_blk * (w2 + 2 * radius) * fp32  # hat field
            + w1_blk * w2 * fp32)               # product intermediate


def _launch_fwd(f1, f2s, coords, radius: int, scales, rb: int,
                out_dtype=None):
    """(rows, W1, D) + per level (rows, W2, D) + (rows, W1) →
    (rows, W1, levels·K) in ONE launch with row blocks of ``rb``.

    Each right-feature block reads past a width that is no whole number
    of sublane tiles (the sampler masks those rows); the kernel's own
    result has the taps on the sublanes and the pixels on the lanes,
    (rows, levels·K, W1), and XLA takes the ``swapaxes``.

    ``out_dtype`` (default: f1's own dtype) exists for the int8 feature
    path: int8 features correlate to fp values (the in-kernel fp32 upcast
    is the in-register dequant modulo the feature scales the caller
    applies), so the output must not round through int8."""
    rows, w1, d = f1.shape
    widths = tuple(int(f2.shape[1]) for f2 in f2s)
    k = (2 * radius + 1) * len(f2s)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, radius=radius, widths=widths,
                          scales=tuple(scales), inv_sqrt_d=1.0 / math.sqrt(d),
                          precision=_precision_for(f1.dtype)),
        grid=(pl.cdiv(rows, rb), pl.cdiv(w1, W1_BLK)),
        in_specs=[pl.BlockSpec((rb, W1_BLK, d), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM)]
                 + [pl.BlockSpec((rb, w2_rows(w2, f2.dtype.itemsize), d),
                                 lambda i, j: (i, 0, 0),
                                 memory_space=pltpu.VMEM)
                    for f2, w2 in zip(f2s, widths)]
                 + [pl.BlockSpec((rb, 1, W1_BLK), lambda i, j: (i, 0, j),
                                 memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((rb, k, W1_BLK), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, k, w1),
                                       out_dtype or f1.dtype),
        interpret=_interpret(),
    )(f1, *f2s, coords[:, None, :])
    return jnp.swapaxes(out, 1, 2)


def _launch_fwd_level(f1, f2, coords, radius: int, scale: float,
                      out_dtype=None):
    """One level a launch, its row block shrunk to the VMEM budget."""
    rb = row_blk_for(_fwd_row_bytes(W1_BLK, f2.shape[1], f1.shape[2],
                                    f1.dtype.itemsize, radius))
    return _launch_fwd(f1, [f2], coords, radius, (scale,), rb, out_dtype)


def _launch_fwd_multi(f1, f2s, coords, radius: int, out_dtype=None):
    """Every level of the pyramid in one launch."""
    return _launch_fwd(f1, f2s, coords, radius,
                       [1.0 / 2 ** i for i in range(len(f2s))], ROW_BLK,
                       out_dtype)


def _launch_bwd(f1, f2, coords, g, radius, scale, inv_sqrt_d):
    rows, w1, d = f1.shape
    w2 = f2.shape[1]
    k = 2 * radius + 1
    fp32 = 4
    rb = row_blk_for(
        _fwd_row_bytes(W1_BLK, w2, d, f1.dtype.itemsize, radius)
        + W1_BLK * d * fp32    # df1 tile
        + w2 * d * fp32        # df2 accumulator tile
        + W1_BLK * w2 * fp32)  # dv tile
    grid = (pl.cdiv(rows, rb), pl.cdiv(w1, W1_BLK))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, radius=radius, scale=scale,
                          inv_sqrt_d=inv_sqrt_d, rows_total=rows,
                          w1_total=w1, precision=_precision_for(f1.dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, W1_BLK, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, w2, d), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, W1_BLK, 1), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, W1_BLK, k), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((rb, W1_BLK, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, w2, d), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, w1, d), f1.dtype),
            jax.ShapeDtypeStruct((rows, w2, d), f2.dtype),
        ],
        interpret=_interpret(),
    )(f1, f2, coords[..., None], g)


# -------------------------------------------------------------- level entry
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _alt_level(f1, f2, coords, radius: int, scale: float):
    """(B,H,W1,D) left + (B,H,W2,D) right + (B,H,W1) centers
    → (B,H,W1,2r+1) correlations at one pyramid level."""
    b, h, w1, d = f1.shape
    w2 = f2.shape[2]
    out = _launch_fwd_level(f1.reshape(b * h, w1, d),
                            f2.reshape(b * h, w2, d),
                            coords.reshape(b * h, w1), radius, scale)
    return out.reshape(b, h, w1, -1)


def _alt_level_fwd(f1, f2, coords, radius, scale):
    return _alt_level(f1, f2, coords, radius, scale), (f1, f2, coords)


def _alt_level_bwd(radius, scale, residuals, g):
    f1, f2, coords = residuals
    b, h, w1, d = f1.shape
    w2 = f2.shape[2]
    inv_sqrt_d = 1.0 / math.sqrt(d)
    df1, df2 = _launch_bwd(f1.reshape(b * h, w1, d),
                           f2.reshape(b * h, w2, d),
                           coords.reshape(b * h, w1),
                           g.reshape(b * h, w1, -1), radius, scale,
                           inv_sqrt_d)
    return (df1.reshape(f1.shape), df2.reshape(f2.shape),
            jnp.zeros_like(coords))


_alt_level.defvjp(_alt_level_fwd, _alt_level_bwd)


# ---------------------------------------------------- multi-level forward
# All pyramid levels in ONE kernel launch (``_launch_fwd_multi``): the levels
# stay separate operands — no concatenated copy of the right features — and
# each row of a tile computes every level's transposed volume slice +
# samples it in the same pass.  Bit-identical to the per-level launches;
# launch overhead dominates at small W2.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _alt_multi(f1, f2s, coords, radius: int):
    """Single-launch all-levels lookup over the tuple ``f2s`` of
    (B,H,W2_i,D) right features."""
    b, h, w1, d = f1.shape
    rows = b * h
    out = _launch_fwd_multi(f1.reshape(rows, w1, d),
                            [f2.reshape(rows, f2.shape[2], d) for f2 in f2s],
                            coords.reshape(rows, w1), radius)
    return out.reshape(b, h, w1, -1)


def _alt_multi_fwd(f1, f2s, coords, radius):
    return _alt_multi(f1, f2s, coords, radius), (f1, f2s, coords)


def _alt_multi_bwd(radius, residuals, g):
    # Backward calls the per-level backward launch directly (training cost
    # is conv-dominated; the forward launch count is what matters for
    # inference latency).
    f1, f2s, coords = residuals
    k = 2 * radius + 1
    df1 = jnp.zeros_like(f1)
    df2s = []
    for lvl, f2 in enumerate(f2s):
        d1, d2, _ = _alt_level_bwd(radius, 1.0 / (2 ** lvl),
                                   (f1, f2, coords),
                                   g[..., lvl * k:(lvl + 1) * k])
        df1 = df1 + d1
        df2s.append(d2)
    return df1, tuple(df2s), jnp.zeros_like(coords)


_alt_multi.defvjp(_alt_multi_fwd, _alt_multi_bwd)


# The gate of the launch PLAN.  Mosaic's scoped-vmem (kernel stack) limit is
# 16 MiB on this generation.  The estimate below and its threshold were
# calibrated on the body this module had before PR 29, which held every
# level's (R, W1B, W2) tile, hat field and product at once: 544x960 fp32
# (wcat=450, d=256) FAILED with a measured 18.11 MiB where the estimate
# says 14.71 MiB (1.23x low), so the threshold sits at 16 MiB / 1.28.
# Today's body works a row at a time and holds little beside its
# double-buffered blocks: by bisection of ``vmem_limit_bytes`` in compiles
# for a described v5e (PR 29) the all-levels program needs 3.7-4.0 MiB at
# the realtime shape (W2 156/78/39/19, bf16), 6.7-7.0 MiB at 544x960 fp32
# (it compiles as one launch now), 11.2-11.5 MiB at W2 720/360/180/90 in
# bf16 (it compiles under the default limit too) and 21.9-22.2 MiB there
# in fp32 (it does not).  PR 29 kept the plan as it was, so that it changed
# one thing (one launch at KITTI realtime widths, four at 544x960 fp32 and
# at 1984x2880); moving the gate to what the body needs now is PERF.md
# section 7's next step.
_MOSAIC_SCOPED_VMEM = int(12.5 * 2 ** 20)


def _multi_alt_scoped_bytes(w2s, d: int, itemsize: int, radius: int) -> int:
    """The launch plan's estimate of one all-levels program (see above:
    what the pre-PR-29 body held, NOT today's live set, which is the
    double-buffered blocks plus one row's upcast features, transposed
    tiles and taps): double-buffered input blocks, fp32 upcast copies
    (none when the input is already fp32), three (R, W1B, W2) fp32 arrays
    a level, and the double-buffered output block."""
    fp32 = 4
    k = 2 * radius + 1
    wcat = sum(w2s)
    inputs = 2 * ROW_BLK * (wcat + W1_BLK) * d * itemsize
    upcasts = (0 if itemsize == fp32
               else ROW_BLK * (wcat + W1_BLK) * d * fp32)
    per_level = ROW_BLK * W1_BLK * sum(
        2 * w2 + (w2 + 2 * radius) for w2 in w2s) * fp32
    out = 2 * ROW_BLK * W1_BLK * len(w2s) * k * fp32
    return inputs + upcasts + per_level + out


def alt_lookup_fused(fmap1: jnp.ndarray, fmap2_pyramid: List[jnp.ndarray],
                     coords: jnp.ndarray, radius: int) -> jnp.ndarray:
    """Fused no-volume window correlation at every level, concat level-major —
    drop-in for the XLA alt lookup in models/corr.py make_corr_fn_alt.

    Uses the single-launch all-levels kernel when the launch plan's
    estimate fits its gate (``_multi_alt_scoped_bytes``); otherwise one
    launch per level, each with its row block shrunk to the VMEM budget
    (``row_blk_for``).  Read on the v5e (PERF.md sections 5 and 6, PR 29,
    the builder's traced runs of the two bulk cells): KITTI realtime
    shapes (W2 156/78/39/19, bf16, 6144 rows) take the single launch,
    4.29 ms (31.8 ms with the tile sampled along the lanes: 42.3 % of the
    lookup's memory roofline against 5.69 %); at 1984x2880 (W2
    720/360/180/90, D 256) a lookup is four launches with row blocks
    2/4/8/8, 4.86/2.50/1.36/0.77 ms each on one pair's 496 rows in float32
    (6.49/3.73/2.79/2.09 before): 14.2 % of the roofline, level 0 held by
    the MXU's six ``HIGHEST`` passes (~4 ms)."""
    d = fmap1.shape[-1]
    w2s = [f2.shape[2] for f2 in fmap2_pyramid]
    single = (_multi_alt_scoped_bytes(w2s, d, fmap1.dtype.itemsize, radius)
              <= _MOSAIC_SCOPED_VMEM)
    log_launch_choice(f"alt lookup D={d}", w2s, fmap1.dtype, single)
    if single:
        return _alt_multi(fmap1, tuple(fmap2_pyramid), coords, radius)

    outs = [_alt_level(fmap1, f2, coords, radius, 1.0 / (2 ** i))
            for i, f2 in enumerate(fmap2_pyramid)]
    return jnp.concatenate(outs, axis=-1)


# ----------------------------------------------------- int8 feature entry
def alt_lookup_fused_q(fmap1_q: jnp.ndarray,
                       fmap2_pyramid_q: List[jnp.ndarray],
                       coords: jnp.ndarray, radius: int,
                       out_dtype, q_dtype=None) -> jnp.ndarray:
    """The no-volume lookup over QUANTIZED feature maps (round-15
    turbo tier; fp8-capable since r22): each tile's volume slice is
    computed on the MXU from 1-byte features upcast in-register — the
    features move 1/4 (vs fp32) or 1/2 (vs bf16) of the HBM bytes per
    iteration.  The RAW quantized-grid correlations come back in
    ``out_dtype``; the caller applies the combined feature scales
    ``s1 * s2_level`` per level (models/corr.py) — the dot product is
    bilinear, so the scales factor out exactly.

    ``q_dtype`` is the shared grid coordinate (``int8`` default /
    ``float8_e4m3`` behind ``fp8_corr_available()``) — validated by the
    same ``check_q_dtype`` contract as ``lookup_pyramid_fused_q``; the
    kernel body is dtype-generic.

    Forward-only (inference tier, under ``stop_gradient``); the kernel
    bodies, launch selection and scoped-VMEM gating of
    ``alt_lookup_fused`` (their fp32 upcast is the in-register dequant)
    with the 1-byte itemsize shrinking the estimate and only the output
    dtype overridden."""
    from raft_stereo_tpu.kernels.corr_lookup import check_q_dtype

    check_q_dtype([fmap1_q] + list(fmap2_pyramid_q), q_dtype)
    d = fmap1_q.shape[-1]
    b, h, w1, _ = fmap1_q.shape
    w2s = [f2.shape[2] for f2 in fmap2_pyramid_q]
    rows = b * h
    f1 = fmap1_q.reshape(rows, w1, d)
    f2s = [f2.reshape(rows, f2.shape[2], d) for f2 in fmap2_pyramid_q]
    coords = coords.reshape(rows, w1)
    single = (_multi_alt_scoped_bytes(w2s, d, fmap1_q.dtype.itemsize,
                                      radius) <= _MOSAIC_SCOPED_VMEM)
    log_launch_choice(f"quantized alt lookup D={d}", w2s, fmap1_q.dtype, single)
    if single:
        out = _launch_fwd_multi(f1, f2s, coords, radius, out_dtype)
    else:
        out = jnp.concatenate(
            [_launch_fwd_level(f1, f2, coords, radius, 1.0 / 2 ** i,
                               out_dtype) for i, f2 in enumerate(f2s)],
            axis=-1)
    return out.reshape(b, h, w1, -1)
