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
one row-block of the correlation volume.  So each (row, W1-block) tile:

  1. computes its volume tile  v = f1_tile @ f2_rowᵀ / √D  on the MXU,
     entirely in VMEM (never written to HBM — the fusion of SURVEY.md §7's
     kernels 9b and 9c), then
  2. hat-samples v exactly like the reg_fused lookup kernel
     (kernels/corr_lookup.py).

Per iteration this recomputes the volume tile (alt's memory/compute trade);
across ``corr_levels`` the right features come from the W-pooled pyramid the
XLA side builds once.

Backward (custom VJP, mirroring the identity):
    dv[w, x] = Σ_k g[w, k] · hat_k(x)        (the reg_fused backward kernel)
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

from raft_stereo_tpu.kernels.corr_lookup import (ROW_BLK, VMEM_BUDGET,
                                                 W1_BLK, log_launch_choice,
                                                 fused_lookup_available,
                                                 hat_sample, hat_scatter,
                                                 row_blk_for,
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
def _fwd_kernel(f1_ref, f2_ref, coords_ref, out_ref, *, radius: int,
                scale: float, inv_sqrt_d: float, precision):
    """(R, W1B, D) left tile + (R, W2, D) right rows + (R, W1B) centers
    → (R, W1B, K) window correlations."""
    f1 = f1_ref[:].astype(jnp.float32)
    f2 = f2_ref[:].astype(jnp.float32)
    # Volume tile on the MXU, VMEM-resident only: (R, W1B, W2).
    v = jax.lax.dot_general(f1, f2, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32,
                            precision=precision) * inv_sqrt_d
    centers = coords_ref[:, :, 0].astype(jnp.float32) * scale
    for k, sample in hat_sample(v, centers, radius):
        out_ref[:, :, k] = sample.astype(out_ref.dtype)


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
# VMEM, and at Middlebury-F scale (w2=496, d=256) the default ROW_BLK=8
# working set is ~12 MB before double buffering — so large shapes shrink the
# row block via the package-shared budget (corr_lookup.row_blk_for).
def _fwd_row_bytes(w1_blk, w2, d, itemsize, radius):
    fp32 = 4
    return (w2 * d * (itemsize + fp32)          # f2 rows: input + upcast
            + w1_blk * d * (itemsize + fp32)    # f1 tile: input + upcast
            + w1_blk * w2 * fp32                # volume tile
            + w1_blk * (w2 + 2 * radius) * fp32  # hat field
            + w1_blk * w2 * fp32)               # product intermediate


def _launch_fwd(f1, f2, coords, radius, scale, inv_sqrt_d,
                out_dtype=None):
    # ``out_dtype`` (default: f1's own dtype) exists for the int8
    # feature path: int8 features correlate to fp values (the in-kernel
    # fp32 upcast is the in-register dequant modulo the feature scales
    # the caller applies), so the output must not round through int8.
    rows, w1, d = f1.shape
    w2 = f2.shape[1]
    k = 2 * radius + 1
    rb = row_blk_for(_fwd_row_bytes(W1_BLK, w2, d, f1.dtype.itemsize,
                                    radius))
    grid = (pl.cdiv(rows, rb), pl.cdiv(w1, W1_BLK))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, radius=radius, scale=scale,
                          inv_sqrt_d=inv_sqrt_d,
                          precision=_precision_for(f1.dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, W1_BLK, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, w2, d), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, W1_BLK, 1), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rb, W1_BLK, k), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, w1, k),
                                       out_dtype or f1.dtype),
        interpret=_interpret(),
    )(f1, f2, coords[..., None])


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
    inv_sqrt_d = 1.0 / math.sqrt(d)
    out = _launch_fwd(f1.reshape(b * h, w1, d), f2.reshape(b * h, w2, d),
                      coords.reshape(b * h, w1), radius, scale, inv_sqrt_d)
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
# All pyramid levels in ONE kernel launch: the right-feature pyramid is
# concatenated along W (static level offsets) and each tile computes every
# level's volume slice + hat-samples it in the same pass.  Bit-identical to
# the per-level launches and ~1.5x faster at realtime shapes (410us ->
# 274us measured on a v5e chip) — launch overhead dominates at small W2.
def _fwd_multi_kernel(f1_ref, f2cat_ref, coords_ref, out_ref, *, radius: int,
                      offsets, widths, inv_sqrt_d: float, precision):
    f1 = f1_ref[:].astype(jnp.float32)
    centers0 = coords_ref[:].astype(jnp.float32)
    k = 2 * radius + 1
    for lvl, (off, w2) in enumerate(zip(offsets, widths)):
        f2 = f2cat_ref[:, off:off + w2, :].astype(jnp.float32)
        v = jax.lax.dot_general(f1, f2, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32,
                                precision=precision) * inv_sqrt_d
        for kk, sample in hat_sample(v, centers0 / (2 ** lvl), radius):
            out_ref[:, :, lvl * k + kk] = sample.astype(out_ref.dtype)




@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _alt_multi(f1, f2cat, coords, static):
    """Single-launch all-levels lookup.  ``static`` = (radius, offsets,
    widths) as hashable tuples."""
    radius, offsets, widths = static
    b, h, w1, d = f1.shape
    wcat = f2cat.shape[2]
    rows = b * h
    k = (2 * radius + 1) * len(offsets)
    grid = (pl.cdiv(rows, ROW_BLK), pl.cdiv(w1, W1_BLK))
    out = pl.pallas_call(
        functools.partial(_fwd_multi_kernel, radius=radius, offsets=offsets,
                          widths=widths, inv_sqrt_d=1.0 / math.sqrt(d),
                          precision=_precision_for(f1.dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROW_BLK, W1_BLK, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ROW_BLK, wcat, d), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ROW_BLK, W1_BLK), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((ROW_BLK, W1_BLK, k), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, w1, k), f1.dtype),
        interpret=_interpret(),
    )(f1.reshape(rows, w1, d), f2cat.reshape(rows, wcat, d),
      coords.reshape(rows, w1))
    return out.reshape(b, h, w1, k)


def _alt_multi_fwd(f1, f2cat, coords, static):
    return _alt_multi(f1, f2cat, coords, static), (f1, f2cat, coords)


def _alt_multi_bwd(static, residuals, g):
    # Backward calls the per-level backward launch directly (training cost
    # is conv-dominated; the forward launch count is what matters for
    # inference latency).
    radius, offsets, widths = static
    f1, f2cat, coords = residuals
    k = 2 * radius + 1
    df1 = jnp.zeros_like(f1)
    df2_parts = []
    for lvl, (off, w2) in enumerate(zip(offsets, widths)):
        f2 = f2cat[:, :, off:off + w2, :]
        d1, d2, _ = _alt_level_bwd(radius, 1.0 / (2 ** lvl),
                                   (f1, f2, coords),
                                   g[..., lvl * k:(lvl + 1) * k])
        df1 = df1 + d1
        df2_parts.append(d2)
    return df1, jnp.concatenate(df2_parts, axis=2), jnp.zeros_like(coords)


_alt_multi.defvjp(_alt_multi_fwd, _alt_multi_bwd)


# Mosaic's scoped-vmem (kernel stack) limit is 16 MiB on this generation,
# and its stack allocator does NOT reuse buffers across the unrolled level
# loop of `_fwd_multi_kernel` — the live set is the per-level SUM.  One
# hard calibration point: 544x960 fp32 (wcat=450, d=256) FAILS with a
# measured 18.11 MiB scoped allocation where `_multi_alt_scoped_bytes`
# estimates 14.71 MiB — the estimator runs ~1.23x low (compiler
# temporaries it can't see).  The gate threshold therefore sits at
# 16 MiB / 1.28 = 12.5 MiB of ESTIMATED bytes, so the worst gate-passing
# program lands at ~12.5 * 1.23 = 15.4 MiB of real allocation, inside the
# limit.  The realtime shape (wcat=292, bf16) estimates 10.39 MiB and is
# proven to compile and run (bench.py r02/r03).
_MOSAIC_SCOPED_VMEM = int(12.5 * 2 ** 20)


def _multi_alt_scoped_bytes(w2s, d: int, itemsize: int, radius: int) -> int:
    """Estimated Mosaic stack bytes of one `_fwd_multi_kernel` program:
    double-buffered input blocks, fp32 upcast copies (free when the input
    is already fp32), per-level volume + hat-field + product (all live —
    no cross-level reuse), and the double-buffered output block."""
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

    Uses the single-launch all-levels kernel when the whole program's
    Mosaic stack estimate fits the scoped-vmem limit; otherwise one launch
    per level, each with its row block shrunk to the VMEM budget
    (``row_blk_for``).  Read on the v5e (PERF.md section 5, PR 28): KITTI
    realtime shapes (W2 156/78, bf16) take the single launch; at 1984x2880
    (W2 720/360/180/90, D 256) the estimate is 3.1x the limit in
    float32 and in bfloat16 alike, so a lookup is four launches with row
    blocks 2/4/8/8, 6.5/3.7/2.8/2.1 ms each on one pair's 496 rows in
    float32: 8.9 % of the lookup's memory roofline, a quarter of a call."""
    d = fmap1.shape[-1]
    w2s = [f2.shape[2] for f2 in fmap2_pyramid]
    single = (_multi_alt_scoped_bytes(w2s, d, fmap1.dtype.itemsize, radius)
              <= _MOSAIC_SCOPED_VMEM)
    log_launch_choice(f"alt lookup D={d}", w2s, fmap1.dtype, single)
    if single:
        static = (radius,
                  tuple(int(sum(w2s[:i])) for i in range(len(w2s))),
                  tuple(int(w) for w in w2s))
        f2cat = jnp.concatenate(fmap2_pyramid, axis=2)
        return _alt_multi(fmap1, f2cat, coords, static)

    outs = [_alt_level(fmap1, f2, coords, radius, 1.0 / (2 ** i))
            for i, f2 in enumerate(fmap2_pyramid)]
    return jnp.concatenate(outs, axis=-1)


# ----------------------------------------------------- int8 feature entry
def _launch_fwd_multi_q(f1, f2cat, coords, radius: int, offsets, widths,
                        inv_sqrt_d: float, out_dtype):
    """Forward-only single-launch all-levels lookup over int8 features:
    the ``_fwd_multi_kernel`` body unchanged (its fp32 upcast is the
    in-register dequant), only the output dtype overridden."""
    rows, w1, d = f1.shape
    wcat = f2cat.shape[1]
    k = (2 * radius + 1) * len(offsets)
    grid = (pl.cdiv(rows, ROW_BLK), pl.cdiv(w1, W1_BLK))
    return pl.pallas_call(
        functools.partial(_fwd_multi_kernel, radius=radius,
                          offsets=offsets, widths=widths,
                          inv_sqrt_d=inv_sqrt_d,
                          precision=_precision_for(f1.dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROW_BLK, W1_BLK, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ROW_BLK, wcat, d), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ROW_BLK, W1_BLK), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((ROW_BLK, W1_BLK, k), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, w1, k), out_dtype),
        interpret=_interpret(),
    )(f1, f2cat, coords)


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

    Forward-only (inference tier, under ``stop_gradient``); same
    launch selection and scoped-VMEM gating as ``alt_lookup_fused``
    with the 1-byte itemsize shrinking the estimate."""
    from raft_stereo_tpu.kernels.corr_lookup import check_q_dtype

    check_q_dtype([fmap1_q] + list(fmap2_pyramid_q), q_dtype)
    d = fmap1_q.shape[-1]
    b, h, w1, _ = fmap1_q.shape
    w2s = [f2.shape[2] for f2 in fmap2_pyramid_q]
    rows = b * h
    inv_sqrt_d = 1.0 / math.sqrt(d)
    single = (_multi_alt_scoped_bytes(w2s, d, fmap1_q.dtype.itemsize,
                                      radius) <= _MOSAIC_SCOPED_VMEM)
    log_launch_choice(f"quantized alt lookup D={d}", w2s, fmap1_q.dtype, single)
    if single:
        offsets = tuple(int(sum(w2s[:i])) for i in range(len(w2s)))
        widths = tuple(int(w) for w in w2s)
        f2cat = jnp.concatenate(fmap2_pyramid_q, axis=2)
        out = _launch_fwd_multi_q(
            fmap1_q.reshape(rows, w1, d),
            f2cat.reshape(rows, sum(w2s), d),
            coords.reshape(rows, w1), radius, offsets, widths,
            inv_sqrt_d, out_dtype)
        return out.reshape(b, h, w1, -1)
    outs = []
    for i, f2 in enumerate(fmap2_pyramid_q):
        out = _launch_fwd(fmap1_q.reshape(rows, w1, d),
                          f2.reshape(rows, f2.shape[2], d),
                          coords.reshape(rows, w1), radius,
                          1.0 / (2 ** i), inv_sqrt_d,
                          out_dtype=out_dtype)
        outs.append(out.reshape(b, h, w1, -1))
    return jnp.concatenate(outs, axis=-1)
