"""Pallas TPU kernel: fused correlation-pyramid window lookup.

TPU-native replacement for the reference's CUDA extension (reference:
sampler/sampler.cpp + sampler/sampler_kernel.cu): sample a (2r+1)-tap window
of the 1-D correlation volume at fractional disparity positions, with linear
interpolation and zero padding, in the volume's own dtype (bf16-safe — the
whole point of the reference's fp16 CUDA path, sampler_kernel.cu:126).

Design: gathers are hostile to the TPU vector unit, so the kernel never
gathers.  For tap k the interpolation weight of volume bin x at center c is
the hat function  max(0, 1 - |x - (c + k - r)|)  — nonzero for at most the
two bins the reference's CUDA kernel reads (sampler_kernel.cu:46-59).  Each
(rows × W1-block) tile computes, per tap, an elementwise weight field over
the whole W2 axis and a multiply-reduce — pure VPU work on contiguous lanes,
O(K·W2) per pixel instead of a 2-bin gather, which wins on TPU because it
vectorizes and the volume tile is already in VMEM.

Backward mirrors the reference's hand-written scatter kernel
(sampler_kernel.cu:64-105) but needs no atomics: dV[x] = Σ_k g_k·hat_k(x) is
again an elementwise multiply-accumulate.  Like the reference's
``CorrSampler.backward`` (core/corr.py:24-29), no coordinate gradient is
produced — RAFT-Stereo detaches coords before every lookup
(core/raft_stereo.py:109).
"""

from __future__ import annotations

import functools
import logging
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

log = logging.getLogger(__name__)

ROW_BLK = 8       # (batch·H) rows per tile
W1_BLK = 128      # output pixels per tile (lane-aligned)

# Single per-program VMEM budget shared by ALL correlation kernels in this
# package (this module and kernels/corr_alt.py).  Mosaic FAILS TO COMPILE
# (no fallback) when a program's live set exceeds VMEM, so every launch
# either gates on a working-set estimate or shrinks its row block with
# ``row_blk_for`` until it fits.
VMEM_BUDGET = 8 * 2 ** 20


def row_blk_for(per_row_bytes: int) -> int:
    """Largest power-of-two row block (≤ ROW_BLK) whose per-program working
    set fits ``VMEM_BUDGET``; callers pass bytes-per-row-of-ROW_BLK=1."""
    rb = ROW_BLK
    while rb > 1 and rb * per_row_bytes > VMEM_BUDGET:
        rb //= 2
    return rb


def _lookup_row_bytes(w2: int, radius: int, itemsize: int) -> int:
    """Per-row working set of the single-level lookup kernels: volume tile
    (input + fp32 upcast), hat field, product/scatter intermediate, out."""
    fp32 = 4
    k = 2 * radius + 1
    return W1_BLK * (w2 * (itemsize + fp32)
                     + (w2 + 2 * radius) * fp32
                     + w2 * fp32
                     + k * fp32)

_interpret_override: Optional[bool] = None


# message -> times said, in the order first said.  Trace-time only: a
# choice is made per traced shape, not per call, so this stays small.
_path_choices: Dict[str, int] = {}


def log_path_once(message: str) -> None:
    """Trace-time record of a shape-driven choice between a kernel and its
    fallback (or between two launch plans), shared by every kernel family
    of the package.  Logged once per distinct message; every time it is
    said is counted, so a caller that traces a program can tell which
    choices that trace made (``path_choices`` before and after)."""
    if message not in _path_choices:
        log.info("kernel path: %s", message)
    _path_choices[message] = _path_choices.get(message, 0) + 1


def path_choices() -> Dict[str, int]:
    """What ``log_path_once`` has been told in this process: each distinct
    message with the number of times it was said."""
    return dict(_path_choices)


def log_launch_choice(what: str, w2s, dtype, single: bool) -> None:
    log_path_once(
        f"{what} W2={'/'.join(str(w) for w in w2s)} "
        f"{jnp.dtype(dtype).name}: "
        + ("single all-levels launch" if single else
           "one launch per level (the all-levels working set exceeds "
           "the VMEM budget)"))


def fused_lookup_available() -> bool:
    if _interpret_override:  # interpret mode works on any backend
        return True
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover
        return False


def interpret_enabled() -> bool:
    """True when kernels run via the HLO interpreter (CPU tests)."""
    return bool(_interpret_override)


_interpret = interpret_enabled  # internal alias


# ------------------------------------------------- fp8 q-entry capability
# float8_e4m3 correlation entries: same itemsize as int8 (the VMEM-fit
# estimators below are already itemsize-parameterized, so every budget
# holds unchanged), but a FLOAT grid — denser near zero where the
# post-softargmax correlation mass lives.  Availability is a separate
# capability from the fused kernels themselves: the backend must execute
# the dtype (interpret mode counts — CPU parity tests run the same kernel
# body through the interpreter).
# The grid is OCP E4M3 (``float8_e4m3fn``: finite-only, max 448 — the
# variant TPU/GPU fp8 units implement), not the IEEE ``float8_e4m3``
# whose 240 finite max would overflow the 448-referenced scales.
FP8_CORR_DTYPE = jnp.float8_e4m3fn


def fp8_corr_available() -> bool:
    """Whether fp8 correlation q-entries can run here: gate BEFORE
    building an fp8 pyramid (models/corr.corr_q_dtype falls back to
    int8 when this is False — same transparent-fallback contract as
    fused_lookup_available)."""
    return fused_lookup_available()


def _q_dtypes_supported():
    return (jnp.dtype(jnp.int8), jnp.dtype(FP8_CORR_DTYPE))


def check_q_dtype(pyramid, q_dtype):
    """Validate one q-entry call's dtype coordinate: every level must
    carry ``q_dtype`` (None = infer from level 0), and the dtype must be
    a supported quantized grid.  Returns the resolved ``jnp.dtype``."""
    q_dtype = jnp.dtype(q_dtype if q_dtype is not None
                        else pyramid[0].dtype)
    if q_dtype not in _q_dtypes_supported():
        raise ValueError(
            f"q_dtype={q_dtype} not a supported quantized grid "
            f"{tuple(str(d) for d in _q_dtypes_supported())}")
    bad = [str(v.dtype) for v in pyramid if jnp.dtype(v.dtype) != q_dtype]
    if bad:
        raise ValueError(
            f"q-entry levels must all be {q_dtype}; got {bad}")
    if q_dtype == jnp.dtype(FP8_CORR_DTYPE) and not fp8_corr_available():
        raise ValueError(
            "fp8 correlation entries are unavailable on this backend "
            "(fp8_corr_available() is False) — quantize int8 instead")
    return q_dtype


# -------------------------------------------------- shared hat-sample math
# The hat-function formulation (module docstring) shared by this kernel and
# the fused no-volume kernel (kernels/corr_alt.py) — one implementation so
# boundary/interpolation semantics can never diverge between them.
def _hat_field(centers, w2: int, radius: int):
    """Shared per-tap weights: tap k's weight at bin x is
    ``max(0, 1-|x - centers - (k-radius)|)`` = F[x + 2·radius - k] where
    F[j] = max(0, 1-|j - radius - centers|) over j ∈ [0, w2+2·radius).
    Computing F ONCE and slicing per tap replaces ~6 vector passes per tap
    (iota, sub, abs, sub, max, mul) with 2 (mul, add) — a training trace
    on an earlier runtime found the VPU weight construction, not DMA or
    launch overhead, dominating the lookup (not re-measured on the v5e)."""
    ext = w2 + 2 * radius
    xs = jax.lax.broadcasted_iota(jnp.int32, (1, 1, ext), 2).astype(jnp.float32)
    return jnp.maximum(0.0, 1.0 - jnp.abs(xs - radius - centers[..., None]))


def hat_sample(v, centers, radius: int):
    """Σ_x v[..., x] · hat_k(x) for each tap k: (R, W1B, W2) tile +
    (R, W1B) centers → per-tap sampler yielding (R, W1B) slices."""
    w2 = v.shape[-1]
    f = _hat_field(centers, w2, radius)
    for k in range(2 * radius + 1):
        off = 2 * radius - k
        yield k, jnp.sum(v * f[:, :, off:off + w2], axis=-1)


def hat_scatter(g, centers, w2: int, radius: int):
    """Transpose of :func:`hat_sample`: (R, W1B, K) cotangent + centers
    → (R, W1B, W2) volume cotangent."""
    f = _hat_field(centers, w2, radius)
    acc = jnp.zeros(centers.shape + (w2,), jnp.float32)
    for k in range(2 * radius + 1):
        off = 2 * radius - k
        acc = acc + g[:, :, k][..., None] * f[:, :, off:off + w2]
    return acc


# ------------------------------------------------------------------ kernels
def _fwd_kernel(vol_ref, coords_ref, out_ref, *, radius: int, scale: float):
    """One (row-block, W1_BLK) tile: volume (R, W1B, W2) + centers
    (R, W1B, 1) → window samples (R, W1B, K)."""
    vol = vol_ref[:].astype(jnp.float32)              # (R, W1B, W2)
    centers = coords_ref[:, :, 0].astype(jnp.float32) * scale   # (R, W1B)
    for k, sample in hat_sample(vol, centers, radius):
        out_ref[:, :, k] = sample.astype(out_ref.dtype)


def _bwd_kernel(coords_ref, g_ref, dvol_ref, *, radius: int, scale: float):
    """Tile transpose of the forward: g (R, W1B, K) → dV (R, W1B, W2)."""
    centers = coords_ref[:, :, 0].astype(jnp.float32) * scale
    g = g_ref[:].astype(jnp.float32)
    dvol = hat_scatter(g, centers, dvol_ref.shape[-1], radius)
    dvol_ref[:] = dvol.astype(dvol_ref.dtype)


# ------------------------------------------------------------------- launch
# coords blocks carry a trailing singleton so the (8, 128)-divisibility rule
# on the last two block dims keeps holding when the row block shrinks below
# 8 for VMEM (large W2).
def _launch_fwd(vol: jnp.ndarray, coords: jnp.ndarray, radius: int,
                scale: float, out_dtype=None) -> jnp.ndarray:
    # ``out_dtype`` (default: the volume's own dtype) exists for the
    # int8 pyramid path: an int8 volume samples to fp values (the
    # in-kernel fp32 upcast IS the in-register dequant modulo the
    # per-level scale the caller applies), so the output must not
    # round-trip through int8.
    rows, w1, w2 = vol.shape
    k = 2 * radius + 1
    rb = row_blk_for(_lookup_row_bytes(w2, radius, vol.dtype.itemsize))
    grid = (pl.cdiv(rows, rb), pl.cdiv(w1, W1_BLK))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, radius=radius, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, W1_BLK, w2), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, W1_BLK, 1), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rb, W1_BLK, k), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, w1, k),
                                       out_dtype or vol.dtype),
        interpret=_interpret(),
    )(vol, coords[..., None])


def _launch_bwd(coords: jnp.ndarray, g: jnp.ndarray, w2: int, radius: int,
                scale: float, dtype) -> jnp.ndarray:
    rows, w1 = coords.shape
    k = 2 * radius + 1
    rb = row_blk_for(_lookup_row_bytes(w2, radius, jnp.dtype(dtype).itemsize))
    grid = (pl.cdiv(rows, rb), pl.cdiv(w1, W1_BLK))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, radius=radius, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, W1_BLK, 1), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, W1_BLK, k), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rb, W1_BLK, w2), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, w1, w2), dtype),
        interpret=_interpret(),
    )(coords[..., None], g)


# ----------------------------------------------------------- level sampling
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _sample_level(vol, coords, radius: int, scale: float):
    """(B,H,W1,W2) volume + (B,H,W1) centers → (B,H,W1,2r+1) window."""
    b, h, w1, w2 = vol.shape
    out = _launch_fwd(vol.reshape(b * h, w1, w2),
                      coords.reshape(b * h, w1), radius, scale)
    return out.reshape(b, h, w1, -1)


def _sample_level_fwd(vol, coords, radius, scale):
    # vol rides along only for its STATIC shape/dtype; its values are unused
    # in the backward, so XLA dead-code-eliminates the residual.
    return _sample_level(vol, coords, radius, scale), (vol, coords)


def _sample_level_bwd(radius, scale, residuals, g):
    vol, coords = residuals
    b, h, w1, w2 = vol.shape
    dvol = _launch_bwd(coords.reshape(b * h, w1),
                       g.reshape(b * h, w1, -1), w2, radius, scale,
                       vol.dtype)
    # No coords grad: RAFT detaches coords before every lookup, and the
    # reference kernel's backward also only produces volume gradients.
    return dvol.reshape(vol.shape), jnp.zeros_like(coords)


_sample_level.defvjp(_sample_level_fwd, _sample_level_bwd)


# ----------------------------------------- single-launch all-levels lookup
# Training-trace finding (an earlier runtime): each custom call inside the
# 22-iteration scan carries ~1 ms of in-graph overhead/stall far above its
# isolated runtime (26 us), so 12 per-iteration launches (4 fwd + 4 remat
# recompute + 4 bwd) dominate the step.  Sampling EVERY level in one launch
# (and all level cotangents in one backward launch) cuts that to 3.  The
# levels stay separate pallas_call operands — no concatenated-volume copy.

def _fwd_kernel_multi(*refs, radius: int, levels: int):
    coords = refs[levels][:, :, 0].astype(jnp.float32)
    out_ref = refs[levels + 1]
    k = 2 * radius + 1
    for i in range(levels):
        vol = refs[i][:].astype(jnp.float32)
        centers = coords * (1.0 / (2 ** i))
        for kk, sample in hat_sample(vol, centers, radius):
            out_ref[:, :, i * k + kk] = sample.astype(out_ref.dtype)


def _bwd_kernel_multi(coords_ref, g_ref, *dvol_refs, radius: int,
                      levels: int):
    coords = coords_ref[:, :, 0].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    k = 2 * radius + 1
    for i in range(levels):
        centers = coords * (1.0 / (2 ** i))
        dvol = hat_scatter(g[:, :, i * k:(i + 1) * k], centers,
                           dvol_refs[i].shape[-1], radius)
        dvol_refs[i][:] = dvol.astype(dvol_refs[i].dtype)


def _launch_fwd_multi(vols, coords, radius: int, out_dtype=None):
    rows, w1 = coords.shape
    levels = len(vols)
    k = 2 * radius + 1
    grid = (pl.cdiv(rows, ROW_BLK), pl.cdiv(w1, W1_BLK))
    return pl.pallas_call(
        functools.partial(_fwd_kernel_multi, radius=radius, levels=levels),
        grid=grid,
        in_specs=[pl.BlockSpec((ROW_BLK, W1_BLK, v.shape[-1]),
                               lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM) for v in vols]
                 + [pl.BlockSpec((ROW_BLK, W1_BLK, 1), lambda i, j: (i, j, 0),
                                 memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((ROW_BLK, W1_BLK, levels * k),
                               lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, w1, levels * k),
                                       out_dtype or vols[0].dtype),
        interpret=_interpret(),
    )(*vols, coords[..., None])


def _launch_bwd_multi(coords, g, w2s, radius: int, dtype):
    rows, w1 = coords.shape
    levels = len(w2s)
    k = 2 * radius + 1
    grid = (pl.cdiv(rows, ROW_BLK), pl.cdiv(w1, W1_BLK))
    return pl.pallas_call(
        functools.partial(_bwd_kernel_multi, radius=radius, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROW_BLK, W1_BLK, 1), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ROW_BLK, W1_BLK, levels * k), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[pl.BlockSpec((ROW_BLK, W1_BLK, w2), lambda i, j: (i, j, 0),
                                memory_space=pltpu.VMEM) for w2 in w2s],
        out_shape=[jax.ShapeDtypeStruct((rows, w1, w2), dtype)
                   for w2 in w2s],
        interpret=_interpret(),
    )(coords[..., None], g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sample_pyramid(vols, coords, radius: int):
    """Tuple of (B,H,W1,W2_i) volumes + (B,H,W1) centers →
    (B,H,W1,levels·(2r+1)) window samples, concat level-major."""
    b, h, w1, _ = vols[0].shape
    out = _launch_fwd_multi([v.reshape(b * h, w1, v.shape[-1]) for v in vols],
                            coords.reshape(b * h, w1), radius)
    return out.reshape(b, h, w1, -1)


def _sample_pyramid_fwd(vols, coords, radius):
    # volumes ride along for static shape/dtype only; values unused in bwd
    return _sample_pyramid(vols, coords, radius), (vols, coords)


def _multi_bwd_scoped_bytes(w2s, radius: int, itemsize: int) -> int:
    """Mosaic's scoped-VMEM allocation for one ``_bwd_kernel_multi``
    program, calibrated against the v5e compiler (the forward estimator
    ``_multi_working_set`` says nothing about it — the backward WRITES a
    tile per level and they are all live at once, the unrolled level loop
    shares no buffers).  Lanes pad to 128; per padded bin the program
    holds four fp32 temporaries (hat field, its shifted slice, the
    product, the accumulator) plus the double-buffered output tile; the
    cotangent and coordinate blocks are double-buffered too.  Calibration
    (reported / estimated MiB): fp32 W2 180/90/45/22 16.32 / 17.0, fp32
    312/156/78/39 22.42 / 23.0, bf16 312/156/78/39 18.67 / 19.0."""
    fp32 = 4
    tile = ROW_BLK * W1_BLK

    def padded(n: int, lanes: int = 128) -> int:
        return -(-n // lanes) * lanes

    bins = sum(padded(w2) for w2 in w2s)
    taps = padded(len(w2s) * (2 * radius + 1))
    return (tile * bins * (4 * fp32 + 2 * itemsize)
            + 2 * tile * (taps * itemsize + padded(1) * fp32))


def _sample_pyramid_bwd(radius, residuals, g):
    vols, coords = residuals
    b, h, w1, _ = vols[0].shape
    w2s = [v.shape[-1] for v in vols]
    dtype = vols[0].dtype
    coords2 = coords.reshape(b * h, w1)
    g2 = g.reshape(b * h, w1, -1)
    # Mosaic's scoped-VMEM limit is twice the package's per-program
    # budget (the other half is the pipeline's double buffering).  The
    # all-levels backward outgrows it before the forward does (fp32 at
    # the SceneFlow crop: 16.32 MiB), so it falls to one launch per
    # level — whose row block shrinks to fit — on its own; the forward
    # keeps its single launch either way.
    single = (_multi_bwd_scoped_bytes(w2s, radius, dtype.itemsize)
              <= 2 * VMEM_BUDGET)
    log_launch_choice("lookup backward", w2s, dtype, single)
    if single:
        dvols = _launch_bwd_multi(coords2, g2, w2s, radius, dtype)
    else:
        k = 2 * radius + 1
        dvols = [_launch_bwd(coords2, g2[:, :, i * k:(i + 1) * k], w2,
                             radius, 1.0 / (2 ** i), dtype)
                 for i, w2 in enumerate(w2s)]
    return (tuple(d.reshape(b, h, w1, -1) for d in dvols),
            jnp.zeros_like(coords))


_sample_pyramid.defvjp(_sample_pyramid_fwd, _sample_pyramid_bwd)


def _multi_working_set(w2s, radius: int, itemsize: int) -> int:
    """Bytes one program of ``_fwd_kernel_multi`` holds live: per level the
    input tile, its fp32 upcast, and the (w2+2r)-wide fp32 hat field; plus
    the per-tap multiply-reduce product (one level live at a time — sized by
    the widest level, matching the ``w2 * fp32`` term ``_lookup_row_bytes``
    counts so the two estimators agree) and the all-levels output tile."""
    fp32 = 4
    k = 2 * radius + 1
    per_level = sum(
        ROW_BLK * W1_BLK * (w2 * (itemsize + fp32) + (w2 + 2 * radius) * fp32)
        for w2 in w2s)
    return (per_level
            + ROW_BLK * W1_BLK * max(w2s) * fp32
            + ROW_BLK * W1_BLK * len(w2s) * k * fp32)


def lookup_pyramid_fused(pyramid: List[jnp.ndarray], coords: jnp.ndarray,
                         radius: int) -> jnp.ndarray:
    """Fused window lookup at every pyramid level, concat level-major —
    drop-in replacement for ``lookup_pyramid_xla`` (models/corr.py).

    Uses the single-launch all-levels kernel when every level's tile fits
    the per-program VMEM budget together; otherwise one launch per level
    (full-resolution volumes grow ~linearly in W2 and must not turn a
    previously-working eval into a Mosaic VMEM compile failure)."""
    w2s = [v.shape[-1] for v in pyramid]
    single = (len(pyramid) > 1 and _multi_working_set(
        w2s, radius, pyramid[0].dtype.itemsize) <= VMEM_BUDGET)
    log_launch_choice("lookup", w2s, pyramid[0].dtype, single)
    if single:
        return _sample_pyramid(tuple(pyramid), coords, radius)
    outs = [_sample_level(vol, coords, radius, 1.0 / (2 ** i))
            for i, vol in enumerate(pyramid)]
    return jnp.concatenate(outs, axis=-1)


# -------------------------------------------------- quantized pyramid entry
def lookup_pyramid_fused_q(pyramid: List[jnp.ndarray],
                           coords: jnp.ndarray, radius: int,
                           out_dtype, q_dtype=None) -> jnp.ndarray:
    """Fused window lookup over a QUANTIZED pyramid (round-15 turbo
    tier; fp8-capable since r22): the kernels read the 1-byte volume
    tiles from HBM — 1/4 (vs fp32) or 1/2 (vs bf16) of the bytes the
    memory-bound lookup moves (PERF.md section 3: its roofline) — and the
    in-kernel fp32 upcast of each tile is the in-register dequant.  The
    caller applies the per-level scales to the RAW sampled output
    (models/corr.py): hat sampling is linear, so ``scale * sample(q)``
    equals ``sample(scale * q)`` exactly.

    ``q_dtype`` is the grid coordinate: ``int8`` (default, inferred) or
    ``float8_e4m3`` where ``fp8_corr_available()`` — the kernel body is
    dtype-generic (the upcast handles either), so the coordinate
    validates and gates rather than switching code paths; every VMEM
    fit already keys on the itemsize, identical for both grids.

    Forward-only by design — the quantized tier is inference-only and
    runs under ``stop_gradient`` (the fp custom-VJP entries above stay
    the training path), so no quantized cotangent program exists to get
    wrong.  Same multi-vs-per-level launch selection and VMEM gating as
    ``lookup_pyramid_fused`` (itemsize=1 shrinks the working set, so
    the single-launch path holds to larger shapes)."""
    check_q_dtype(pyramid, q_dtype)
    b, h, w1, _ = pyramid[0].shape
    w2s = [v.shape[-1] for v in pyramid]
    single = (len(pyramid) > 1 and _multi_working_set(
        w2s, radius, pyramid[0].dtype.itemsize) <= VMEM_BUDGET)
    log_launch_choice("quantized lookup", w2s, pyramid[0].dtype, single)
    if single:
        out = _launch_fwd_multi(
            [v.reshape(b * h, w1, v.shape[-1]) for v in pyramid],
            coords.reshape(b * h, w1), radius, out_dtype=out_dtype)
        return out.reshape(b, h, w1, -1)
    outs = []
    for i, vol in enumerate(pyramid):
        out = _launch_fwd(vol.reshape(b * h, w1, vol.shape[-1]),
                          coords.reshape(b * h, w1), radius,
                          1.0 / (2 ** i), out_dtype=out_dtype)
        outs.append(out.reshape(b, h, w1, -1))
    return jnp.concatenate(outs, axis=-1)
