"""1-D (epipolar) all-pairs correlation backends.

The reference's performance-critical switch (reference: core/corr.py, dispatch
at core/raft_stereo.py:90-100) — all backends implement one contract:

    corr_fn = make_corr_fn(config, fmap1, fmap2)   # NHWC feature maps
    feats   = corr_fn(coords_x)                    # (B,H,W1) x-positions
    # feats: (B, H, W1, corr_levels * (2*radius+1)), level-major channels

Backends:
* ``reg``       — precompute the all-pairs (B,H,W1,W2) volume as a batched
                  matmul (MXU), average-pool a W2 pyramid, and look windows up
                  with the XLA 1-D linear sampler.  Correctness reference.
                  (≙ reference CorrBlock1D, core/corr.py:110-156.)
* ``alt``       — no precomputed volume: per lookup, linearly sample the
                  (progressively W-pooled) right feature map and dot with the
                  left features.  O(H·W·(2r+1)·D) per iteration instead of
                  O(H·W²) memory — the full-resolution / "long-context" path.
                  (≙ reference PytorchAlternateCorrBlock1D, core/corr.py:64-107.)
* ``reg_fused`` — same math as ``reg`` with the pyramid lookup fused into a
                  Pallas TPU kernel (≙ reference CorrBlockFast1D + the CUDA
                  sampler/ extension), bf16-safe.

The volume build runs in fp32 for ``reg``/``alt`` mirroring the reference's
autocast boundary (core/raft_stereo.py:92,95); ``reg_fused`` keeps the input
dtype (the point of the reference's fp16 CUDA kernel —
sampler/sampler_kernel.cu:126).
"""

from __future__ import annotations

import math
from typing import Callable, List, Tuple

import jax.lax as lax
import jax.numpy as jnp

from raft_stereo_tpu.config import RaftStereoConfig
from raft_stereo_tpu.ops.sampler import (linear_sampler_1d,
                                         linear_sampler_1d_features)

CorrFn = Callable[[jnp.ndarray], jnp.ndarray]


def over_data_axis(fn, batched):
    """parallel/data_sharded.over_data_axis, imported at call time
    (``parallel`` imports this module)."""
    from raft_stereo_tpu.parallel import data_sharded

    return data_sharded.over_data_axis(fn, batched)


# ------------------------------------------------------------ int8 pyramid
def corr_quant_enabled(cfg: RaftStereoConfig) -> bool:
    """Whether this config stores the correlation pyramid int8
    (round-15 turbo tier): the lookup is memory-bound
    (PERF.md section 3), so the int8 volume moves 1/4 (vs
    fp32) or 1/2 (vs bf16) of the bytes per iteration.  The int8_mxu
    compute mode (r22) shares the identical pyramid path — the modes
    differ in the ENCODER convs, not here."""
    return cfg.quant in ("int8", "int8_mxu") and cfg.quant_corr


def corr_q_dtype(cfg: RaftStereoConfig):
    """The quantized correlation grid this trace uses: ``float8_e4m3``
    when the config asks for it AND the backend can run it
    (``fp8_corr_available`` — TPU or kernel-interpret mode), else
    ``int8``.  A config with ``quant_corr_fp8=True`` compiles
    everywhere; the downgrade is logged once."""
    from raft_stereo_tpu.kernels.corr_lookup import (FP8_CORR_DTYPE,
                                                     fp8_corr_available,
                                                     log_path_once)

    if cfg.quant_corr_fp8:
        if fp8_corr_available():
            return FP8_CORR_DTYPE
        log_path_once("quant_corr_fp8 asked for float8_e4m3fn correlation "
                      "entries but no backend here runs them: int8 grid")
    return jnp.int8


def quantize_pyramid(pyramid: List[jnp.ndarray], cfg: RaftStereoConfig
                     ) -> Tuple[List[jnp.ndarray], List[jnp.ndarray]]:
    """Per-level symmetric quantization of the (fp) pyramid:
    ``(quantized levels, per-level fp32 scales)`` on the
    ``corr_q_dtype(cfg)`` grid.  Scales are the calibrated
    percentile-clipped constants when the config carries them
    (``quant_corr_scales``, quant/calibrate.py — int8-referenced, so
    the fp8 grid rescales them by 127/448) or per-level max-abs
    reductions computed in-graph otherwise.  Inference-only: the volume
    is detached first (the quantized tier never trains — round() has no
    useful gradient and the fused q kernels are forward-only)."""
    from raft_stereo_tpu.quant.core import (FP8_QMAX, dynamic_scale,
                                            quantize_fp8,
                                            quantize_symmetric)

    q_dtype = corr_q_dtype(cfg)
    fp8 = jnp.dtype(q_dtype) != jnp.dtype(jnp.int8)
    qmax = FP8_QMAX if fp8 else 127.0
    pyramid = [lax.stop_gradient(v) for v in pyramid]
    if cfg.quant_corr_scales is not None:
        # Calibrated scales are absmax/127 by convention (clipped_scale);
        # a wider grid reuses the same calibrated absmax.
        scales = [jnp.float32(s * (127.0 / qmax))
                  for s in cfg.quant_corr_scales]
    else:
        scales = [dynamic_scale(v, qmax=qmax) for v in pyramid]
    if fp8:
        return ([quantize_fp8(v, s, q_dtype)
                 for v, s in zip(pyramid, scales)], scales)
    return ([quantize_symmetric(v, s) for v, s in zip(pyramid, scales)],
            scales)


def _tap_scale_vector(scales: List[jnp.ndarray], radius: int
                      ) -> jnp.ndarray:
    """The per-channel dequant vector of a level-major lookup output:
    level i's scale repeated over its 2r+1 taps.  Hat sampling is linear
    in the volume, so ``scale * sample(q) == sample(scale * q)``
    exactly — the scale multiply after the kernel IS the dequant."""
    return jnp.repeat(jnp.stack([s.astype(jnp.float32) for s in scales]),
                      2 * radius + 1)


def _dequantize_levels(pyramid_q: List[jnp.ndarray],
                       scales: List[jnp.ndarray], dtype
                       ) -> List[jnp.ndarray]:
    """XLA-fallback dequant (CPU / non-Pallas backends): same int8
    grid, same scales — bit-level the same QUANTIZATION as the kernel
    path, only the sample-then-scale order differs (both linear)."""
    return [(q.astype(jnp.float32) * s).astype(dtype)
            for q, s in zip(pyramid_q, scales)]


def build_corr_volume(fmap1: jnp.ndarray, fmap2: jnp.ndarray,
                      precision=lax.Precision.HIGHEST) -> jnp.ndarray:
    """(B,H,W1,D), (B,H,W2,D) → (B,H,W1,W2) dot-product volume / sqrt(D).

    A batched (W1, D) × (D, W2) matmul per image row — the MXU-friendly
    formulation of the reference's einsum (core/corr.py:154).
    """
    d = fmap1.shape[-1]
    corr = jnp.einsum("bhwd,bhvd->bhwv", fmap1, fmap2, precision=precision)
    return corr / math.sqrt(d)


def pool_axis(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """2-wide stride-2 mean along ``axis``, floor semantics
    (reference: core/corr.py:124 ``F.avg_pool2d([1,2])``)."""
    axis = axis % x.ndim
    w2 = (x.shape[axis] // 2) * 2
    lo = x[(slice(None),) * axis + (slice(0, w2, 2),)]
    hi = x[(slice(None),) * axis + (slice(1, w2, 2),)]
    return 0.5 * (lo + hi)


pool_last_axis = pool_axis


def build_corr_pyramid(corr: jnp.ndarray, num_levels: int,
                       axis: int = -1) -> List[jnp.ndarray]:
    """Level i has W2 // 2^i disparity bins along ``axis`` (-2 for the
    transposed volume the lookup kernel reads).  The reference stores
    ``num_levels+1`` entries but only ever reads ``num_levels``
    (core/corr.py:122-125 vs :133) — we build exactly ``num_levels``."""
    pyramid = [corr]
    for _ in range(num_levels - 1):
        pyramid.append(pool_axis(pyramid[-1], axis))
    return pyramid


def _window_coords(coords: jnp.ndarray, level: int, radius: int) -> jnp.ndarray:
    """(B,H,W1) center x-positions → (B,H,W1,2r+1) tap positions at ``level``."""
    dx = jnp.arange(-radius, radius + 1, dtype=coords.dtype)
    return coords[..., None] / (2 ** level) + dx


def lookup_pyramid_xla(pyramid: List[jnp.ndarray], coords: jnp.ndarray,
                       radius: int) -> jnp.ndarray:
    """Bilinear window lookup at every level; concat level-major
    (reference: core/corr.py:127-146)."""
    outs = [linear_sampler_1d(vol, _window_coords(coords, i, radius))
            for i, vol in enumerate(pyramid)]
    return jnp.concatenate(outs, axis=-1)


# --------------------------------------------------------------------- reg
def make_corr_fn_reg(cfg: RaftStereoConfig, fmap1, fmap2) -> CorrFn:
    fmap1 = fmap1.astype(jnp.float32)
    fmap2 = fmap2.astype(jnp.float32)
    pyramid = build_corr_pyramid(build_corr_volume(fmap1, fmap2),
                                 cfg.corr_levels)
    if corr_quant_enabled(cfg):
        # The pure-XLA int8 reference: same int8 grid and scales as the
        # fused kernel path, dequantized before the XLA sampler — the
        # numerics the kernel parity tests compare against.
        pyramid_q, scales = quantize_pyramid(pyramid, cfg)
        pyramid = _dequantize_levels(pyramid_q, scales, jnp.float32)

    def corr_fn(coords):
        return lookup_pyramid_xla(pyramid, coords, cfg.corr_radius)

    return corr_fn


# --------------------------------------------------------------------- alt
def make_corr_fn_alt(cfg: RaftStereoConfig, fmap1, fmap2) -> CorrFn:
    # On TPU the whole lookup fuses into one Pallas kernel per level that
    # computes volume tiles on the MXU in VMEM (never HBM) and hat-samples
    # them — kernels/corr_alt.py.  The kernel keeps the incoming compute
    # dtype (bf16 under mixed precision, like the reference's fp16 CUDA
    # lookup; fp32 features get exact HIGHEST-precision MXU passes).  The
    # XLA path below is the correctness reference and off-TPU fallback.
    from raft_stereo_tpu.kernels.corr_alt import (alt_fused_available,
                                                  alt_fused_fits,
                                                  alt_lookup_fused)
    use_fused = (alt_fused_available()
                 and alt_fused_fits(fmap2.shape[2], fmap1.shape[-1],
                                    fmap1.dtype.itemsize, cfg.corr_radius))
    if alt_fused_available() and not use_fused:
        from raft_stereo_tpu.kernels.corr_lookup import log_path_once
        log_path_once(
            f"alt lookup W2={fmap2.shape[2]} D={fmap1.shape[-1]} "
            f"{fmap1.dtype.name}: XLA sampler (one row of the kernel's "
            f"backward tile exceeds the VMEM budget)")
    if not use_fused:
        # XLA fallback runs in fp32 like the reference's alt backend
        # (core/raft_stereo.py:95 forces fp32 for it).
        fmap1 = fmap1.astype(jnp.float32)
        fmap2 = fmap2.astype(jnp.float32)
    d = fmap1.shape[-1]
    # Progressively W-pooled right features (reference: core/corr.py:104).
    fmap2_pyramid = [fmap2]
    for _ in range(cfg.corr_levels - 1):
        fmap2_pyramid.append(pool_axis(fmap2_pyramid[-1], axis=2))

    if corr_quant_enabled(cfg):
        # The no-volume backend has no pyramid to store — its bytes are
        # the FEATURE maps re-read every iteration, so those quantize
        # instead: per-tensor symmetric int8 (dynamic in-graph scales —
        # feature ranges are not what quant_corr_scales calibrates), and
        # the combined scale s1*s2_level factors out of the bilinear dot
        # exactly.  The fused q kernel upcasts in-register; the XLA
        # fallback dequantizes then runs the reference path.
        from raft_stereo_tpu.quant.core import (FP8_QMAX, dynamic_scale,
                                                quantize_fp8,
                                                quantize_symmetric)

        q_dtype = corr_q_dtype(cfg)
        fp8 = jnp.dtype(q_dtype) != jnp.dtype(jnp.int8)
        qmax = FP8_QMAX if fp8 else 127.0

        def _q(x, s):
            return (quantize_fp8(x, s, q_dtype) if fp8
                    else quantize_symmetric(x, s))

        f1_det = lax.stop_gradient(fmap1)
        s1 = dynamic_scale(f1_det, qmax=qmax)
        f1_q = _q(f1_det, s1)
        f2_qs, s2s = [], []
        for f2 in fmap2_pyramid:
            f2_det = lax.stop_gradient(f2)
            s2 = dynamic_scale(f2_det, qmax=qmax)
            f2_qs.append(_q(f2_det, s2))
            s2s.append(s2)
        if use_fused:
            from raft_stereo_tpu.kernels.corr_alt import alt_lookup_fused_q

            compute_dtype = fmap1.dtype
            scale_vec = _tap_scale_vector(
                [s1 * s2 for s2 in s2s], cfg.corr_radius)

            def corr_fn(coords):
                raw = over_data_axis(
                    lambda f1, f2s, c: alt_lookup_fused_q(
                        f1, f2s, c, cfg.corr_radius, out_dtype=jnp.float32,
                        q_dtype=q_dtype),
                    (f1_q, f2_qs, coords))
                return (raw * scale_vec).astype(compute_dtype)
            return corr_fn
        fmap1 = (f1_q.astype(jnp.float32) * s1)
        fmap2_pyramid = [(q.astype(jnp.float32) * s)
                         for q, s in zip(f2_qs, s2s)]
    elif use_fused:
        def corr_fn(coords):
            return over_data_axis(
                lambda f1, f2s, c: alt_lookup_fused(f1, f2s, c,
                                                    cfg.corr_radius),
                (fmap1, fmap2_pyramid, coords))
        return corr_fn

    def corr_fn(coords):
        outs = []
        for i, f2 in enumerate(fmap2_pyramid):
            taps = _window_coords(coords, i, cfg.corr_radius)  # (B,H,W1,K)
            sampled = linear_sampler_1d_features(f2, taps)     # (B,H,W1,K,D)
            outs.append(jnp.einsum("bhwd,bhwkd->bhwk", fmap1, sampled,
                                   precision=lax.Precision.HIGHEST)
                        / math.sqrt(d))
        return jnp.concatenate(outs, axis=-1)

    return corr_fn


# --------------------------------------------------------------- reg_fused
def make_corr_fn_reg_fused(cfg: RaftStereoConfig, fmap1, fmap2) -> CorrFn:
    """Pallas-fused pyramid lookup (≙ reference sampler/ CUDA extension).

    Where the kernel runs, the pyramid is built TRANSPOSED, level i
    (B,H,W2_i,W1): the kernel samples along the sublanes with the pixels
    on the lanes (kernels/corr_lookup.py), so the volume is the product
    ``fmap2 · fmap1ᵀ`` — the same products at the same precision, the
    result's layout free on the MXU — and the levels pool along axis -2.
    The XLA fallback (Pallas unavailable, e.g. CPU tests) keeps
    (B,H,W1,W2_i) and ``lookup_pyramid_xla``, as ``reg`` does.
    Keeps the compute dtype of the inputs (bf16-safe).  With
    ``cfg.quant == "int8"`` the pyramid is stored int8 with per-level
    scales and the kernels dequantize in-register
    (kernels/corr_lookup.lookup_pyramid_fused_q); the XLA fallback
    dequantizes the same int8 grid before sampling, so the tier's
    numerics are backend-independent up to float associativity."""
    from raft_stereo_tpu.kernels.corr_lookup import (
        fused_lookup_available, lookup_pyramid_fused,
        lookup_pyramid_fused_q)

    compute_dtype = fmap1.dtype
    fused = fused_lookup_available()
    fmap1 = fmap1.astype(jnp.float32)
    fmap2 = fmap2.astype(jnp.float32)
    # the volume of (fmap2, fmap1) IS the transposed volume
    volume = (build_corr_volume(fmap2, fmap1) if fused
              else build_corr_volume(fmap1, fmap2))
    if corr_quant_enabled(cfg):
        # int8 from the fp32 volume (not the bf16 round-trip): one
        # rounding step instead of two.
        pyramid_q, scales = quantize_pyramid(
            build_corr_pyramid(volume, cfg.corr_levels,
                               axis=-2 if fused else -1), cfg)
        if fused:
            scale_vec = _tap_scale_vector(scales, cfg.corr_radius)

            def corr_fn(coords):
                raw = over_data_axis(
                    lambda pyr, c: lookup_pyramid_fused_q(
                        pyr, c, cfg.corr_radius, out_dtype=jnp.float32,
                        q_dtype=corr_q_dtype(cfg)),
                    (pyramid_q, coords))
                return (raw * scale_vec).astype(compute_dtype)
        else:
            pyramid = _dequantize_levels(pyramid_q, scales, compute_dtype)

            def corr_fn(coords):
                return lookup_pyramid_xla(pyramid, coords, cfg.corr_radius)
        return corr_fn

    pyramid = build_corr_pyramid(volume.astype(compute_dtype),
                                 cfg.corr_levels, axis=-2 if fused else -1)
    if fused:
        def corr_fn(coords):
            return over_data_axis(
                lambda pyr, c: lookup_pyramid_fused(pyr, c,
                                                    cfg.corr_radius),
                (pyramid, coords))
    else:
        def corr_fn(coords):
            return lookup_pyramid_xla(pyramid, coords, cfg.corr_radius)

    return corr_fn


_BACKENDS = {
    "reg": make_corr_fn_reg,
    "alt": make_corr_fn_alt,
    "reg_fused": make_corr_fn_reg_fused,
}


def make_corr_fn(cfg: RaftStereoConfig, fmap1: jnp.ndarray,
                 fmap2: jnp.ndarray) -> CorrFn:
    """Dispatch on ``cfg.corr_backend`` (≙ core/raft_stereo.py:90-100).

    ``corr_w2_shards > 1`` routes to the disparity-axis-sharded volume
    (parallel/corr_sharded.py): ``reg_fused`` samples each shard with the
    Pallas kernel (full-manual shard_map, shard-shifted centers) and also
    stores shard volumes in the compute dtype; ``reg`` keeps the XLA
    sampler as the pure-XLA correctness reference.  ``alt`` builds no
    volume and is rejected at config validation.  Activate a mesh with
    ``corr_sharding(mesh)`` during tracing first."""
    if cfg.corr_fp32:
        # Reference-exact correlation numerics under mixed precision
        # (core/raft_stereo.py:92,95 force fp32 for reg/alt): upcast before
        # backend construction so even the dtype-preserving fused kernels
        # run fp32.
        fmap1 = fmap1.astype(jnp.float32)
        fmap2 = fmap2.astype(jnp.float32)
    if cfg.corr_w2_shards > 1:
        from raft_stereo_tpu.parallel.corr_sharded import (
            active_corr_mesh, make_corr_fn_w2_sharded)
        mesh = active_corr_mesh()
        if mesh is None:
            raise RuntimeError(
                f"corr_w2_shards={cfg.corr_w2_shards} needs an active mesh: "
                "trace the model under parallel.corr_sharded.corr_sharding(mesh)")
        return make_corr_fn_w2_sharded(cfg, fmap1, fmap2, mesh)
    return _BACKENDS[cfg.corr_backend](cfg, fmap1, fmap2)
