"""Pallas TPU kernel: fused correlation-pyramid window lookup.

TPU-native replacement for the reference's CUDA extension (reference:
sampler/sampler.cpp + sampler/sampler_kernel.cu): sample a (2r+1)-tap window
of the 1-D correlation volume at fractional disparity positions, with linear
interpolation and zero padding, in the volume's own dtype (bf16-safe — the
whole point of the reference's fp16 CUDA path, sampler_kernel.cu:126).

Design: gathers are hostile to the TPU vector unit, so the kernel never
gathers.  For tap k the interpolation weight of volume bin x at center c is
the hat function  max(0, 1 - |x - (c + k - r)|)  — nonzero for at most the
two bins the reference's CUDA kernel reads (sampler_kernel.cu:46-59), and
the 2r+1 taps of one pixel sit one bin apart, so together they read the
2r+2 consecutive bins from floor(c) - r on.

Layout (PR 33): the kernel reads the volume TRANSPOSED, level i as
(rows, W2_i, W1) — the right image's bins on the sublanes (padded to whole
registers of 8, not to 128 lanes), the pixels on the lanes;
``models/corr.make_corr_fn_reg_fused`` builds it so, the same products on
the MXU.  Each (row block × 128 pixels) tile, a row and a level at a time,
picks those 2r+2 bins out of the (W2_i, 128) tile by comparing a sublane
iota with each bin's index (``sublane_sample``, shared with the no-volume
kernel, kernels/corr_alt.py): per volume register and bin one compare, one
select and one add, whole registers, nothing crosses lanes; tap k is then
(1-a)·bin[k] + a·bin[k+1].  The taps are stored as dense rows of a
(levels·K, 128) block; the kernel's result is (rows, levels·K, W1) and XLA
takes the ``swapaxes``.  O(W2) work a pixel instead of a 2-bin gather, which
wins on TPU because it vectorizes and the tile is already in VMEM; all
levels of the pyramid go in ONE launch where their blocks fit the VMEM
budget (``_program_bytes``; every shape a benchmark cell runs), else a
launch a level with its row block shrunk to fit.  Until PR 33 the tile had
the bins on the lanes and every tap cost a product over the tile, a
reduction across lanes and a one-lane store: 0.955 ms a KITTI float32
pair-lookup in four launches on the v5e against 0.147 in one, 1.69 ms
against 0.22 at the SceneFlow training shape (PERF.md section 6, PR 33).

Backward mirrors the reference's hand-written scatter kernel
(sampler_kernel.cu:64-105) but needs no atomics: window bin m of a pixel
receives (1-a)·g[m] + a·g[m-1], placed by the same sublane compare
(``sublane_scatter``), and the kernel writes the DENSE cotangent of every
level, (rows, W2_i, W1), which XLA sums over the iterations.  Like the
reference's ``CorrSampler.backward`` (core/corr.py:24-29), no coordinate
gradient is produced — RAFT-Stereo detaches coords before every lookup
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


def row_blk_for(per_row_bytes: int, fixed_bytes: int = 0) -> int:
    """Largest power-of-two row block (≤ ROW_BLK) whose per-program working
    set fits ``VMEM_BUDGET``; callers pass bytes-per-row-of-ROW_BLK=1 and
    what a program holds whatever its rows."""
    rb = ROW_BLK
    while rb > 1 and rb * per_row_bytes + fixed_bytes > VMEM_BUDGET:
        rb //= 2
    return rb


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
SUBLANES = 8      # rows of one float32 vector register


def w2_rows(w2: int, itemsize: int) -> int:
    """Sublane rows of a block that holds ``w2`` bins: ``w2`` rounded up to
    whole sublane tiles of the dtype (8 rows of float32, 16 of bfloat16, 32
    of a one-byte grid), so a transposed tile is whole registers."""
    tile = SUBLANES * max(1, 4 // itemsize)
    return -(-w2 // tile) * tile


def _window(centers, radius: int, w2: int, lanes: int):
    """What the sampler and its transpose share: per pixel (lane) the
    fraction ``a = c - floor(c)`` and, as (2·radius+2, 8, lanes) int32, how
    far register 0's sublanes sit from window bin m = ``floor(c) - radius
    + m`` of the pixel on their lane (0 marks the sublane that IS the bin;
    register x0/8's sublanes are ``x0`` further).  The bins ride a leading
    axis, so one traced operation is that many whole registers and the
    trace stays short (it is paid at every start-up)."""
    bins = 2 * radius + 2
    # beyond these every tap reads bins outside the row: clamping keeps
    # the integer conversion in range and changes no result
    c = jnp.clip(centers, -(radius + 2.0), w2 + radius + 1.0)
    first = jnp.floor(c)
    a = c - first
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, lanes), 0)
    off = (sub - (first.astype(jnp.int32) - radius))[None] - \
        jax.lax.broadcasted_iota(jnp.int32, (bins, SUBLANES, lanes), 0)
    return a, sub, off


def sublane_sample(vt, centers, radius: int, w2: int):
    """Σ_x vt[x, w] · hat_k(x - centers[w]) for each tap k, for a volume
    tile laid out TRANSPOSED: (W2p, W1B) float32 with the right image's
    bins on the sublanes (W2p whole vector registers of 8; bins at and
    beyond ``w2`` count as zero whatever they hold) and the tile's pixels
    on the lanes, plus its (1, W1B) centers → 2·radius+1 rows of (1, W1B).

    Same numbers as ``hat_sample`` on the untransposed tile
    (tests/test_corr_alt.py holds the two together, borders included), by
    another route: the taps of one pixel sit one bin apart, so all of them
    read the same 2·radius+2 consecutive bins ``floor(c) - radius + m``
    and tap k is ``(1-a)·bin[k] + a·bin[k+1]`` with ``a = c - floor(c)``,
    which is what the hat weights come to.  Each bin is picked out of the
    tile by comparing a sublane iota with its index: per volume register
    and bin one compare, one select and one add into that bin's
    accumulator, whole registers on the vector unit; the eight sublanes of
    an accumulator are reduced once at the end.  Nothing crosses lanes."""
    w2p, lanes = vt.shape
    a, sub, off = _window(centers, radius, w2, lanes)
    acc = None
    for x0 in range(0, w2p, SUBLANES):
        v = vt[x0:x0 + SUBLANES]
        if x0 + SUBLANES > w2:
            v = jnp.where(sub < w2 - x0, v, 0.0)
        hit = jnp.where(off == -x0, v[None], 0.0)
        acc = hit if acc is None else acc + hit
    g = jnp.sum(acc, axis=1, keepdims=True)            # (bins, 1, W1B)
    taps = (1.0 - a) * g[:-1] + a * g[1:]
    return [taps[k] for k in range(2 * radius + 1)]


def sublane_scatter(g, centers, radius: int, w2: int, w2p: int):
    """Transpose of :func:`sublane_sample`: the taps' cotangent, 2·radius+1
    rows of (1, W1B) float32, and the (1, W1B) centers → the (W2p, W1B)
    cotangent of the transposed tile.  Window bin m of a pixel receives
    ``(1-a)·g[m] + a·g[m-1]`` (what the hat weights of the two taps that
    touch it come to) and every other bin nothing: per register of the
    result and bin one compare and one select of that value, broadcast
    down the sublanes, and one add.  Rows at and beyond ``w2`` hold what
    the window puts there; the launch's result has no such rows."""
    lanes = centers.shape[-1]
    a, _, off = _window(centers, radius, w2, lanes)
    zero = jnp.zeros_like(centers)
    val = jnp.stack([(1.0 - a) * hi + a * lo
                     for hi, lo in zip(list(g) + [zero], [zero] + list(g))])
    val = jnp.broadcast_to(val, off.shape)             # (bins, 8, W1B)
    return jnp.concatenate(
        [jnp.sum(jnp.where(off == -x0, val, 0.0), axis=0)
         for x0 in range(0, w2p, SUBLANES)], axis=0)


def _hat_field(centers, w2: int, radius: int):
    """Per-tap weights of the UNTRANSPOSED tile: tap k's weight at bin x
    is ``max(0, 1-|x - centers - (k-radius)|)`` = F[x + 2·radius - k]
    where F[j] = max(0, 1-|j - radius - centers|) over j ∈ [0, w2+2·radius),
    computed once and sliced per tap.  With the bins on the lanes every
    tap then costs a product over the whole tile and a reduction ACROSS
    lanes: on the v5e that was 27.7 of 39.5 ms of the no-volume launch
    (PR 29) and 0.955 ms a KITTI pair-lookup here against 0.147 (PR 33), which is why no
    forward kernel uses it any more.  It stays as the plain expression the
    tests hold ``sublane_sample`` to, and under ``hat_scatter``."""
    ext = w2 + 2 * radius
    xs = jax.lax.broadcasted_iota(jnp.int32, (1, 1, ext), 2).astype(jnp.float32)
    return jnp.maximum(0.0, 1.0 - jnp.abs(xs - radius - centers[..., None]))


def hat_sample(v, centers, radius: int):
    """Σ_x v[..., x] · hat_k(x) for each tap k: (R, W1B, W2) tile +
    (R, W1B) centers → per-tap sampler yielding (R, W1B) slices.  The
    plain expression of the lookup (no kernel runs it)."""
    w2 = v.shape[-1]
    f = _hat_field(centers, w2, radius)
    for k in range(2 * radius + 1):
        off = 2 * radius - k
        yield k, jnp.sum(v * f[:, :, off:off + w2], axis=-1)


def hat_scatter(g, centers, w2: int, radius: int):
    """Transpose of :func:`hat_sample`: (R, W1B, K) cotangent + centers
    → (R, W1B, W2) volume cotangent (the no-volume kernel's backward, which
    contracts it with the features: kernels/corr_alt.py)."""
    f = _hat_field(centers, w2, radius)
    acc = jnp.zeros(centers.shape + (w2,), jnp.float32)
    for k in range(2 * radius + 1):
        off = 2 * radius - k
        acc = acc + g[:, :, k][..., None] * f[:, :, off:off + w2]
    return acc


# ------------------------------------------------------------------ kernels
# A row's body is jitted, so that a kernel's unrolled rows share ONE trace
# of it a level shape (the Mosaic lowering inlines the calls): the trace is
# paid at every start-up, whatever the compile cache holds (PR 29).
@functools.partial(jax.jit, static_argnames=("radius", "w2"))
def _row_taps(vt, centers, *, radius: int, w2: int):
    return sublane_sample(vt.astype(jnp.float32), centers, radius, w2)


@functools.partial(jax.jit, static_argnames=("radius", "w2", "w2p"))
def _row_scatter(g, centers, *, radius: int, w2: int, w2p: int):
    return sublane_scatter(g, centers, radius, w2, w2p)


def _fwd_kernel(*refs, radius: int, widths, scales):
    """Per level a (R, W2p, W1B) block of the TRANSPOSED volume (bins on
    the sublanes, pixels on the lanes) + (R, 1, W1B) centers → window
    samples (R, levels·K, W1B), taps on the sublanes.  One level a launch,
    or every level of the pyramid."""
    *vol_refs, coords_ref, out_ref = refs
    for r in range(out_ref.shape[0]):
        centers0 = coords_ref[r].astype(jnp.float32)
        taps = []
        for vol_ref, w2, scale in zip(vol_refs, widths, scales):
            taps += _row_taps(vol_ref[r], centers0 * scale, radius=radius,
                              w2=w2)
        out_ref[r] = jnp.concatenate(taps, axis=0).astype(out_ref.dtype)


def _bwd_kernel(coords_ref, g_ref, *dvol_refs, radius: int, widths, scales):
    """Tile transpose of the forward: g (R, levels·K, W1B) → per level the
    DENSE cotangent block (R, W2p, W1B) of the transposed volume."""
    k = 2 * radius + 1
    for r in range(g_ref.shape[0]):
        centers0 = coords_ref[r].astype(jnp.float32)
        g = g_ref[r].astype(jnp.float32)
        for i, (dvol_ref, w2, scale) in enumerate(
                zip(dvol_refs, widths, scales)):
            dvol_ref[r] = _row_scatter(
                [g[i * k + t:i * k + t + 1] for t in range(k)],
                centers0 * scale, radius=radius, w2=w2,
                w2p=dvol_ref.shape[1]).astype(dvol_ref.dtype)


# ------------------------------------------------------------------- launch
def _program_bytes(w2s, radius: int, itemsize: int, tap_itemsize: int,
                   backward: bool):
    """(bytes a row of the blocks, bytes whatever the rows) of one program
    over the levels ``w2s``, as HALF of what Mosaic allocates for it:
    ``VMEM_BUDGET`` is half of the scoped VMEM, the pipeline double-buffers
    the blocks.  A row holds the levels' volume blocks (forward: read;
    backward: the dense cotangent, written), the centres' and the taps'
    block (``tap_itemsize``: the result's dtype forward, the cotangent's
    backward).  One row of one level is in flight at a time: forward the
    float32 upcast of its tile where the volume is not float32, backward
    the scatter's float32 tile and the copy its cast makes; besides the
    taps in float32 and the window's offsets and accumulators, 2·radius+2
    registers each.  No hat field and no product (PR 33).  Calibrated
    against the v5e compiler by bisection of ``vmem_limit_bytes``
    (needed / estimated MiB; tests/test_v5e_compile.py holds three of them):
    forward fp32 W2 312/156/78/39 4.96-5.21 / 5.10, bf16 2.48-2.73 / 2.88,
    int8 1.49-1.74 / 1.88, fp32 180/90/45/22 2.98-3.23 / 3.22, bf16
    1.49-1.74 / 1.88, fp32 720/360/180/90 10.92-11.16 / 11.10; backward
    fp32 312/... 5.21-5.46 / 5.40, bf16 2.73-2.98 / 3.04, fp32 180/...
    2.98-3.23 / 3.40, bf16 1.49-1.74 / 1.97."""
    fp32 = 4
    taps = len(w2s) * (2 * radius + 1)
    tile = max(w2_rows(w2, itemsize) for w2 in w2s)
    per_row = W1_BLK * (sum(w2_rows(w2, itemsize) for w2 in w2s) * itemsize
                        + SUBLANES * fp32
                        + w2_rows(taps, tap_itemsize) * tap_itemsize)
    in_flight = (2 * tile if backward
                 else 0 if itemsize == fp32 else tile)
    temp = W1_BLK * fp32 * (in_flight + w2_rows(taps, fp32)
                            + 2 * (2 * radius + 2) * SUBLANES)
    return per_row, temp // 2


def _vol_spec(rb: int, w2: int, itemsize: int):
    """A level's block: whole sublane tiles of bins, so it reads (writes)
    past a width that is no whole number of them; the sampler masks those
    rows and the result has none."""
    return pl.BlockSpec((rb, w2_rows(w2, itemsize), W1_BLK),
                        lambda i, j: (i, 0, j), memory_space=pltpu.VMEM)


def _launch_fwd(vols, coords, radius: int, scales, rb: int, out_dtype=None):
    """Per level (rows, W2_i, W1) + (rows, W1) centers → (rows, W1,
    levels·K) in ONE launch with row blocks of ``rb``.  The kernel's own
    result has the taps on the sublanes and the pixels on the lanes,
    (rows, levels·K, W1), and XLA takes the ``swapaxes``.

    ``out_dtype`` (default: the volume's own dtype) exists for the
    quantized pyramid: a one-byte volume samples to fp values (the
    in-kernel float32 upcast IS the in-register dequant modulo the
    per-level scale the caller applies), so the output must not round
    through the grid."""
    rows, w1 = coords.shape
    widths = tuple(int(v.shape[1]) for v in vols)
    k = (2 * radius + 1) * len(vols)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, radius=radius, widths=widths,
                          scales=tuple(scales)),
        grid=(pl.cdiv(rows, rb), pl.cdiv(w1, W1_BLK)),
        in_specs=[_vol_spec(rb, w2, v.dtype.itemsize)
                  for v, w2 in zip(vols, widths)]
                 + [pl.BlockSpec((rb, 1, W1_BLK), lambda i, j: (i, 0, j),
                                 memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((rb, k, W1_BLK), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, k, w1),
                                       out_dtype or vols[0].dtype),
        interpret=_interpret(),
    )(*vols, coords[:, None, :])
    return jnp.swapaxes(out, 1, 2)


def _launch_bwd(coords, g, w2s, radius: int, scales, rb: int, dtype):
    """(rows, W1) centers + (rows, W1, levels·K) cotangent → per level the
    (rows, W2_i, W1) cotangent of the transposed volume, ONE launch."""
    rows, w1 = coords.shape
    k = (2 * radius + 1) * len(w2s)
    itemsize = jnp.dtype(dtype).itemsize
    return pl.pallas_call(
        functools.partial(_bwd_kernel, radius=radius, widths=tuple(w2s),
                          scales=tuple(scales)),
        grid=(pl.cdiv(rows, rb), pl.cdiv(w1, W1_BLK)),
        in_specs=[
            pl.BlockSpec((rb, 1, W1_BLK), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, k, W1_BLK), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[_vol_spec(rb, w2, itemsize) for w2 in w2s],
        out_shape=[jax.ShapeDtypeStruct((rows, w2, w1), dtype)
                   for w2 in w2s],
        interpret=_interpret(),
    )(coords[:, None, :], jnp.swapaxes(g, 1, 2))


def _level_scales(levels: int):
    return tuple(1.0 / 2 ** i for i in range(levels))


def _single_launch(w2s, radius: int, itemsize: int, tap_itemsize: int,
                   backward: bool = False) -> bool:
    """Whether every level's blocks fit one program at ``ROW_BLK`` rows."""
    per_row, fixed = _program_bytes(w2s, radius, itemsize, tap_itemsize,
                                    backward)
    return ROW_BLK * per_row + fixed <= VMEM_BUDGET


def _sample(vols, coords, radius: int, what: str, out_dtype=None):
    """The forward launches of one lookup over (B,H,W2_i,W1) levels and
    (B,H,W1) centers, by the plan their widths and dtype give (said as
    ``what``): every level in one launch at ``ROW_BLK`` rows a block, or
    one launch a level with its row block shrunk to the VMEM budget."""
    b, h, w1 = coords.shape
    vols2 = [v.reshape(b * h, v.shape[2], w1) for v in vols]
    coords2 = coords.reshape(b * h, w1)
    w2s = [v.shape[1] for v in vols2]
    itemsize = vols[0].dtype.itemsize
    tap_itemsize = jnp.dtype(out_dtype or vols[0].dtype).itemsize
    scales = _level_scales(len(vols))
    single = _single_launch(w2s, radius, itemsize, tap_itemsize)
    log_launch_choice(what, w2s, vols[0].dtype, single)
    if single:
        out = _launch_fwd(vols2, coords2, radius, scales, ROW_BLK, out_dtype)
    else:
        out = jnp.concatenate(
            [_launch_fwd([v], coords2, radius, (s,), row_blk_for(
                *_program_bytes([w2], radius, itemsize, tap_itemsize,
                                False)), out_dtype)
             for v, w2, s in zip(vols2, w2s, scales)], axis=-1)
    return out.reshape(b, h, w1, -1)


# ---------------------------------------------------------- pyramid sampling
# Every level in one launch where the working set allows (and all level
# cotangents in one backward launch): each custom call inside the scanned
# loop carries in-graph overhead far above its isolated runtime, and the
# served float32 KITTI program ran four an iteration only because the old
# body's hat field and product did not fit together (PR 33).  The levels
# stay separate pallas_call operands — no concatenated-volume copy.
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sample_pyramid(vols, coords, radius: int):
    """Tuple of (B,H,W2_i,W1) TRANSPOSED volumes + (B,H,W1) centers →
    (B,H,W1,levels·(2r+1)) window samples, concat level-major."""
    return _sample(vols, coords, radius, "transposed-volume lookup")


def _sample_pyramid_fwd(vols, coords, radius):
    # volumes ride along for static shape/dtype only; their values are
    # unused in the backward, so XLA dead-code-eliminates the residual
    return _sample_pyramid(vols, coords, radius), (vols, coords)


def _sample_pyramid_bwd(radius, residuals, g):
    vols, coords = residuals
    b, h, w1 = coords.shape
    w2s = [v.shape[2] for v in vols]
    dtype = vols[0].dtype
    coords2 = coords.reshape(b * h, w1)
    g2 = g.reshape(b * h, w1, -1)
    scales = _level_scales(len(vols))
    # The backward WRITES a tile per level, so it has its own estimate and
    # falls to one launch a level — whose row block shrinks to fit — on
    # its own; the forward keeps its plan either way.
    single = _single_launch(w2s, radius, dtype.itemsize, dtype.itemsize,
                            backward=True)
    log_launch_choice("transposed-volume lookup backward", w2s, dtype, single)
    if single:
        dvols = _launch_bwd(coords2, g2, w2s, radius, scales, ROW_BLK, dtype)
    else:
        k = 2 * radius + 1
        dvols = [_launch_bwd(coords2, g2[:, :, i * k:(i + 1) * k], [w2],
                             radius, (s,), row_blk_for(*_program_bytes(
                                 [w2], radius, dtype.itemsize,
                                 dtype.itemsize, True)), dtype)[0]
                 for i, (w2, s) in enumerate(zip(w2s, scales))]
    # No coords grad: RAFT detaches coords before every lookup, and the
    # reference kernel's backward also only produces volume gradients.
    return (tuple(d.reshape(b, h, -1, w1) for d in dvols),
            jnp.zeros_like(coords))


_sample_pyramid.defvjp(_sample_pyramid_fwd, _sample_pyramid_bwd)


def lookup_pyramid_fused(pyramid: List[jnp.ndarray], coords: jnp.ndarray,
                         radius: int) -> jnp.ndarray:
    """Fused window lookup at every level of a TRANSPOSED pyramid (level i
    is (B,H,W2_i,W1): ``models/corr.make_corr_fn_reg_fused`` builds it so),
    concat level-major — the same numbers as ``lookup_pyramid_xla``
    (models/corr.py) on the (B,H,W1,W2_i) levels.

    One launch for all levels when their blocks fit the per-program VMEM
    budget together; otherwise one launch per level, each with its row
    block shrunk to fit (volumes grow linearly in W2 and must not turn a
    working eval into a Mosaic VMEM compile failure)."""
    return _sample_pyramid(tuple(pyramid), coords, radius)


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
    return _sample(pyramid, coords, radius,
                   "quantized transposed-volume lookup", out_dtype)
