"""Pallas TPU kernel: fused ConvGRU gate pipeline.

The GRU refinement loop is RAFT-Stereo's runtime: it is most of the
device's busy time in every cell of the benchmark (PERF.md section 5:
52-72 %), and its hot block is the ConvGRU gate math in
models/update.py — per level per iteration, XLA dispatches the ``convzr``
conv, the ``convq`` conv, and a trail of pointwise ops (~10 ops/level), each
round-tripping activations through HBM.  This kernel computes BOTH gate
convolutions and the r-gate coupling between them in ONE row-blocked launch,
keeping every intermediate (the ``[h, x]`` concat rows, the pre-activation
``zr``, the recurrence-gated ``[r*h, x]``) in VMEM:

    zr   = conv3x3([h, x], Wzr) + bzr          # MXU, 9 shifted matmuls
    r    = sigmoid(zr[..., Ch:] + cr)          # VPU, fp32
    qpre = conv3x3([r*h, x], Wq) + bq          # MXU

The kernel intentionally stops at the pre-activation outputs ``(zr, qpre)``
— exactly the two tensors models/update.py tags with
``checkpoint_name("gru_gates")``.  The remaining tail
(``sigmoid``/``tanh``/blend) is pure elementwise work that XLA fuses into a
single kernel, and keeping it OUTSIDE the Pallas call is what makes the op
compose with the training remat policy (config.remat_save): with
``"gru_gates"`` saved, the backward's recompute of the scan body rebuilds
``h_out`` from the SAVED gates through the pointwise tail only — the fused
kernel is never re-run (the same shortcut the Flax path gets from its named
conv outputs).

Row blocking / halo scheme: output blocks are ``rb`` image rows; the gate
pipeline needs a 2-row/2-col receptive field (1 for each conv).  Inputs are
zero-padded OUTSIDE the kernel (2 rows/cols for ``[h, x]``, 1 for ``cr`` —
zero padding is exactly the convs' SAME-padding semantics, and ``r*h`` is
automatically 0 wherever ``h`` is padding) and each program reads TWO
row-block views of the same padded array — block ``i`` and block ``i+1`` —
assembling the ``rb+4`` halo rows from block ``i`` plus the first 4 rows of
block ``i+1``.  Block-granular index maps stay legal, no overlapping
BlockSpecs needed; the row pad is extended to ``(nb+1)*rb`` rows so view
``i+1`` never reads out of bounds.  This caps the row block at
``rb >= _MIN_ROW_BLK = 4``.

Backward is a custom VJP over a pure-JAX reference of the same math
(``lax.conv_general_dilated``, the ops the Flax path lowers to): residuals
are the op's INPUTS only, so under ``remat_gru`` the backward never re-runs
the Pallas kernel, and gradients agree with the Flax path to dtype
tolerance (tests/test_gru_fused.py).

Kernel-family contract (shared with corr_lookup.py / corr_alt.py):
``gru_fused_available()`` capability gate, a VMEM working-set fit check that
picks the row block (``gru_fused_row_block``; ``None`` = does not fit, fall
back), the package-wide interpret override so the tier-1 CPU suite runs the
same kernel code path, and a transparent fallback to the Flax conv path —
wired through ``config.fused_gru`` ("auto"|"on"|"off") in models/update.py.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_stereo_tpu.kernels.corr_alt import _precision_for
from raft_stereo_tpu.kernels.corr_lookup import (VMEM_BUDGET,
                                                 fused_lookup_available,
                                                 interpret_enabled,
                                                 log_path_once)

ROW_BLK = 8      # default image rows per program
# The two-view halo assembly reads the first 4 rows of the NEXT row block,
# so blocks can never shrink below 4 rows; shapes whose working set still
# exceeds VMEM_BUDGET at rb=4 fall back to the Flax path instead of hitting
# a Mosaic VMEM compile failure (the package-wide rule, corr_lookup.py).
_MIN_ROW_BLK = 4


def gru_fused_available() -> bool:
    """Capability gate: TPU backend, or the package interpret override
    (tier-1 CPU tests run the kernel through the HLO interpreter)."""
    return fused_lookup_available()


# ------------------------------------------------------------ VMEM fit check
def _gates_fixed_bytes(cin: int, ch: int, itemsize: int) -> int:
    """Grid-invariant VMEM residents: both weight tensors + biases."""
    fp32 = 4
    return 9 * cin * 3 * ch * itemsize + 3 * ch * fp32


def _gates_block_bytes(rb: int, w: int, cin: int, ch: int,
                       itemsize: int) -> int:
    """Working set of one program at row block ``rb``, HALO ROWS INCLUDED:
    the two ``rb``-row views of ``[h, x]`` and ``cr``, the ``rb+4`` halo
    rows assembled from them, the fp32 ``zr`` accumulator plus one live
    tap product on ``rb+2`` rows, the fp32 r / r*h intermediates and the
    ``[r*h, x]`` tile on the same ring, the fp32 ``qpre`` accumulator +
    tap product, and both output blocks.  At the minimum block the halo
    doubles the rows a program holds; an estimate that scaled a per-row
    figure by ``rb`` alone ran 1.5x under what Mosaic allocates at W=180
    (7.9 MiB estimated, 11.57 MiB reported) and chose launches the v5e's
    compiler then refused inside the batch-4 training step.  Against the
    v5e compiler this one reads 10.27 / 11.57 MiB (W=180, bf16, rb 4),
    9.25 / 10.01 (W=156), 9.42 / 7.42 (W=90, rb 8), 7.61 / 6.02 (W=78,
    fp32, rb 4)."""
    fp32 = 4
    return (2 * rb * (w + 4) * cin * itemsize        # hx views i, i+1
            + 2 * rb * (w + 2) * ch * itemsize       # cr views i, i+1
            + (rb + 4) * (w + 4) * cin * itemsize    # assembled halo rows
            + 2 * (rb + 2) * (w + 2) * 2 * ch * fp32  # zr_ext acc + product
            + 2 * (rb + 2) * (w + 2) * ch * fp32     # r, r*h (fp32)
            + (rb + 2) * (w + 2) * cin * itemsize    # [r*h, x] tile
            + 2 * rb * w * ch * fp32                 # qpre acc + product
            + rb * w * 3 * ch * itemsize)            # zr + qpre out blocks


def gru_fused_row_block(w: int, cin: int, ch: int,
                        itemsize: int) -> Optional[int]:
    """Largest power-of-two row block (<= ROW_BLK, >= 4) whose working set
    fits ``VMEM_BUDGET``; ``None`` when even rb=4 does not fit (wide
    levels — there is no W-blocking) and the caller must fall back."""
    fixed = _gates_fixed_bytes(cin, ch, itemsize)
    rb = ROW_BLK
    while rb >= _MIN_ROW_BLK:
        if fixed + _gates_block_bytes(rb, w, cin, ch, itemsize) \
                <= VMEM_BUDGET:
            return rb
        rb //= 2
    return None


def gru_fused_should_use(mode: str, *, kernel_size: int, w: int, cin: int,
                         ch: int, itemsize: int) -> bool:
    """Dispatch decision for one GRU level at trace time.

    ``auto``: use the kernel iff the backend supports it AND the level's
    working set fits VMEM — the Flax path otherwise (no workload breaks),
    with the choice and its reason logged once per level shape.
    ``on``: force the kernel; raise with the specific reason when it cannot
    run (explicit user intent should not silently degrade).
    ``off``: never (bitwise-preserves the Flax graph)."""
    if mode == "off":
        return False
    if mode not in ("auto", "on"):
        raise ValueError(f"fused_gru={mode!r} not in ('auto', 'on', 'off')")
    available = gru_fused_available() and kernel_size == 3
    rb = (gru_fused_row_block(w, cin, ch, itemsize) if available else None)
    if mode == "on":
        if not available:
            raise RuntimeError(
                "fused_gru='on' but the fused ConvGRU kernel is unavailable "
                f"(backend={jax.default_backend()!r}, "
                f"kernel_size={kernel_size}); use 'auto' for transparent "
                "fallback")
        if rb is None:
            raise RuntimeError(
                f"fused_gru='on' but the level working set (W={w}, Cin={cin},"
                f" Ch={ch}) exceeds the VMEM budget even at the minimum row "
                "block; use 'auto' for transparent fallback")
        return True
    use = available and rb is not None
    shape = (f"ConvGRU level W={w} Cin={cin} Ch={ch} "
             f"{8 * itemsize}-bit")
    if use:
        log_path_once(f"{shape}: kernel (row block {rb})")
    elif not available:
        log_path_once(f"{shape}: flax (no Pallas backend here, or "
                      f"kernel_size {kernel_size} != 3)")
    else:
        log_path_once(f"{shape}: flax (the working set exceeds the VMEM "
                      f"budget even at the minimum row block "
                      f"{_MIN_ROW_BLK}; the kernel does not block along W)")
    return use


# ------------------------------------------------------------------- kernel
def _gates_kernel(hxa_ref, hxb_ref, cra_ref, crb_ref, wzr_ref, bzr_ref,
                  wq_ref, bq_ref, zr_ref, qpre_ref, *, ch: int, precision):
    """One (image, row-block) program.

    Refs (blocks):
      hxa/hxb: (1, rb, W+4, Cin) — row blocks i / i+1 of the 2-padded [h, x]
      cra/crb: (1, rb, W+2, Ch)  — row blocks i / i+1 of the 1-padded cr
      wzr/wq:  (3, 3, Cin, Cout) gate conv weights (compute dtype)
      bzr/bq:  (1, Cout) fp32 biases
      zr:      (1, rb, W, 2*Ch) out — pre-activation z|r gates
      qpre:    (1, rb, W, Ch)   out — pre-activation candidate
    """
    rb = hxa_ref.shape[1]
    w = zr_ref.shape[2]
    # Assemble the rb+4 halo rows (2-padded coords [i*rb, i*rb+rb+4)) from
    # view i plus the first 4 rows of view i+1, and likewise rb+2 cr rows.
    rows = jnp.concatenate([hxa_ref[0], hxb_ref[0, :4]], axis=0)
    crw = jnp.concatenate([cra_ref[0], crb_ref[0, :2]], axis=0)

    def conv_valid(inp, wk_ref, nr, nc):
        """3x3 VALID conv as 9 shifted MXU matmuls, fp32 accumulation:
        (nr+2, nc+2, Cin) -> (nr, nc, Cout)."""
        acc = None
        for ty in range(3):
            for tx in range(3):
                part = jax.lax.dot_general(
                    inp[ty:ty + nr, tx:tx + nc, :], wk_ref[ty, tx],
                    (((2,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=precision)
                acc = part if acc is None else acc + part
        return acc

    # zr on the rb+2 halo rows / W+2 halo cols: the q conv below needs the
    # r gate one ring beyond the output block.  Ring positions outside the
    # image compute garbage pre-activations from the zero padding — harmless
    # because r multiplies h there, and padded h is 0 (= the Flax path's
    # SAME-padding zeros on the [r*h, x] conv input).
    zr_ext = (conv_valid(rows, wzr_ref, rb + 2, w + 2)
              + bzr_ref[0].astype(jnp.float32))
    r = jax.nn.sigmoid(zr_ext[..., ch:] + crw.astype(jnp.float32))
    h_halo = rows[1:rb + 3, 1:w + 3, :ch]
    rh = (r * h_halo.astype(jnp.float32)).astype(rows.dtype)
    rhx = jnp.concatenate([rh, rows[1:rb + 3, 1:w + 3, ch:]], axis=-1)
    qpre = conv_valid(rhx, wq_ref, rb, w) + bq_ref[0].astype(jnp.float32)

    zr_ref[0] = zr_ext[1:rb + 1, 1:w + 1].astype(zr_ref.dtype)
    qpre_ref[0] = qpre.astype(qpre_ref.dtype)


def _gates_launch(h, x, cr, wzr, bzr, wq, bq):
    b, hh, ww, ch = h.shape
    cin = ch + x.shape[-1]
    dt = h.dtype
    rb = gru_fused_row_block(ww, cin, ch, dt.itemsize)
    if rb is None:
        raise ValueError(
            f"gru_fused: working set for W={ww}, Cin={cin}, Ch={ch} exceeds "
            "VMEM budget — gru_fused_should_use must gate this launch")
    nb = pl.cdiv(hh, rb)
    # Row pad to (nb+1)*rb so the i+1 halo view of the LAST block stays in
    # bounds (deterministic zeros, no reliance on OOB-block semantics);
    # output rows are allocated at nb*rb and sliced back to H.
    rows_pad = (nb + 1) * rb
    hx = jnp.concatenate([h, x], axis=-1)
    hx_pad = jnp.pad(hx, ((0, 0), (2, rows_pad - hh - 2), (2, 2), (0, 0)))
    cr_pad = jnp.pad(cr, ((0, 0), (1, rows_pad - hh - 1), (1, 1), (0, 0)))
    # Weights in the compute dtype (the cast nn.Conv(dtype=...) applies);
    # biases ride fp32 and join the fp32 accumulators directly.
    wzr_c = wzr.astype(dt)
    wq_c = wq.astype(dt)
    bzr_c = bzr.astype(jnp.float32).reshape(1, -1)
    bq_c = bq.astype(jnp.float32).reshape(1, -1)
    full = lambda bi, i: (0, 0, 0, 0)  # noqa: E731 — weights, grid-invariant
    zr, qpre = pl.pallas_call(
        functools.partial(_gates_kernel, ch=ch,
                          precision=_precision_for(dt)),
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, rb, ww + 4, cin), lambda bi, i: (bi, i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, rb, ww + 4, cin),
                         lambda bi, i: (bi, i + 1, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, rb, ww + 2, ch), lambda bi, i: (bi, i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, rb, ww + 2, ch),
                         lambda bi, i: (bi, i + 1, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, 3, cin, 2 * ch), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2 * ch), lambda bi, i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, 3, cin, ch), full, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, ch), lambda bi, i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, rb, ww, 2 * ch), lambda bi, i: (bi, i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, rb, ww, ch), lambda bi, i: (bi, i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nb * rb, ww, 2 * ch), dt),
            jax.ShapeDtypeStruct((b, nb * rb, ww, ch), dt),
        ],
        interpret=interpret_enabled(),
    )(hx_pad, hx_pad, cr_pad, cr_pad, wzr_c, bzr_c, wq_c, bq_c)
    return zr[:, :hh], qpre[:, :hh]


# ---------------------------------------------------------------- reference
def _conv3x3_same(inp, kernel):
    """The exact conv the Flax path lowers to (nn.Conv via our
    models/extractor.conv wrapper): NHWC/HWIO, stride 1, symmetric (1,1)
    padding, default precision."""
    return jax.lax.conv_general_dilated(
        inp, kernel, window_strides=(1, 1), padding=((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _gates_reference(h, x, cr, wzr, bzr, wq, bq):
    """Pure-JAX twin of the fused kernel — the backward's linearization
    point.  Mirrors the Flax path's dtype behaviour (params cast to the
    activations' compute dtype, conv + bias in that dtype), so its VJP is
    the same XLA backward the Flax path runs."""
    dt = h.dtype
    ch = h.shape[-1]
    hx = jnp.concatenate([h, x], axis=-1)
    zr = _conv3x3_same(hx, wzr.astype(dt)) + bzr.astype(dt)
    r = jax.nn.sigmoid(zr[..., ch:] + cr)
    qpre = (_conv3x3_same(jnp.concatenate([r * h, x], axis=-1),
                          wq.astype(dt)) + bq.astype(dt))
    return zr, qpre


# --------------------------------------------------------------- custom VJP
@jax.custom_vjp
def gru_gates_fused(h, x, cr, wzr, bzr, wq, bq) -> Tuple[jnp.ndarray,
                                                         jnp.ndarray]:
    """Fused ConvGRU gate pre-activations.

    Args:
      h:   (B, H, W, Ch) hidden state, compute dtype.
      x:   (B, H, W, Cx) concatenated GRU inputs, compute dtype.
      cr:  (B, H, W, Ch) r-gate context bias (needed in-kernel for the
           recurrence coupling; cz/cq stay in the caller's pointwise tail).
      wzr, bzr: convzr parameters, (3, 3, Ch+Cx, 2*Ch) / (2*Ch,), fp32.
      wq, bq:   convq parameters, (3, 3, Ch+Cx, Ch) / (Ch,), fp32.

    Returns:
      (zr, qpre): pre-activation gate tensors in the compute dtype —
      identical in meaning (and checkpoint_name tagging site) to the Flax
      path's convzr/convq outputs.
    """
    return _gates_launch(h, x, cr, wzr, bzr, wq, bq)


def _gates_fwd(h, x, cr, wzr, bzr, wq, bq):
    # Residuals are the op's INPUTS only: under remat the residual rebuild
    # needs no Pallas re-run (the kernel outputs are dead in the recompute
    # when "gru_gates" is in config.remat_save, and the inputs themselves
    # come from the scan carry / saved motion features).
    return (gru_gates_fused(h, x, cr, wzr, bzr, wq, bq),
            (h, x, cr, wzr, bzr, wq, bq))


def _gates_bwd(residuals, g):
    # VJP of the pure-JAX twin: the identical conv backward the Flax path
    # runs (conv-transpose for activations, input x cotangent for weights).
    _, vjp = jax.vjp(_gates_reference, *residuals)
    return vjp(g)


gru_gates_fused.defvjp(_gates_fwd, _gates_bwd)
