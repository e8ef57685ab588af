"""Quantized compute core: int8×int8→int32 convolution on the MXU.

The r15 int8 tier moved BYTES — weights ship int8 but
``dequantize_variables`` upcasts at trace time, so every matmul still
runs fp32 (never timed on the chip: ROADMAP R7).
This module converts the bytes win into a flops
win (AQT-style, ROADMAP open item 1): the conv itself multiplies
int8×int8 and accumulates int32 (``preferred_element_type=jnp.int32``
— the MXU's native low-precision mode on TPU; XLA:CPU lowers the same
program to int8 GEMMs), and the per-output-channel rescale to fp32
happens ONCE, *after* accumulation:

    y = conv_i8(q(x), q8) · (ascale · qscale) + bias

* **Rescale-after-accumulate contract**: the int32 accumulator is
  exact (no rounding between taps), so the only error sources are the
  two quantizations — the same error budget the r15 weights-only mode
  measured, plus the activation quantization the drift gate re-measures
  (tools/quant_drift.py int8_mxu rows).  Accumulator headroom: the
  widest conv here reduces K = 3·3·128 = 1152 int8 products,
  1152 · 127² ≈ 1.86e7 « 2³¹ — overflow-free by 2 orders of magnitude.
* **Activation scales**: static per-conv scales calibrated by
  ``quant/calibrate.py`` (percentile-clipped, carried in the variables
  pack as ``ascale``); packs without one fall back to a dynamic
  per-tensor max-abs scale computed in-graph (one extra reduction —
  the ``context_zqr`` convs take this path, they are outside the
  calibration passes' capture surface).
* **Routing is data-driven**: ``QuantConv`` subclasses ``nn.Conv`` and
  switches on what the variables tree carries.  A plain fp kernel (the
  ``quant="off"`` and weights-only ``"int8"`` paths — the latter
  dequantizes the tree before apply) delegates to ``nn.Conv.__call__``
  unchanged, keeping the jaxpr-level zero-int8-ops pin for ``"off"``
  bitwise intact; a {q8, qscale[, ascale]} pack (the ``"int8_mxu"``
  path — eval/runner passes packs THROUGH to the traced program) takes
  the quantized-compute branch.  Inference-only, like every quant mode.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.extend.core import Literal

from raft_stereo_tpu.quant.core import (dynamic_scale, is_quantized_leaf,
                                        quantize_symmetric)

_NHWC_HWIO = ("NHWC", "HWIO", "NHWC")


def _as_tuple(v, rank: int) -> Tuple[int, ...]:
    if isinstance(v, int):
        return (v,) * rank
    return tuple(v)


def int8_conv_int32(x_q, w_q, *, strides: Sequence[int],
                    padding: Union[str, Sequence[Tuple[int, int]]],
                    dimension_numbers=_NHWC_HWIO):
    """The quantized conv primitive: int8 activations × int8 weights →
    int32 accumulator in ONE op (``preferred_element_type``) — no fp32
    materialization of either operand feeds the conv (the jaxpr pin
    tests/test_quant.py asserts).  Explicit zero padding commutes with
    symmetric quantization (0 → 0), so padding the int8 tensor is exact."""
    return jax.lax.conv_general_dilated(
        x_q, w_q, window_strides=tuple(strides), padding=padding,
        dimension_numbers=dimension_numbers,
        preferred_element_type=jnp.int32)


def int8_dot_int32(x_q, w_q, dimension_numbers=None):
    """int8×int8→int32 ``dot_general`` — the matmul twin of
    ``int8_conv_int32`` (1×1 convs lowered as GEMMs, and the building
    block a future quantized GRU extension would use).  Defaults to a
    plain last-dim × first-dim contraction."""
    if dimension_numbers is None:
        dimension_numbers = (((x_q.ndim - 1,), (0,)), ((), ()))
    return jax.lax.dot_general(x_q, w_q, dimension_numbers,
                               preferred_element_type=jnp.int32)


def quantize_activation(x, ascale=None):
    """One activation tensor to (int8, fp32 scale): the calibrated
    static ``ascale`` when the pack carries one, else the dynamic
    per-tensor max-abs fallback (quant/core.dynamic_scale)."""
    if ascale is None:
        ascale = dynamic_scale(x)
    ascale = jnp.asarray(ascale, jnp.float32)
    return quantize_symmetric(x.astype(jnp.float32), ascale), ascale


def quantized_conv_apply(x, pack, bias, *, strides, padding, out_dtype):
    """The full quantized conv: quantize input → int8 conv (int32
    accumulate) → per-output-channel rescale to fp32 AFTER accumulation
    → bias add → cast to the module compute dtype."""
    x_q, ascale = quantize_activation(x, pack.get("ascale"))
    acc = int8_conv_int32(x_q, pack["q8"], strides=strides,
                          padding=padding)
    # qscale is f32[1,1,1,O] (kept dims from quantize_array); the
    # combined factor stays a rank-4 broadcast against NHWC output.
    y = acc.astype(jnp.float32) * (ascale
                                   * jnp.asarray(pack["qscale"],
                                                 jnp.float32))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(out_dtype)


def int8_matmul_report(closed) -> dict:
    """Walk a jaxpr (recursively through sub-jaxprs: pjit/scan/while
    bodies, custom_jvp calls) and classify its matmuls — the shared
    inspection behind the int8_mxu jaxpr pin (tests/test_quant.py,
    scripts/quant_smoke.py):

    * ``int8_convs`` / ``int8_dots``: int8 × int8 → int32 (the MXU path
      — must be ≥ 1 under ``quant="int8_mxu"``);
    * ``other_matmuls``: everything else (fp convs/dots — the GRU and
      non-extractor surface, legitimately fp under every mode);
    * ``dequant_fed_matmuls``: convs/dots consuming an fp32 tensor
      produced DIRECTLY by an int8 → fp32 convert — the
      dequantize-then-fp32 anti-pattern the rescale-after-accumulate
      contract forbids (must be 0)."""
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    stats = {"int8_convs": 0, "int8_dots": 0, "other_matmuls": 0,
             "dequant_fed_matmuls": 0}

    def subjaxprs(p):
        if hasattr(p, "eqns"):                      # core.Jaxpr
            yield p
        elif hasattr(p, "jaxpr"):                   # core.ClosedJaxpr
            yield p.jaxpr
        elif isinstance(p, (list, tuple)):
            for item in p:
                yield from subjaxprs(item)

    def walk(jxp):
        dequant_outs = set()
        for eqn in jxp.eqns:
            prim = eqn.primitive.name
            if prim == "convert_element_type":
                src, dst = eqn.invars[0], eqn.outvars[0]
                if (getattr(src, "aval", None) is not None
                        and src.aval.dtype == jnp.int8
                        and dst.aval.dtype == jnp.float32):
                    dequant_outs.add(dst)
            elif prim in ("conv_general_dilated", "dot_general"):
                in_dt = [v.aval.dtype for v in eqn.invars[:2]]
                out_dt = eqn.outvars[0].aval.dtype
                if (all(d == jnp.int8 for d in in_dt)
                        and out_dt == jnp.int32):
                    key = ("int8_convs" if prim == "conv_general_dilated"
                           else "int8_dots")
                    stats[key] += 1
                else:
                    stats["other_matmuls"] += 1
                if any(v in dequant_outs for v in eqn.invars
                       if not isinstance(v, Literal)):
                    stats["dequant_fed_matmuls"] += 1
            for sub in eqn.params.values():
                for j in subjaxprs(sub):
                    walk(j)

    walk(jaxpr)
    return stats


class QuantConv(nn.Conv):
    """``nn.Conv`` that runs the int8 MXU path when its kernel arrives
    as a {q8, qscale[, ascale]} pack.

    * init / fp apply: identical to ``nn.Conv`` (same param tree, same
      program — the ``quant="off"`` bitwise pin rides on this).
    * calibration: sows its INPUT under ``intermediates/<path>/qin`` so
      the existing ``quant/calibrate.py`` capture passes collect conv
      input ranges with zero calibration-side model knowledge (conv
      inputs are mostly relu/norm outputs, which the automatic
      ``__call__``-output capture never sees).
    * pack apply: ``quantized_conv_apply`` — the variables tree decides
      the path, not a module attribute, so ONE module class serves
      every quant mode and executables differ only by their inputs."""

    @nn.compact
    def __call__(self, x):
        if not self.is_initializing():
            # No-op unless "intermediates" is mutable (the calibration
            # apply); skipped at init so variable trees stay pristine.
            self.sow("intermediates", "qin", x)
        kernel = self.get_variable("params", "kernel")
        if not is_quantized_leaf(kernel):
            return super().__call__(x)
        if self.feature_group_count != 1:
            raise NotImplementedError(
                "QuantConv int8 path supports feature_group_count=1 "
                "only (the encoder surface)")
        bias = (self.get_variable("params", "bias")
                if self.use_bias else None)
        rank = len(self.kernel_size)
        return quantized_conv_apply(
            x, kernel, bias,
            strides=_as_tuple(self.strides or 1, rank),
            padding=self.padding,
            out_dtype=self.dtype or x.dtype)
