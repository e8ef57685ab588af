"""The control of ``correct``: the nearest precision below the one a
configuration states, which the comparison has to fail.

* float32 (accuracy): bfloat16 — the program has that path of its own
  (``mixed_precision`` with one-pass products), so the program with it
  switched on is the control: ``program_control_rig``.
* bfloat16 (realtime): int8.  The program's own int8 path lowers the
  encoders and the correlation only; the control is the plain reference
  put in the program's place with BOTH inputs of EVERY product rounded to
  int8 (per-tensor scale for activations, per-output-channel for kernels):
  ``int8_inputs``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def _fake_int8(x, axes):
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def int8_inputs(a, b):
    """An activation (any rank) and a kernel (HWIO) or a second activation:
    each rounded to the int8 grid of its own largest magnitude."""
    a = _fake_int8(a, None)
    if b.ndim == 4 and b.shape[0] <= 7 and b.shape[1] <= 7:   # HWIO kernel
        return a, _fake_int8(b, (0, 1, 2))
    return a, _fake_int8(b, None)


def bf16_inputs(a, b):
    """Both inputs rounded to bfloat16's 8 bits of mantissa and kept in
    float32: at ``highest`` each product is then the exact product of two
    bfloat16 numbers, summed in float32, as the chip's matrix unit does it.
    ``reduce_precision`` is an operation of its own that no compiler pass
    folds away, as it may a pair of converts.  Not a control: the
    yardstick's own model of the precision a bfloat16 configuration states
    (``post.py`` counts the program's gaps in units of this one's)."""
    return (lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7),
            lax.reduce_precision(b, exponent_bits=8, mantissa_bits=7))


LOWER = {"int8": int8_inputs, "bf16": bf16_inputs}


def program_control_rig(config: dict):
    """The ``harness.TestRig`` that switches the program's own
    lower-precision path on, for a configuration that states float32."""
    from benchmark import harness

    if config["model"]["mixed_precision"]:
        raise ValueError("the program has no whole-model path below "
                         "bfloat16: use the reference-based control")
    return harness.TestRig(
        program_overrides={"mixed_precision": True},
        env={"JAX_DEFAULT_MATMUL_PRECISION": "bfloat16"})
