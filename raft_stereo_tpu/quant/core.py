"""Post-training int8 weight quantization for the inference tier.

The correlation lookup is memory-bound (PERF.md section 3) and the
encoders were the dominant per-frame cost at streaming shapes on an
earlier runtime (not re-measured on the v5e: ROADMAP R7), so the bytes a
program MOVES — not the flops it runs — bound the turbo tier's
throughput.  This module implements the weight half of the int8 story:

* **Per-channel symmetric quantization** (Wu et al. 2020, "Integer
  Quantization for Deep Learning Inference" §4: per-output-channel scales
  hold conv-backbone accuracy where per-tensor scales do not): each
  encoder conv kernel is stored int8 with one fp32 scale per OUTPUT
  channel, ``q = clip(round(w / s), -127, 127)``, ``s = absmax_c / 127``.
* **Dequant in-register**: quantization happens on the HOST once per
  process (``quantize_variables``); the jitted program receives the int8
  tree and dequantizes at trace time (``dequantize_variables`` inside
  ``eval/runner.make_forward``), so the checkpoint on disk stays fp32,
  the host->device upload and the executable's parameter residency carry
  int8, and XLA upcasts next to the consuming conv.
* **Scope**: the feature/context encoders only — ``fnet`` / ``cnet`` /
  the shared-backbone projection (``conv2_res``/``conv2_out``) and the
  per-level ``context_zqr_conv*`` biases.  They run ONCE per frame and
  are pure conv stacks (the setting the PTQ literature validates); the
  GRU update block runs ``iters`` times over its own state and stays in
  the compute dtype — quantization error there would compound per
  iteration, which is exactly the failure mode the BF16_DRIFT series
  measured for low-precision correlation at depth.

``config.quant == "off"`` never calls anything here; the compiled
program is bitwise-identical to the pre-quant build (pinned by
tests/test_quant.py).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

QUANT_MODES = ("off", "int8", "int8_mxu")

# A quantized leaf is the fp32 kernel array replaced by a dict
# {"q8": int8[HWIO], "qscale": f32[1,1,1,O]} — a plain all-array pytree
# (jax.device_put / tree_map / jit all handle it; a string marker would
# not trace).  The key set IS the marker: no flax module in this model
# names parameters "q8"/"qscale".  The "int8_mxu" compute path adds an
# optional third member, {"ascale": f32[]} — the calibrated static
# activation scale for the conv's INPUT (quant/calibrate.py
# conv_input_scales); packs without it fall back to a dynamic in-graph
# max-abs scale (quant/matmul.py).

# Top-level param modules whose conv kernels quantize (the encoder
# surface; see module docstring for why the update block is excluded).
# ``context_zqr_conv*`` is matched by prefix — one conv per GRU level.
_ENCODER_MODULES = ("fnet", "cnet", "conv2_res", "conv2_out")
_ENCODER_PREFIXES = ("context_zqr_conv",)


_PACK_KEYS = frozenset(("q8", "qscale"))
_PACK_KEYS_ASCALE = frozenset(("q8", "qscale", "ascale"))


def is_quantized_leaf(x: Any) -> bool:
    """True for the {q8, qscale[, ascale]} pack ``quantize_variables``
    produces."""
    if not isinstance(x, dict):
        return False
    keys = frozenset(x.keys())
    return keys == _PACK_KEYS or keys == _PACK_KEYS_ASCALE


def _quantizable_module(name: str) -> bool:
    return name in _ENCODER_MODULES or any(
        name.startswith(p) for p in _ENCODER_PREFIXES)


def quantize_array(w: np.ndarray, axis: int = -1
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-channel symmetric int8 quantization of one conv kernel:
    ``(q int8, scale f32)`` with ``scale`` broadcastable against ``w``
    (kept dims).  ``axis`` is the channel axis the scales live on —
    the OUTPUT channel (-1 in HWIO).  All-zero channels get a scale of 1
    so dequant reproduces the zeros exactly instead of dividing by 0."""
    w = np.asarray(w, dtype=np.float32)
    absmax = np.max(np.abs(w), axis=tuple(
        a for a in range(w.ndim) if a != axis % w.ndim), keepdims=True)
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    return q, scale


def dequantize_array(q, scale):
    """``q * scale`` in fp32 — works on NumPy and (inside jit) on traced
    arrays; the in-jit use is the in-register dequant."""
    import jax.numpy as jnp

    if isinstance(q, np.ndarray):
        return q.astype(np.float32) * scale
    return q.astype(jnp.float32) * scale


def quantize_variables(variables: Dict, config=None,
                       act_scales: Optional[Dict[str, float]] = None
                       ) -> Dict:
    """The int8 inference tree: every encoder conv kernel in
    ``variables["params"]`` replaced by its {q, scale} pack; everything
    else (biases, norms, the update block, batch_stats) passes through
    untouched.  Host-side NumPy — runs once per process; the result is
    what ``eval/runner.make_forward`` programs with ``quant != "off"``
    take as their ``variables`` argument.  ``config`` is accepted for
    signature symmetry/forward evolution and currently unused (the
    quantized surface is architectural, not knob-dependent).

    ``act_scales`` maps "/"-joined module paths (e.g.
    ``"fnet/trunk/conv1"`` — the keys ``quant/calibrate.py
    conv_input_scales`` returns) to calibrated int8 scales for the
    conv's input; matching packs gain an ``ascale`` member so the
    int8_mxu compute path quantizes activations with static constants
    instead of in-graph max-abs reductions."""
    del config
    act_scales = act_scales or {}

    def walk(tree, under_encoder: bool, prefix: str):
        if not isinstance(tree, dict) or is_quantized_leaf(tree):
            return tree
        out = {}
        for name, sub in tree.items():
            in_scope = under_encoder or _quantizable_module(name)
            if (in_scope and name == "kernel"
                    and getattr(sub, "ndim", 0) == 4):
                q, scale = quantize_array(np.asarray(sub))
                pack = {"q8": q, "qscale": scale}
                ascale = act_scales.get(prefix)
                if ascale is not None:
                    pack["ascale"] = np.float32(ascale)
                out[name] = pack
            else:
                out[name] = walk(
                    sub, in_scope,
                    f"{prefix}/{name}" if prefix else name)
        return out

    out = dict(variables)
    if "params" in out:
        out["params"] = walk(dict(out["params"]), False, "")
    return out


def dequantize_variables(variables: Dict) -> Dict:
    """Invert ``quantize_variables`` structurally: every {q, scale} pack
    becomes the fp32 kernel again.  Called INSIDE the jitted forward —
    the int8 arrays are the program inputs, the multiply is fused next
    to the consuming conv, and the fp32 materialization is an XLA
    temporary rather than resident parameter state."""
    def walk(tree):
        if is_quantized_leaf(tree):
            return dequantize_array(tree["q8"], tree["qscale"])
        if isinstance(tree, dict):
            return {k: walk(v) for k, v in tree.items()}
        return tree

    return walk(dict(variables))


def tree_is_quantized(variables: Dict) -> bool:
    """True when ``variables`` contains at least one quantized pack."""
    found = [False]

    def walk(tree):
        if found[0]:
            return
        if is_quantized_leaf(tree):
            found[0] = True
            return
        if isinstance(tree, dict):
            for v in tree.values():
                walk(v)

    walk(variables)
    return found[0]


def quantized_param_bytes(variables: Dict) -> Dict[str, int]:
    """Byte accounting of one quantized tree: ``{"int8": n, "fp32": n,
    "scales": n}`` — what the drift tools report as the moved-bytes
    win."""
    acc = {"int8": 0, "fp32": 0, "scales": 0}

    def walk(tree):
        if is_quantized_leaf(tree):
            acc["int8"] += int(np.asarray(tree["q8"]).nbytes)
            acc["scales"] += int(np.asarray(tree["qscale"]).nbytes)
            if "ascale" in tree:
                acc["scales"] += int(np.asarray(tree["ascale"]).nbytes)
            return
        if isinstance(tree, dict):
            for v in tree.values():
                walk(v)
            return
        arr = np.asarray(tree)
        if arr.dtype.kind == "f":
            acc["fp32"] += int(arr.nbytes)

    walk(variables)
    return acc


# --------------------------------------------------------- corr pyramid
def quantize_symmetric(x, scale):
    """Traced int8 quantization of one activation tensor given its
    (static or traced) scale — the correlation-pyramid path
    (models/corr.py).  Callers wrap the surrounding computation in
    ``stop_gradient``: the int8 tier is inference-only."""
    import jax.numpy as jnp

    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)


def dynamic_scale(x, eps: float = 1e-12, qmax: float = 127.0):
    """In-graph per-tensor symmetric scale: ``max|x| / qmax`` — the
    fallback when no calibrated scale file is configured.  One reduction
    per tensor per forward; deterministic for a given input.  ``qmax``
    is the grid's largest representable magnitude: 127 for int8 (the
    default), ``FP8_QMAX`` for the float8_e4m3 correlation entries."""
    import jax.numpy as jnp

    return jnp.maximum(jnp.max(jnp.abs(x)), eps) / qmax


# float8_e4m3's largest finite magnitude (1.75 · 2^8): the fp8 analogue
# of int8's 127 for symmetric scale construction.
FP8_QMAX = 448.0


def quantize_fp8(x, scale, dtype):
    """Traced fp8 quantization of one activation tensor: clip to the
    finite e4m3 range first (the cast saturates NaN/inf semantics vary
    by backend — an explicit clip keeps the grid deterministic), then
    cast.  Dequant is ``q.astype(f32) * scale``, same as int8."""
    import jax.numpy as jnp

    return jnp.clip(x / scale, -FP8_QMAX, FP8_QMAX).astype(dtype)


def clipped_scale(absmax_percentile: float) -> float:
    """A calibrated percentile-clipped range to its int8 scale."""
    return max(float(absmax_percentile), 1e-12) / 127.0
