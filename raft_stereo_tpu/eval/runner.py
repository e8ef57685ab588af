"""Jitted inference runner with per-shape compile caching.

The eval datasets have per-image shapes (KITTI/ETH3D/Middlebury all vary);
under jit each padded shape compiles once and is reused.  The reference's
50-image warmup discard absorbs cuDNN autotuning — here it absorbs XLA
compilation the same way (reference: evaluate_stereo.py:77-82).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_stereo_tpu.config import RaftStereoConfig
from raft_stereo_tpu.kernels.corr_lookup import path_choices
from raft_stereo_tpu.models.raft_stereo import RAFTStereo, create_model
from raft_stereo_tpu.ops.padding import InputPadder
from raft_stereo_tpu.profiling import annotate
from raft_stereo_tpu.telemetry.spans import Phases

log = logging.getLogger(__name__)

# Donated image buffers alias an output only when XLA finds one of the
# same byte size; the stereo forward returns a 1-channel f32 flow, so the
# 3-channel uint8 inputs never pair and every backend warns once per
# compile.  The donation is still declared (caller contract: inputs are
# consumed) so any future same-size output — warm-start state, multi-head
# returns — aliases without touching the dispatch sites; the warning is
# pure noise for this program shape.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

# GRU-iteration depth at which bf16 correlation measurably drifts on TRAINED
# weights: at iters=32 the per-pixel p99 reaches ~6.5-7 px with ΔEPE +0.04 px
# (tools/bf16_drift.py), while at the realtime depth (7) drift is ≤0.03 px
# EPE.  Eval/demo runs at or past this depth flip the correlation features to
# fp32 (everything else stays bf16) unless the caller opts out.
DEEP_ITERS_FP32_CORR = 16

# Host phases of one ``__call__`` / ``run_batch``, in order (spans
# ``infer.<name>``).
RUNNER_PHASES = ("stack_pad", "upload", "execute", "fetch", "unpad")


def effective_inference_config(config: RaftStereoConfig, iters: int,
                               corr_fp32_auto: bool = True
                               ) -> RaftStereoConfig:
    """The config an inference path should actually run: deep-iteration
    bf16 correlation gets ``corr_fp32`` flipped on (the measured 32-iter
    drift on trained weights, tools/bf16_drift.py).  Shared by the solo
    ``InferenceRunner`` and the serving engine so both compile the same
    program for the same request class — the engine's batch-1 bucket is
    bitwise-equal to solo inference by construction.  Another model
    class's configuration (IGEV-Stereo's: its volumes are float32 by
    construction) runs as given."""
    if not isinstance(config, RaftStereoConfig):
        return config
    if (corr_fp32_auto and iters >= DEEP_ITERS_FP32_CORR
            and config.mixed_precision and not config.corr_fp32):
        log.warning(
            "iters=%d >= %d with bf16 correlation: enabling corr_fp32 "
            "for this runner (measured 32-iter drift on trained "
            "weights, tools/bf16_drift.py; pass corr_fp32_auto=False "
            "to keep bf16 corr)", iters, DEEP_ITERS_FP32_CORR)
        return dataclasses.replace(config, corr_fp32=True)
    return config


class ArchitectureLacksOption(ValueError):
    """An inference option that only RAFT-Stereo's program has (the
    convergence-gated loop, int8 weights, warm-start streaming), asked of
    another model class (IGEV-Stereo: ``config.IgevStereoConfig``)."""


def require_raft(config, option: str) -> None:
    """Raise ``ArchitectureLacksOption`` where ``config`` is not
    RAFT-Stereo's."""
    if not isinstance(config, RaftStereoConfig):
        raise ArchitectureLacksOption(
            f"{option}: a {getattr(config, 'KIND', type(config).__name__)!r} "
            f"model has no early exit, int8 path or warm-start streaming; "
            f"only RaftStereoConfig models do")


def early_exit_enabled(config: RaftStereoConfig) -> bool:
    """Whether ``make_forward`` programs for this config return the extra
    ``iters_used`` scalar (the convergence-gated while-loop path,
    models/raft_stereo.py)."""
    return config.exit_threshold_px > 0


def make_forward(model: RAFTStereo, iters: int, fetch_dtype=None,
                 donate_images: bool = True, warm_start: bool = False,
                 return_state: bool = False,
                 ctx: Optional[str] = None,
                 hidden_init: bool = False,
                 return_hidden: bool = False,
                 return_confidence: bool = False):
    """The one jitted inference program both the solo runner and the
    serving engine compile, per (padded shape, batch): cast -> forward ->
    optional half-precision fetch cast.  Built here so the two paths share
    one jaxpr by construction (the serving parity contract).

    With ``model.config.exit_threshold_px > 0`` the program returns
    ``(flow_up, iters_used)`` — the convergence-gated while-loop's actual
    trip count rides the fetch as one extra int32 scalar; otherwise the
    return is the flow alone and the program is bitwise-identical to the
    pre-early-exit build (``early_exit_enabled`` tells callers which
    contract they compiled).

    ``donate_images`` marks the image arguments donated
    (``donate_argnums``): both call sites upload fresh per-call device
    buffers, so the runtime is free to reclaim or alias them the moment
    the program consumes them.  Donation never changes numerics (tested)
    and the module-level filter above silences XLA's not-usable note for
    output shapes that cannot alias.

    Streaming variants (round 14 warm-start sessions; both default OFF,
    keeping the base program byte-for-byte the pre-session build):

    * ``return_state=True`` — the program additionally returns the final
      PADDED low-res x-flow (``flow_low``, (N, Hp/f, Wp/f) float32, f =
      ``config.downsample_factor``): the temporal state a streaming
      session feeds the next frame.  Same math, same ``flow_up`` values
      (pinned bitwise by tests/test_sessions.py) — one extra small
      output rides the fetch.  Return order: ``(flow_up, flow_low[,
      iters_used])``.
    * ``warm_start=True`` (implies ``return_state``) — the program takes
      a fourth traced argument ``flow_init`` ((N, Hp/f, Wp/f) float32)
      and seeds the GRU refinement from it instead of zero
      (models/raft_stereo.py; RAFT's warm start, arXiv 2109.07547 §3).
      ``flow_init`` is donated alongside the images when
      ``donate_images`` — it is the same shape/dtype as the
      ``flow_low`` output, so XLA can alias the state round-trip.
    * ``ctx`` ("save" | "reuse"; streaming only, implies the streaming
      signature) — the per-session CONTEXT cache (round 15): "save"
      appends the frame's context bundle (initial GRU hidden states +
      context biases, models/raft_stereo.py ``return_ctx``) as the LAST
      output; "reuse" appends the bundle as the LAST traced input and
      SKIPS the context encoder entirely (``ctx_init``) — the program a
      static-camera stream runs once the inter-frame delta proves the
      scene unchanged.  The bundle is a pytree and rides jit like any
      other argument; it is never donated (the session re-feeds it
      frame after frame from its host copy).
    * ``return_hidden=True`` (streaming only, implies the streaming
      signature) — the program additionally returns the FINAL per-level
      GRU hidden states (a tuple of (N, Hp/(f·2^l), Wp/(f·2^l), C_l)
      arrays in the model's compute dtype): the second half of the
      temporal state, which ``flow_init`` alone leaves cold (round-19
      hidden-state warm start).  Appended after ``iters_used`` and
      before the ctx bundle.
    * ``hidden_init=True`` (implies ``return_hidden``'s signature use —
      warm-h programs both consume and return the tree) — the program
      takes the previous frame's hidden tree as an extra traced input
      (after ``flow_init``, before any ctx bundle) and the refinement
      loop resumes from those EVOLVED states instead of the fresh
      ``tanh`` init.  Donated alongside the images when
      ``donate_images`` — same shapes/dtypes as the returned tree, so
      XLA can alias the state round-trip.

    * ``return_confidence=True`` — the program additionally returns the
      per-pixel confidence element (models/raft_stereo.py): one 2-tuple
      ``(conf_low, conf_up)`` of the (N, Hp/f, Wp/f) feature-resolution
      map and its convex-upsampled (N, Hp, Wp) full-res counterpart,
      both float32 in (0, 1], derived from the refinement loop's own
      convergence signals (final |Δdisparity|, trajectory EWMA, and —
      adaptive — the iteration-budget fraction).  Appended after
      ``iters_used`` and before the hidden tree.  Off (default) the
      program is bitwise-identical to the pre-confidence build (pinned
      by tests).  Composes with every streaming variant and with the
      base signature; unsupported on the mesh path
      (``make_forward_mesh``).

    Traced-input order (streaming): ``(variables, images1, images2
    [, flow_init][, hidden][, ctx])``; return order: ``(flow_up,
    flow_low[, iters_used][, confidence][, hidden][, ctx])``.

    With ``model.config.quant == "int8"`` every variant expects the
    QUANTIZED variable tree (quant/core.quantize_variables) and
    dequantizes it in-register at the top of the program — int8 is what
    uploads and resides; ``quant="int8_mxu"`` passes the int8 packs
    THROUGH to the traced program so the encoder convs run the
    int8×int8→int32 compute path (quant/matmul.QuantConv — the
    variables tree routes, no dequant is traced); ``quant="off"``
    builds the exact pre-quant jaxpr (no dequant ops are traced).
    """
    adaptive = early_exit_enabled(model.config)
    quantized = model.config.quant == "int8"

    def prepare(variables):
        if quantized:
            from raft_stereo_tpu.quant.core import dequantize_variables
            return dequantize_variables(variables)
        return variables

    if (warm_start or return_state or ctx is not None
            or hidden_init or return_hidden):
        if ctx not in (None, "save", "reuse"):
            raise ValueError(f"ctx={ctx!r}: use None, 'save', or 'reuse'")

        def fwd_stream(variables, images1, images2, *extra):
            with annotate("prep"):
                img1 = images1.astype(jnp.float32)
                img2 = images2.astype(jnp.float32)
            pos = 0
            flow_init = None
            if warm_start:
                flow_init = extra[pos].astype(jnp.float32)
                pos += 1
            hidden = None
            if hidden_init:
                hidden = extra[pos]
                pos += 1
            ctx_init = extra[pos] if ctx == "reuse" else None
            kwargs = ({"return_confidence": True} if return_confidence
                      else {})
            out = model.apply(
                variables if not quantized else prepare(variables),
                img1, img2, iters=iters, test_mode=True,
                flow_init=flow_init, ctx_init=ctx_init,
                return_ctx=(ctx == "save"),
                hidden_init=hidden, return_hidden=return_hidden,
                **kwargs)
            flow_up = out[1]
            if fetch_dtype is not None:
                with annotate("upsample"):
                    flow_up = flow_up.astype(fetch_dtype)
            # flow_low stays float32 regardless of fetch_dtype: it is the
            # next frame's init, and a half-precision state would compound
            # rounding frame over frame.  (The hidden tree rides in the
            # model's own compute dtype — it re-enters the SAME compute
            # path, so there is no precision boundary to cross.)
            ret = (flow_up, out[0].astype(jnp.float32))
            src = 2
            if adaptive:
                ret = ret + (out[src],)
                src += 1
            if return_confidence:
                ret = ret + (out[src],)
                src += 1
            if return_hidden:
                ret = ret + (out[src],)
                src += 1
            if ctx == "save":
                ret = ret + (out[src],)
            return ret

        donate: Tuple[int, ...] = ()
        if donate_images:
            donate = (1, 2)
            pos = 3
            if warm_start:
                donate = donate + (pos,)
                pos += 1
            if hidden_init:
                donate = donate + (pos,)
        return jax.jit(fwd_stream, donate_argnums=donate)

    def fwd(variables, images1, images2):  # (N, Hp, Wp, 3)
        with annotate("prep"):
            img1 = images1.astype(jnp.float32)
            img2 = images2.astype(jnp.float32)
        kwargs = {"return_confidence": True} if return_confidence else {}
        out = model.apply(variables if not quantized
                          else prepare(variables),
                          img1, img2, iters=iters, test_mode=True,
                          **kwargs)
        flow_up = out[1]
        if fetch_dtype is not None:
            with annotate("upsample"):
                flow_up = flow_up.astype(fetch_dtype)
        if return_confidence:
            # Base-signature confidence: (flow_up[, iters_used], conf) —
            # the conf element is the model's (conf_low, conf_up) tuple.
            return ((flow_up, out[2], out[3]) if adaptive
                    else (flow_up, out[2]))
        return (flow_up, out[2]) if adaptive else flow_up

    return jax.jit(fwd, donate_argnums=(1, 2) if donate_images else ())


class MeshForward:
    """A mesh-sharded inference program with the ``make_forward`` calling
    convention (``fn(variables, images1, images2) -> flow_up``), plus the
    sharding-context plumbing a GSPMD trace needs.

    The model's sharded executors (``parallel/rows_sharded.py`` trunk,
    ``parallel/rows_gru.py`` loop, ``parallel/corr_sharded.py`` volume)
    discover their mesh through context managers that must be ACTIVE
    whenever the function traces — and jit traces lazily, at the first
    call for each shape and inside ``.lower()`` on the AOT path.  This
    wrapper re-enters the contexts around both entry points, so the
    serving engine can treat a sharded program exactly like a solo one
    (dispatch it, AOT-lower it for the persistent executable cache,
    instrument it through the CompileRegistry)."""

    def __init__(self, jitted, mesh, rows: int, corr: int):
        self._jitted = jitted
        self.mesh = mesh
        self._rows = rows
        self._corr = corr

    def _contexts(self):
        import contextlib

        from raft_stereo_tpu.parallel.corr_sharded import corr_sharding
        from raft_stereo_tpu.parallel.mesh import ROWS_AXIS
        from raft_stereo_tpu.parallel.rows_sharded import rows_sharding

        stack = contextlib.ExitStack()
        if self._rows > 1:
            stack.enter_context(rows_sharding(self.mesh, ROWS_AXIS))
        if self._corr > 1:
            stack.enter_context(corr_sharding(self.mesh))
        return stack

    def __call__(self, *args):
        with self._contexts():
            return self._jitted(*args)

    def lower(self, *args, **kwargs):
        with self._contexts():
            return self._jitted.lower(*args, **kwargs)


def make_forward_mesh(model: RAFTStereo, iters: int, mesh,
                      fetch_dtype=None, donate_images: bool = True):
    """Mesh-sharded variant of ``make_forward``: ONE jitted program whose
    forward runs sharded over ``mesh`` per the model config's
    ``rows_shards`` / ``corr_w2_shards`` (+ ``rows_gru`` for full-loop
    context parallelism), with the image buffers and parameters
    replicated in and the full-resolution disparity GATHERED out — the
    program an "xl" serving bucket dispatches when one full-resolution
    pair cannot fit (or meet latency) on one device
    (a compiler's memory analysis on an earlier runtime: 141 GiB at rows=1
    vs 13.8 GiB/device on a 16-way rows mesh; not re-measured on the v5e).

    Same calling convention and numerics contract as the base program:
    ``fn(variables, images1, images2) -> (N, Hp, Wp) flow`` with the
    sharded output equal to the solo program's up to float reassociation
    (tests/test_xl.py pins 5e-4).
    With a trivial mesh (every axis 1) this IS ``make_forward`` — the
    identical jaxpr, bitwise, so a rows=1 xl tier degrades to the solo
    program instead of a subtly different one.

    Restrictions (validated here so misconfigurations fail at build, not
    mid-dispatch): early exit is unsupported (the row-sharded loop
    executor runs a fixed-depth program — config.py already rejects the
    combination; the corr-only mesh inherits the same contract so every
    xl program has one output arity), and so are the streaming
    warm/ctx families (sessions stay single-device)."""
    import jax

    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = model.config
    rows, corr = cfg.rows_shards, cfg.corr_w2_shards
    if early_exit_enabled(cfg):
        raise ValueError(
            "make_forward_mesh: early exit (exit_threshold_px > 0) is "
            "unsupported on mesh-sharded programs — xl tiers run the "
            "fixed-depth program")
    if rows <= 1 and corr <= 1:
        # Trivial mesh: the solo program, bitwise (tests/test_xl.py).
        return make_forward(model, iters, fetch_dtype,
                            donate_images=donate_images)

    def fwd(variables, images1, images2):  # (N, Hp, Wp, 3)
        with annotate("prep"):
            img1 = images1.astype(jnp.float32)
            img2 = images2.astype(jnp.float32)
        out = model.apply(variables, img1, img2, iters=iters,
                          test_mode=True)
        flow_up = out[1]
        if fetch_dtype is not None:
            with annotate("upsample"):
                flow_up = flow_up.astype(fetch_dtype)
        return flow_up

    # Replicated in, gathered out: the host uploads each image once per
    # device (megabytes — small next to the sharded activations), the
    # shard_map executors inside re-slice to their own row/bin spans, and
    # the caller fetches one assembled full-res disparity with no
    # host-side reassembly.
    repl = NamedSharding(mesh, P())
    jitted = jax.jit(fwd,
                     donate_argnums=(1, 2) if donate_images else (),
                     in_shardings=(repl, repl, repl),
                     out_shardings=repl)
    return MeshForward(jitted, mesh, rows, corr)


@dataclasses.dataclass
class StreamFrame:
    """One frame of a warm-started sequence (``InferenceRunner.run_stream``).

    ``flow`` is the usual unpadded (H, W) x-flow; ``flow_low`` is the
    PADDED low-res x-flow to feed back as the next frame's
    ``prev_flow_low`` — padded on purpose: consecutive frames share the
    padded grid, so the state round-trips without resampling."""

    flow: np.ndarray             # (H, W) float32 x-flow (= -disparity)
    flow_low: np.ndarray         # (Hp/f, Wp/f) float32 padded low-res state
    seconds: float               # same stop clock as __call__ (result fetch)
    iters_used: Optional[int]    # GRU trip count (None without early exit)
    warm: bool                   # True when prev_flow_low seeded the GRU
    # Final per-level GRU hidden states (tuple of (h_l, w_l, C_l) host
    # arrays, batch axis stripped) — the next frame's ``prev_hidden``.
    # None unless the caller asked for it (``carry_hidden``).
    hidden: Optional[object] = None

    @property
    def disparity(self) -> np.ndarray:
        return -self.flow


def _fill_edge_padded(buf: np.ndarray, images, pads) -> None:
    """``buf[:] = np.pad(np.stack(images), .., mode="edge")``, bitwise, in
    one pass over ``buf`` (n, Hp, Wp, C): each image into its window, then
    the strips beside it and, corners included, above and below it."""
    l, r, t, b = pads
    hp, wp = buf.shape[1:3]
    for out, image in zip(buf, images):
        rows = out[t:hp - b]
        rows[:, l:wp - r] = image
        rows[:, :l] = rows[:, l:l + 1]
        rows[:, wp - r:] = rows[:, wp - r - 1:wp - r]
        out[:t] = out[t]
        out[hp - b:] = out[hp - b - 1]


@functools.partial(jax.jit, static_argnames="pads")
def _crop_flat(flows, pads):
    """``InputPadder.unpad`` on the device, flattened.  A 1-D result has one
    layout, so the fetch arrives row-major; the TPU's compiler lays the
    forward's own (n, Hp, Wp) result out with H minor and a 3-D crop of it
    with n minor, and NumPy would be handed those strides."""
    l, r, t, b = pads
    return flows[:, t:flows.shape[1] - b, l:flows.shape[2] - r].reshape(-1)


# The least a sub-batch of a pipelined call stages (both images of its
# pairs, as uploaded): 16 of the realtime cell's 128 KITTI uint8 pairs are
# 46.0e6 B.  Read on the v5e (PERF.md section 6, PR 31): a program of 16
# pairs costs 5.67 ms a pair against 5.03 at 128, which is what a finer
# split would pay more of, and a coarser one leaves more of the first fill
# and upload and of the last fetch exposed.
_SUB_BATCH_MIN_BYTES = 40 << 20


def _sub_batches(n: int, staged_bytes: int) -> int:
    """How many equal sub-batches ``_run_padded`` runs a fixed-depth call
    of ``n`` pairs and ``staged_bytes`` as: the most that divide ``n`` and
    leave each ``_SUB_BATCH_MIN_BYTES`` to stage; 1 is the call as one
    program (a call of one, a call under twice that, an ``n`` that no such
    number divides)."""
    most = min(n, staged_bytes // _SUB_BATCH_MIN_BYTES)
    return max((k for k in range(2, most + 1) if n % k == 0), default=1)


class InferenceRunner:
    """``runner(image1, image2)`` → full-resolution disparity-flow (H, W).

    Inputs are (H, W, 3) float/uint8 NumPy images; padding to /32,
    test-mode forward, and exact unpadding happen inside.
    """

    def __init__(self, config: RaftStereoConfig, variables,
                 iters: int = 32, divis_by: int = 32,
                 shape_bucket: Optional[int] = None,
                 max_cached_shapes: int = 16,
                 corr_fp32_auto: bool = True,
                 fetch_dtype: Optional[str] = None,
                 cost_registry=None, cost_site: str = "eval",
                 donate_images: bool = True,
                 exit_threshold_px: Optional[float] = None,
                 exit_min_iters: Optional[int] = None,
                 quant: Optional[str] = None,
                 quant_act_scales=None):
        """``shape_bucket`` (e.g. 64) pads to a coarser grid than the
        reference's /32, collapsing nearby image shapes into one compiled
        program — fewer Middlebury recompiles at the cost of deviating from
        the reference's exact padding (off by default; the parity tests
        require /32 semantics).  ``max_cached_shapes`` bounds the per-shape
        executable cache LRU-style so a many-shape eval (Middlebury-F) holds
        memory flat instead of accumulating compiled programs forever.
        ``corr_fp32_auto`` guards deep-iteration bf16 correlation: at
        ``iters >= DEEP_ITERS_FP32_CORR`` a mixed-precision config without
        ``corr_fp32`` gets it enabled here (with a one-line warning) —
        the measured 32-iter drift on trained weights is the reason
        (tools/bf16_drift.py).  Pass False to measure raw bf16 numerics
        (tools/bf16_drift.py does).
        ``cost_registry`` (telemetry/costs.CompileRegistry | None): when
        set, every per-shape compile routes through the AOT path
        (``jit(...).lower(...).compile()``) so the executable's
        cost/memory analysis and compile wall time are recorded, and the
        cache's size/evictions feed its instruments; None (default) keeps
        the exact plain-``jax.jit`` dispatch.  ``cost_site`` labels the
        records ("eval" here, "serving" for service workers).
        ``fetch_dtype`` ("fp16" | "bf16" | None): cast the flow on DEVICE
        before the device->host fetch, halving the down-leg bytes of the
        product path.  fp16 is the right
        half precision for a disparity map: |flow| < 2048 everywhere the
        metrics are defined (|d| < 192 — evaluate_stereo.py:133-135), so
        the worst ulp is 0.125 px at the far end and the mean rounding
        error is ~ulp/4, far below metric noise; bf16's 8-bit mantissa
        would round 190 px to ±0.75 px.  Results are returned as float32
        regardless.
        ``exit_threshold_px`` / ``exit_min_iters`` (None = the config's
        own knobs): adaptive GRU early exit — with a threshold > 0 the
        test-mode loop stops once the mean |Δdisparity| stalls
        (config.py), ``iters`` becomes the depth CAP, and every call
        records its actual trip count (``last_iters_used`` /
        ``iters_used_mean()``).  The default keeps the fixed-depth scan
        program bitwise-unchanged.
        ``quant`` (None = the config's own knob): "int8" runs this
        runner on the post-training int8 path — the given fp32
        ``variables`` are quantized HERE at construction
        (quant/core.quantize_variables; checkpoints on disk stay fp32)
        and every compiled program dequantizes in-register; "int8_mxu"
        additionally keeps the packs IN the traced program so encoder
        convs multiply int8×int8→int32 (quant/matmul.py).
        ``quant_act_scales`` (int8_mxu only): calibrated per-conv
        activation scales (quant/calibrate.conv_input_scales) baked
        into the packs at quantization time; None leaves every conv on
        the dynamic in-graph max-abs fallback."""
        if shape_bucket is not None and shape_bucket % divis_by:
            raise ValueError(f"shape_bucket={shape_bucket} must be a "
                             f"multiple of the model's /{divis_by} "
                             f"divisibility requirement")
        if max_cached_shapes < 1:
            raise ValueError(
                f"max_cached_shapes={max_cached_shapes} must be >= 1")
        # ``self.config`` stays the config AS GIVEN — consumers compare it
        # against their own (eval.validate.make_validation_fn re-creates the
        # runner on mismatch); the guard's flip lives in effective_config.
        self.config = config
        for option, value in (("exit_threshold_px", exit_threshold_px),
                              ("exit_min_iters", exit_min_iters),
                              ("quant", quant)):
            if value is not None:
                require_raft(config, option)
        if (exit_threshold_px is not None or exit_min_iters is not None
                or quant is not None):
            config = dataclasses.replace(
                config,
                exit_threshold_px=(config.exit_threshold_px
                                   if exit_threshold_px is None
                                   else exit_threshold_px),
                exit_min_iters=(config.exit_min_iters
                                if exit_min_iters is None
                                else exit_min_iters),
                quant=config.quant if quant is None else quant)
        self.effective_config = effective_inference_config(
            config, iters, corr_fp32_auto)
        self.early_exit = early_exit_enabled(self.effective_config)
        if self.effective_config.quant != "off":
            # Host-side, once per runner: int8 weights are what upload
            # and reside on device; disk checkpoints stay fp32.
            from raft_stereo_tpu.quant.core import (quantize_variables,
                                                    tree_is_quantized)
            if not tree_is_quantized(variables):
                variables = quantize_variables(
                    variables, self.effective_config,
                    act_scales=quant_act_scales)
        # Per-call trip-count accounting (early exit only): the CLIs print
        # it and tools/early_exit_report.py averages it per validator.
        self.last_iters_used: Optional[int] = None
        self._iters_used_sum = 0
        self._iters_used_calls = 0
        self.variables = variables
        self.iters = iters
        self.divis_by = shape_bucket or divis_by
        self.max_cached_shapes = max_cached_shapes
        if fetch_dtype not in (None, "fp16", "bf16"):
            raise ValueError(f"fetch_dtype={fetch_dtype!r}: use 'fp16', "
                             f"'bf16', or None (full fp32 fetch)")
        self.fetch_dtype = {None: None, "fp16": jnp.float16,
                            "bf16": jnp.bfloat16}[fetch_dtype]
        self.model = create_model(self.effective_config)
        self.cost_registry = cost_registry
        self.cost_site = cost_site
        self.donate_images = donate_images
        # Host phases of a call (telemetry/spans.py): ``infer.*`` events on
        # the profiler's clock, and ``infer_phase_seconds{phase=}`` where
        # the caller's cost registry brings a metrics registry.
        self.phases = Phases("infer.", RUNNER_PHASES,
                             getattr(cost_registry, "metrics", None),
                             "infer_phase_seconds")
        self._compiled: Dict[Tuple[int, int], any] = {}
        # (key, (left, right)): the host staging pair of the latest call
        self._staging: Tuple = (None, None)
        # Streaming (warm-start) programs live in their own small cache:
        # they carry an extra state output (and, warm, an extra input),
        # so they are distinct executables from the ``_compiled`` ones —
        # and keeping them apart leaves the sessionless cache, its cost
        # keys, and its eviction accounting byte-for-byte untouched.
        self._stream_compiled: Dict[Tuple, any] = {}

    def _cost_key(self, padded_hw: Tuple[int, int], batch: int) -> str:
        """Stable label of one compile point in the cost registry —
        what GET /debug/compiles lists and what the serving MFU path
        looks up (``compiled_cost``)."""
        return (f"{self.cost_site}.forward"
                f"({padded_hw[0]}x{padded_hw[1]},b{batch})")

    def compiled_cost(self, padded_hw: Tuple[int, int], batch: int = 1):
        """The cost record for a compiled (padded shape, batch)
        executable, or None (no registry / not compiled yet / analysis
        degraded)."""
        if self.cost_registry is None:
            return None
        return self.cost_registry.get(self._cost_key(padded_hw, batch))

    def _forward_for(self, padded_hw: Tuple[int, int], batch: int = 1):
        """One compiled program per (PADDED shape, batch) covering
        cast -> forward.

        Keyed by the padded shape so distinct raw shapes that pad to the
        same grid share one executable (real KITTI-2015 mixes 375x1242 /
        370x1224 / 376x1241 — all 384x1248 padded; a raw-shape key would
        compile each).  Padding happens on the HOST in NumPy and the crop
        of the result is its own tiny program (``_crop_flat``): this one
        never sees a raw shape."""
        key = (padded_hw, batch)
        if key not in self._compiled:
            while len(self._compiled) >= self.max_cached_shapes:
                # dicts iterate in insertion order -> drop the oldest
                evicted = next(iter(self._compiled))
                self._compiled.pop(evicted)
                log.info(
                    "compile cache full (max_cached_shapes=%d): evicting "
                    "oldest executable for padded shape %s batch %d — "
                    "its next use re-pays XLA compile time",
                    self.max_cached_shapes, evicted[0], evicted[1])
                if self.cost_registry is not None:
                    self.cost_registry.note_runner_eviction(
                        self._cost_key(*evicted), len(self._compiled))
            fwd = make_forward(self.model, self.iters, self.fetch_dtype,
                               donate_images=self.donate_images)
            if self.cost_registry is not None:
                # AOT-instrumented dispatch: first call lowers + compiles
                # through the registry (cost/memory analysis recorded),
                # later calls hit the cached executable (telemetry/costs).
                fwd = self.cost_registry.instrument(
                    fwd, key=self._cost_key(padded_hw, batch),
                    site=self.cost_site)
            self._compiled[key] = fwd
            if self.cost_registry is not None:
                self.cost_registry.note_runner_cache_size(
                    len(self._compiled))
        else:  # LRU refresh
            self._compiled[key] = self._compiled.pop(key)
        return self._compiled[key]

    # -------------------------------------------------- iters-used tracking
    def _note_iters_used(self, iters_used) -> int:
        used = int(iters_used)
        self.last_iters_used = used
        self._iters_used_sum += used
        self._iters_used_calls += 1
        return used

    def iters_used_mean(self) -> Optional[float]:
        """Mean GRU trip count over the calls since the last reset; None
        without early exit (the fixed path always runs ``iters``)."""
        if not self._iters_used_calls:
            return None
        return self._iters_used_sum / self._iters_used_calls

    def reset_iters_used(self) -> None:
        self.last_iters_used = None
        self._iters_used_sum = 0
        self._iters_used_calls = 0

    def __call__(self, image1: np.ndarray, image2: np.ndarray,
                 ) -> Tuple[np.ndarray, float]:
        """Returns ``(flow, seconds)`` — flow is (H, W) x-flow (=-disparity),
        seconds is the full per-image product path: host replicate pad,
        host->device copy in the caller's dtype (KITTI/eval images arrive
        uint8, a 4x smaller copy; the program casts on the device),
        forward, crop, and the host fetch of the result.

        The stop clock is the ``np.asarray`` fetch: the product path ends
        with the result on the host.  A first call at a new padded shape
        includes XLA
        compilation; the warmup discard absorbs it (``FpsProtocol``), the
        way the reference's 50-image discard absorbs cuDNN autotune
        (reference: evaluate_stereo.py:77-82)."""
        assert image1.ndim == 3 and image1.shape == image2.shape
        flows, elapsed = self._run_padded((image1,), (image2,))
        return flows[0], elapsed

    def run_batch(self, images1, images2) -> Tuple[np.ndarray, float]:
        """Batched product mode: N same-shape pairs through ONE compiled
        program — amortizes the per-image dispatch and transfer setup.  A
        small call is one upload, one launch and one fetch; a large
        fixed-depth call is launched as equal sub-batches of that one
        program, each one's fill, upload and fetch under another's execute
        (``_run_padded``, ``_sub_batches``), and every pair's answer is
        what its sub-batch alone would give.  The per-image ``__call__``
        remains the reference protocol (evaluate_stereo.py:60-109 is
        per-image by definition); this is the throughput surface.

        Args: ``images1``/``images2`` — sequences of (H, W, 3) images, all
        the same shape.  Returns ``(flows (N, H, W), seconds)``, the array
        the caller's own; the stop clock is the (last) result fetch, as in
        ``__call__``.
        """
        assert len(images1) == len(images2) and len(images1) > 0
        shape = np.asarray(images1[0]).shape
        assert all(np.asarray(im).shape == shape
                   for im in (*images1, *images2)), \
            "run_batch requires same-shape pairs; pad upstream or use " \
            "per-image calls for mixed shapes"
        return self._run_padded(images1, images2)

    def _run_padded(self, images1, images2) -> Tuple[np.ndarray, float]:
        """The product path of ``__call__`` and ``run_batch``, each step a
        host phase (``infer.stack_pad`` / ``upload`` / ``execute`` /
        ``fetch`` / ``unpad``, telemetry/spans.py).  Each input byte is
        written once, into a padded host pair that is kept for the next
        call of the same shape, batch and dtype (only the latest pair, so
        host memory does not grow with ``max_cached_shapes``); the result
        is cropped on the device and fetched at its own size, row-major, so
        ``unpad`` is left the reshape.

        A large fixed-depth call (``_sub_batches``) runs as ``chunks``
        equal sub-batches, contiguous row ranges of the one staging pair,
        through ONE compiled program of batch ``n / chunks``, pipelined on
        this thread by jax's asynchronous dispatch: sub-batch k is filled,
        uploaded and launched, its crop enqueued right behind it, and only
        then is sub-batch k-1 fetched, which waits for program k-1 alone
        and copies its piece into the call's new ``(n, H, W)`` result while
        program k runs.  The device is left to wait for the first
        sub-batch's fill and upload and for the last one's fetch.  In such
        a call ``execute`` is the launch and the wait for the program is in
        ``fetch``.  Every other call (``chunks`` 1) is one program, which
        ``execute`` waits for, and its result is the fetch's own memory,
        read-only.  Every span carries ``chunk`` and ``chunks``.

        The first ``infer.execute`` of a (padded shape, batch) also carries
        ``compiled=1`` and ``paths``, the choices
        ``kernels.corr_lookup.log_path_once`` was told while the program
        was traced (fnet sequential or batched, the lookup's launches,
        fused gates or Flax a level).  The seconds run from the first
        phase's start to the end of the last ``fetch``, as they always
        have.  A runner's calls do not interleave (no caller in the repo
        drives one runner from two threads): within a call no row of the
        pair is written twice, and the next call refills the pair after
        this one has waited for its last program (in the last ``fetch``,
        or in ``execute`` where there is one) and with it for every
        upload."""
        n = len(images1)
        images1 = [np.asarray(im) for im in images1]
        images2 = [np.asarray(im) for im in images2]
        h, w, c = images1[0].shape
        padder = InputPadder((1, h, w, c), divis_by=self.divis_by)
        l, r, t, b = padder.pads
        key = ((n, t + h + b, l + w + r, c),
               np.result_type(*{im.dtype for im in images1 + images2}))
        staged = 2 * int(np.prod(key[0])) * key[1].itemsize
        chunks = 1 if self.early_exit else _sub_batches(n, staged)
        m = n // chunks                                # pairs a sub-batch
        flows = None

        def phase(name: str, k: int, **attrs):
            return self.phases.phase(name, batch_size=n, chunk=k,
                                     chunks=chunks, **attrs)

        def fetch(k: int, crop, iters_used):
            nonlocal flows
            with phase("fetch", k) as fetched:
                if iters_used is not None:
                    self._note_iters_used(iters_used)
                if chunks == 1:
                    flows = piece = np.asarray(crop)
                    if flows.dtype != np.float32:  # half-precision fetch
                        flows = flows.astype(np.float32)
                else:
                    if flows is None:
                        flows = np.empty(n * h * w, np.float32)
                    rows = flows[k * m * h * w:(k + 1) * m * h * w]
                    # New memory costs a page fault a page on its first
                    # write: take them now, under the program this fetch
                    # is about to wait for, not in the copy behind it.
                    rows.fill(0)
                    piece = np.asarray(crop)
                    rows[:] = piece
                fetched.set(bytes=piece.nbytes)
            return fetched

        queued = None        # the sub-batch launched and not yet fetched
        for k in range(chunks):
            rows = slice(k * m, (k + 1) * m)
            with phase("stack_pad", k) as fill:
                reused = self._staging[0] == key
                if not reused:
                    self._staging = (None, None)    # free the old pair first
                    self._staging = (key, (np.empty(*key), np.empty(*key)))
                p1, p2 = (p[rows] for p in self._staging[1])
                _fill_edge_padded(p1, images1[rows], padder.pads)
                _fill_edge_padded(p2, images2[rows], padder.pads)
                fill.set(bytes=p1.nbytes + p2.nbytes, reused=reused)
                builds = (p1.shape[1:3], m) not in self._compiled
                fwd = self._forward_for(p1.shape[1:3], batch=m)
            if k == 0:
                first = fill
            with phase("upload", k, bytes=p1.nbytes + p2.nbytes):
                # returns at once; the launch waits for the copy
                d1, d2 = jnp.asarray(p1), jnp.asarray(p2)
            with phase("execute", k) as execute:
                said = path_choices() if builds else None
                out = fwd(self.variables, d1, d2)
                if chunks == 1:
                    out = jax.block_until_ready(out)
                if builds:
                    # this call traced and built the executable: the span
                    # says so, with the shape-driven choices the trace made
                    execute.set(compiled=1, paths="; ".join(
                        msg for msg, times in path_choices().items()
                        if times != said.get(msg, 0)))
                out, iters_used = out if self.early_exit else (out, None)
                # right behind its program and before the next one, or the
                # fetch of this sub-batch would wait for the next program
                crop = _crop_flat(out, pads=padder.pads)
            if queued is not None:
                fetch(k - 1, *queued)
            queued = (crop, iters_used)
        last = fetch(chunks - 1, *queued)
        with phase("unpad", chunks - 1):
            flows = flows.reshape(n, h, w)
        return flows, last.t_end - first.t_start

    # ------------------------------------------------------------- streaming
    def _stream_forward_for(self, padded_hw: Tuple[int, int], warm: bool,
                            hidden_in: bool = False,
                            hidden_out: bool = False):
        """The state-returning (and, warm, state-consuming) program for
        one padded shape — the sequence/demo twin of the serving engine's
        warm bucket executables.  Bounded like ``_compiled``.  The
        hidden flags select the round-19 warm-h program variants; both
        False keeps the exact round-14 programs (and cache keys)."""
        key = (padded_hw, warm, hidden_in, hidden_out)
        if key not in self._stream_compiled:
            while len(self._stream_compiled) >= self.max_cached_shapes:
                self._stream_compiled.pop(
                    next(iter(self._stream_compiled)))
            self._stream_compiled[key] = make_forward(
                self.model, self.iters, self.fetch_dtype,
                donate_images=self.donate_images,
                warm_start=warm, return_state=True,
                hidden_init=hidden_in, return_hidden=hidden_out)
        else:  # LRU refresh
            self._stream_compiled[key] = self._stream_compiled.pop(key)
        return self._stream_compiled[key]

    def run_stream(self, image1: np.ndarray, image2: np.ndarray,
                   prev_flow_low: Optional[np.ndarray] = None,
                   prev_hidden: Optional[object] = None,
                   carry_hidden: bool = False) -> StreamFrame:
        """One frame of a temporally ordered sequence: like ``__call__``
        but the GRU warm-starts from ``prev_flow_low`` (the previous
        frame's ``StreamFrame.flow_low``) and the returned frame carries
        the state to chain forward.  ``prev_flow_low=None`` (frame 0, or
        after a scene cut) runs the cold zero-init — the same math as the
        sessionless path (pinned bitwise by tests/test_sessions.py).

        With early exit configured (``exit_threshold_px``) a warm frame
        typically stalls after far fewer iterations than a cold one
        (no cell times it yet: ROADMAP R5).  A ``prev_flow_low`` whose
        shape does not match this frame's padded low-res grid raises:
        resolution changes are a caller-visible stream break, not
        something to resample over silently.

        ``carry_hidden=True`` asks for the frame's final GRU hidden
        states on the returned ``StreamFrame.hidden``; passing them back
        as ``prev_hidden`` (together with ``prev_flow_low``) runs the
        warm-h program — the GRU resumes its own trajectory instead of
        re-deriving it from the context encoder every frame (round 19;
        requires ``prev_flow_low``, the hidden state is meaningless
        without the disparity it evolved against).  Both default off:
        the round-14 programs and their cache keys are untouched."""
        require_raft(self.effective_config, "run_stream")
        assert image1.ndim == 3 and image1.shape == image2.shape
        t0 = time.perf_counter()
        padder = InputPadder((1,) + image1.shape, divis_by=self.divis_by)
        l, r, t, b = padder.pads
        spec = ((t, b), (l, r), (0, 0))
        p1 = np.pad(np.asarray(image1), spec, mode="edge")
        p2 = np.pad(np.asarray(image2), spec, mode="edge")
        f = self.effective_config.downsample_factor
        low_hw = (p1.shape[0] // f, p1.shape[1] // f)
        warm = prev_flow_low is not None
        if prev_hidden is not None and not warm:
            raise ValueError("prev_hidden needs prev_flow_low: the "
                             "hidden state is meaningless without the "
                             "disparity it evolved against")
        if warm and tuple(prev_flow_low.shape) != low_hw:
            raise ValueError(
                f"prev_flow_low shape {prev_flow_low.shape} does not "
                f"match this frame's padded low-res grid {low_hw} — the "
                f"stream changed resolution; restart with "
                f"prev_flow_low=None")
        hidden_in = prev_hidden is not None
        hidden_out = carry_hidden or hidden_in
        fwd = self._stream_forward_for(p1.shape[:2], warm,
                                       hidden_in=hidden_in,
                                       hidden_out=hidden_out)
        args = [self.variables, jnp.asarray(p1[None]), jnp.asarray(p2[None])]
        if warm:
            args.append(jnp.asarray(
                np.ascontiguousarray(prev_flow_low, dtype=np.float32)[None]))
        if hidden_in:
            args.append(tuple(jnp.asarray(np.asarray(h)[None])
                              for h in prev_hidden))
        out = fwd(*args)
        iters_used = None
        pos = 2
        if self.early_exit:
            flow_up, flow_low = out[0], out[1]
            iters_used = self._note_iters_used(out[2])
            pos = 3
        else:
            flow_up, flow_low = out[0], out[1]
        hidden = None
        if hidden_out:
            hidden = tuple(np.asarray(h)[0] for h in out[pos])
        flow_padded = np.asarray(flow_up)[0]
        state = np.ascontiguousarray(np.asarray(flow_low)[0],
                                     dtype=np.float32)
        flow = padder.unpad(flow_padded[None])[0]
        if flow.dtype != np.float32:               # half-precision fetch
            flow = flow.astype(np.float32)
        return StreamFrame(flow=np.ascontiguousarray(flow),
                           flow_low=state,
                           seconds=time.perf_counter() - t0,
                           iters_used=iters_used, warm=warm,
                           hidden=hidden)

    def disparity(self, image1: np.ndarray, image2: np.ndarray) -> np.ndarray:
        """Positive disparity map (the demo/user-facing convention,
        reference: demo.py:47-50 saves ``-flow_up``)."""
        flow, _ = self(image1, image2)
        return -flow
