"""Model / training configuration.

The reference duplicates architecture flags across three argparse entry points
(reference: train_stereo.py:233-240, evaluate_stereo.py:193-208, demo.py:54-72)
and a checkpoint can silently mismatch them.  Here the architecture lives in a
single frozen dataclass that is serialized alongside every checkpoint, so a
checkpoint is self-describing.

Convention note (documented per SURVEY.md §2 "default-dependent quirks"): the
reference indexes ``hidden_dims`` coarse→fine in the update block but fine→coarse
in ``context_zqr_convs`` — invisible because all dims equal 128.  We pick ONE
convention: ``hidden_dims[0]`` is the FINEST level (1/2^n_downsample resolution),
``hidden_dims[-1]`` the coarsest.  The torch-checkpoint importer handles the
reordering.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional, Tuple, Union

CORR_BACKENDS = ("reg", "alt", "reg_fused")

# Reference CLI --corr_implementation values → our backends
# (reference: core/raft_stereo.py:90-100; "alt_cuda" is dead code there).
_REFERENCE_CORR_ALIASES = {
    "reg": "reg",
    "alt": "alt",
    "reg_cuda": "reg_fused",
    "alt_cuda": "alt",
}


@dataclasses.dataclass(frozen=True)
class RaftStereoConfig:
    """Architecture of one RAFT-Stereo model (reference: core/raft_stereo.py:22-44)."""

    # Per-GRU-level hidden state channels, FINE → COARSE
    # (level 0 = 1/2^n_downsample resolution).
    hidden_dims: Tuple[int, ...] = (128, 128, 128)
    # Context dims are aliased to hidden dims in the reference
    # (core/raft_stereo.py:27); we keep them separate but default-equal.
    context_dims: Optional[Tuple[int, ...]] = None
    n_gru_layers: int = 3
    n_downsample: int = 2          # features at 1/2^n_downsample resolution
    corr_levels: int = 4
    corr_radius: int = 4
    # One of CORR_BACKENDS.  TPU-first default is the Pallas fused lookup —
    # measured 5.3-5.4x over the XLA gather lookup at KITTI resolution on one
    # chip for both the 32-iter accuracy model and the realtime model, with
    # bit-level agreement vs 'reg' in fp32 (under mixed precision reg_fused
    # stores the pyramid in bf16, a deliberate memory/precision trade the
    # reference's fp16 CUDA path also makes).  'reg' stays the pure-XLA
    # correctness reference and the off-TPU fallback.
    corr_backend: str = "reg_fused"
    shared_backbone: bool = False  # fnet shares the cnet trunk (core/raft_stereo.py:34-39)
    slow_fast_gru: bool = False    # extra coarse-GRU-only updates per iter
    mixed_precision: bool = False  # bf16 compute for encoders + update block
    # Force fp32 features into the correlation backend even under mixed
    # precision.  The reference forces fp32 for reg/alt (core/raft_stereo.py:
    # 92,95) but runs its CUDA lookup in fp16; our fused kernels likewise keep
    # the compute dtype by default (~1e-2 corr drift in bf16).  Set True to
    # reproduce the reference's fp32 correlation numerics exactly while still
    # running everything else in bf16.
    corr_fp32: bool = False
    context_norm: str = "batch"    # cnet norm (reference uses frozen batch norm)
    fnet_norm: str = "instance"
    fnet_dim: int = 256
    # Fused ConvGRU gate kernel (kernels/gru_fused.py): compute both gate
    # convolutions (convzr, convq) and the r-gate coupling of every GRU
    # level in one Pallas launch per level, keeping the gate intermediates
    # in VMEM — the refinement loop is most of the device's busy time in
    # every cell of the benchmark (PERF.md section 5), and this collapses
    # its ~10 XLA dispatches per level to 1 kernel + 1 fused pointwise tail.
    #   "auto" (default): use the kernel when the backend supports it and
    #     the level's working set fits VMEM; silently fall back to the Flax
    #     conv path otherwise (CPU/GPU, very wide levels).
    #   "on": force the kernel; raises when it cannot run.
    #   "off": always the Flax conv path (bitwise-identical to the
    #     pre-kernel graph; guarded by tests/test_gru_fused.py).
    # Parameters are shared with the Flax path (same pytree), so the flag
    # is a pure execution choice — checkpoints are unaffected.
    fused_gru: str = "auto"
    # Rematerialize the GRU scan body in the backward pass (train mode only;
    # ``jax.checkpoint``).  Training stores per-iteration activations of
    # every conv in the update block otherwise — ~0.6 GB x train_iters at the
    # reference's SceneFlow config (batch 8, 320x720), which overflows a
    # single 16 GB chip.  With remat only the scan carries persist and the
    # backward recomputes each iteration's internals (~1/3 more FLOPs for
    # ~10x less activation memory).  Turn off when per-device batch is small
    # enough (e.g. data-parallel over many chips) to trade memory for speed.
    remat_gru: bool = True
    # Named intermediates the remat policy SAVES instead of recomputing in
    # the backward pass (jax save_only_these_names).  Available names:
    # "corr_lookup" (the Pallas lookup output, ~2 MB/iter — saves a kernel
    # launch per backward iteration, measured -7.4% step time, round 3),
    # "gru_gates" (pre-activation convzr/convq outputs of every ConvGRU
    # level, ~110 MB/iter at the SceneFlow config), "motion_features"
    # (BasicMotionEncoder output, ~30 MB/iter).  Each trades HBM for
    # skipped recompute; the combinations are not re-measured on the v5e
    # (no training cell yet: PERF.md section 7, 0a).
    remat_save: Tuple[str, ...] = ("corr_lookup",)
    # Stream the encoders' FULL-RESOLUTION stages in horizontal bands
    # (models/banded.py): only band-sized activations exist, cutting peak
    # HBM several-fold at Middlebury-F-class resolutions in exchange for
    # ~3.5x the (cheap) stem FLOPs when instance norm needs global-stats
    # sweeps.  Opt-in; supported for n_downsample=2 with
    # instance/batch/none norms (the published configurations).
    banded_encoder: bool = False
    # Extension beyond the reference: shard the W2 (disparity-search) axis of
    # the correlation volume across a mesh axis for full-res inputs.
    corr_w2_shards: int = 1
    # Extension beyond the reference: shard the IMAGE-ROW axis of the
    # encoders' full-resolution segment across a mesh axis (context
    # parallelism — parallel/rows_sharded.py): each device holds 1/N of the
    # full-res stem activations.  Training: the train loop auto-wires a
    # dedicated ``rows`` mesh axis composing with data/corr (gradients flow
    # through the ppermute halos and gathered instance-norm moments —
    # tests/test_rows_sharded.py training-parity test); image height must
    # be divisible by 4*rows_shards.  Inference/eval: trace the forward
    # under ``parallel.rows_sharded.rows_sharding(mesh)``.  Supported for
    # the same trunks as banded_encoder (n_downsample=2,
    # instance/batch/none norms); incompatible with banded_encoder (pick
    # streaming OR sharding for the segment).
    rows_shards: int = 1
    # Extend row sharding through the WHOLE refinement loop — correlation
    # volume, per-iteration multilevel ConvGRU updates, convex upsampling
    # (parallel/rows_gru.py: clamped extended windows, per-iteration
    # ppermute halo refresh, window-restricted align-corners interp).  The
    # O(H) heavyweights (full-res stem activations, corr volume, train-scan
    # carries) stay sharded end to end; the static fine-level
    # feature/context maps are replicated per device at the executor
    # boundary (a deliberate sharding pin, see parallel/rows_gru.py).  This
    # is what lets full-resolution TRAINING scale across chips: the train
    # scan's per-iteration carries are O(H) and exceed one chip at
    # Middlebury-F-class frames.  Requires rows_shards > 1 (the mesh axis),
    # corr_w2_shards == 1, and fine-level height divisible by
    # 4 * rows_shards with H/(2^n_downsample * rows_shards) >= 2 * halo.
    rows_gru: bool = False
    # Fine-level halo rows for rows_gru window exchange; None = derive from
    # the architecture's per-iteration row receptive field
    # (parallel/rows_gru.default_gru_halo: 16, or 32 for 3-level
    # slow_fast_gru).  Must be a multiple of 4.  Smaller halos trade
    # exactness for less overlap compute — parity holds only when the halo
    # covers the receptive field.
    rows_gru_halo: Optional[int] = None
    # Pixel count above which fnet processes the two images sequentially
    # instead of as one batch-2 concat (halves the full-resolution stem's
    # peak HBM).  None = derive from the local device's HBM at trace time
    # (models/raft_stereo.sequential_fnet_threshold — measured stem
    # bytes/pixel); 0 forces always-sequential, a huge value forces
    # always-batched.
    sequential_fnet_pixels: Optional[int] = None
    # Row height of the banded encoder's streaming bands (banded_encoder
    # only).  None = derive from device HBM and image width at trace time
    # (models/banded.default_band_rows); must be even (stride-2 alignment).
    band_rows: Optional[int] = None
    # --- Adaptive GRU early exit (test-mode inference only) -------------
    # The GRU refinement loop is most of the device's busy time
    # (PERF.md section 5) and the paper's iterative-refinement
    # framing makes every intermediate disparity a valid output, so the
    # test-mode loop can stop once the update stalls.  When
    # ``exit_threshold_px > 0`` the fixed-depth ``lax.scan`` becomes a
    # convergence-gated ``lax.while_loop``: each iteration computes the
    # per-image mean |Δdisparity| (px at 1/2^n_downsample resolution — the
    # same quantity TrainConfig.gru_telemetry measures) and the loop exits
    # once the WORST batch member (max over the batch axis, so one
    # executable serves the whole bucket) falls below the threshold,
    # subject to the min/max bounds below.  The forward then returns an
    # extra ``iters_used`` scalar.  <= 0 (default) keeps today's scan
    # program bitwise-unchanged.  Train mode and unroll_gru ignore it.
    exit_threshold_px: float = 0.0
    # Iterations that always run before the threshold may fire (a
    # too-early exit sees the large first updates as "converged-from-
    # zero"); clamped to the effective depth.
    exit_min_iters: int = 1
    # Hard cap on the loop depth; None = the caller's ``iters`` argument.
    exit_max_iters: Optional[int] = None
    # --- Post-training int8 inference tier (quant/, inference only) -----
    # "int8": encoder conv weights ship int8 with per-output-channel
    # scales and dequantize in-register inside the jitted program
    # (quant/core.py; params on disk stay fp32 — the runner/engine
    # quantize at load), and the correlation pyramid stores int8 with
    # per-level scales read by the extended Pallas lookup kernels
    # (models/corr.py).  The memory-bound halves of the per-frame cost
    # (the lookups: PERF.md section 3) move 1/4 (vs fp32) or 1/2 (vs
    # bf16) of the bytes.  "int8_mxu": the compute-path extension
    # (quant/matmul.py) — encoder convs MULTIPLY int8×int8 and
    # accumulate int32 on the MXU (activations quantized in-graph with
    # calibrated static scales, dynamic max-abs fallback), rescaling to
    # fp32 once per conv AFTER accumulation; the bytes win becomes a
    # flops win.  "off" (default) compiles the EXACT pre-quant
    # program — bitwise-identical, pinned by tests/test_quant.py.
    # Accuracy is gated by the measured in-distribution drift
    # (tools/quant_drift.py), the tools/bf16_drift.py
    # methodology extended down.  Inference-only: the training CLIs
    # never set it, and the quantized corr path runs under
    # stop_gradient.
    quant: str = "off"
    # Also store the correlation pyramid int8 when quant != "off"
    # (False: weights-only quantization — the ablation knob the drift
    # tool measures both sides of).
    quant_corr: bool = True
    # Calibrated per-level int8 scales for the correlation pyramid
    # (quant/calibrate.py corr_scales; percentile-clipped on
    # in-distribution pairs).  None = dynamic per-level max-abs scales
    # computed in-graph (shape-generic, no file dependency, one extra
    # reduction per level per forward).
    quant_corr_scales: Optional[Tuple[float, ...]] = None
    # Store the quantized correlation entries float8_e4m3 instead of
    # int8 on hardware that has it (kernels/corr_lookup.py
    # fp8_corr_available — same 1-byte itemsize, a float grid that is
    # denser near zero).  Capability-gated at trace: where fp8 is
    # unavailable the pyramid quantizes int8 exactly as before (the
    # transparent-fallback family contract), so the knob is safe to
    # leave on in shared configs.
    quant_corr_fp8: bool = False

    def __post_init__(self):
        if self.context_dims is None:
            object.__setattr__(self, "context_dims", tuple(self.hidden_dims))
        object.__setattr__(self, "hidden_dims", tuple(self.hidden_dims))
        object.__setattr__(self, "context_dims", tuple(self.context_dims))
        if self.corr_backend not in CORR_BACKENDS:
            alias = _REFERENCE_CORR_ALIASES.get(self.corr_backend)
            if alias is None:
                raise ValueError(
                    f"corr_backend={self.corr_backend!r} not in {CORR_BACKENDS}")
            object.__setattr__(self, "corr_backend", alias)
        if not (1 <= self.n_gru_layers <= min(len(self.hidden_dims), 3)):
            raise ValueError(
                "n_gru_layers must be in [1, min(len(hidden_dims), 3)] — the "
                "update block implements at most 3 GRU levels")
        if self.band_rows is not None and (self.band_rows < 2
                                           or self.band_rows % 2):
            raise ValueError(
                f"band_rows={self.band_rows} must be an even integer >= 2 "
                f"(stride-2 alignment of the banded encoder)")
        if self.rows_shards > 1 and self.banded_encoder:
            raise ValueError(
                "rows_shards and banded_encoder both replace the "
                "full-resolution segment's executor — enable at most one")
        if self.fused_gru not in ("auto", "on", "off"):
            raise ValueError(
                f"fused_gru={self.fused_gru!r} not in ('auto', 'on', 'off')")
        object.__setattr__(self, "remat_save", tuple(self.remat_save))
        known_saves = {"corr_lookup", "gru_gates", "motion_features"}
        unknown = set(self.remat_save) - known_saves
        if unknown:
            raise ValueError(f"remat_save names {sorted(unknown)} unknown; "
                             f"choose from {sorted(known_saves)}")
        if self.rows_gru:
            if self.rows_shards <= 1:
                raise ValueError(
                    "rows_gru extends rows_shards' context parallelism "
                    "through the GRU loop — set rows_shards > 1")
            if self.corr_w2_shards > 1:
                raise ValueError(
                    "rows_gru and corr_w2_shards>1 both reshard the "
                    "correlation volume; the combination is unsupported — "
                    "pick row sharding OR disparity-axis sharding")
        if self.rows_gru_halo is not None and (self.rows_gru_halo < 8
                                               or self.rows_gru_halo % 4):
            raise ValueError(
                f"rows_gru_halo={self.rows_gru_halo} must be a multiple of "
                f"4, >= 8 (GRU pyramid alignment; see "
                f"parallel/rows_gru.default_gru_halo)")
        if self.exit_min_iters < 1:
            raise ValueError(
                f"exit_min_iters={self.exit_min_iters} must be >= 1")
        if (self.exit_max_iters is not None
                and self.exit_max_iters < self.exit_min_iters):
            raise ValueError(
                f"exit_max_iters={self.exit_max_iters} must be >= "
                f"exit_min_iters={self.exit_min_iters}")
        if self.exit_threshold_px > 0 and self.rows_gru:
            raise ValueError(
                "exit_threshold_px > 0 (adaptive early exit) is "
                "unsupported with rows_gru: the row-sharded loop executor "
                "runs a fixed-depth program (parallel/rows_gru.py)")
        if self.corr_w2_shards > 1 and self.corr_backend == "alt":
            raise ValueError(
                f"corr_w2_shards={self.corr_w2_shards} shards the 'reg' "
                f"volume and is incompatible with corr_backend='alt' (which "
                f"builds no volume) — use 'reg' or 'reg_fused'")
        if self.quant not in ("off", "int8", "int8_mxu"):
            raise ValueError(
                f"quant={self.quant!r} not in "
                f"('off', 'int8', 'int8_mxu')")
        if self.quant != "off":
            for field, why in (
                    ("rows_shards", self.rows_shards > 1),
                    ("rows_gru", self.rows_gru),
                    ("corr_w2_shards", self.corr_w2_shards > 1),
                    ("banded_encoder", self.banded_encoder)):
                if why:
                    raise ValueError(
                        f"quant={self.quant!r} is unsupported with "
                        f"{field}: the sharded/banded executors run "
                        f"their own full-precision paths — quantize the "
                        f"single-chip serving configs")
        if self.quant_corr_scales is not None:
            object.__setattr__(self, "quant_corr_scales",
                               tuple(float(s)
                                     for s in self.quant_corr_scales))
            if len(self.quant_corr_scales) != self.corr_levels:
                raise ValueError(
                    f"quant_corr_scales has "
                    f"{len(self.quant_corr_scales)} entries for "
                    f"corr_levels={self.corr_levels} — recalibrate "
                    f"(quant/calibrate.py) for this architecture")
            if any(s <= 0 for s in self.quant_corr_scales):
                raise ValueError(
                    f"quant_corr_scales={self.quant_corr_scales} must "
                    f"be positive")

    # ------------------------------------------------------------------ sizes
    @property
    def downsample_factor(self) -> int:
        return 2 ** self.n_downsample

    @property
    def corr_channels(self) -> int:
        """Channels of one correlation lookup (reference: core/update.py:69)."""
        return self.corr_levels * (2 * self.corr_radius + 1)

    @property
    def mask_channels(self) -> int:
        """Convex-upsample mask channels (reference: core/update.py:108-113)."""
        return 9 * self.downsample_factor ** 2

    # -------------------------------------------------------------- serialize
    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "RaftStereoConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "RaftStereoConfig":
        return cls.from_dict(json.loads(s))

    # ---------------------------------------------------------------- presets
    @classmethod
    def default(cls) -> "RaftStereoConfig":
        """The published middlebury/eth3d/sceneflow architecture."""
        return cls()

    @classmethod
    def realtime(cls) -> "RaftStereoConfig":
        """The realtime config (reference: README.md:84 uses reg_cuda there).

        On TPU the fused no-volume 'alt' kernel is the chosen backend:
        the correlation volume never exists in HBM (tiles are computed in
        VMEM), freeing memory for larger batches/resolutions, and it is
        the path the benchmark's realtime cell times (PERF.md section 4);
        reg_fused has not been timed against it on the v5e (ROADMAP D3)."""
        return cls(shared_backbone=True, n_downsample=3, n_gru_layers=2,
                   slow_fast_gru=True, corr_backend="alt",
                   mixed_precision=True)


# ------------------------------------------------------------ request tiers
@dataclasses.dataclass(frozen=True)
class RequestTier:
    """A named accuracy/latency point on the early-exit knob.

    A tier is a preset of (exit_threshold_px, min_iters, quant): the
    serving engine compiles one executable family per tier
    (serving/engine.py), the HTTP front door selects one per request, and
    the CLIs accept the raw knobs directly.  ``exit_threshold_px <= 0``
    means the tier runs the fixed-depth scan program (full quality,
    bitwise-identical to the pre-early-exit path).  ``quant="int8"``
    additionally runs the tier on the post-training int8 path
    (``RaftStereoConfig.quant``; the engine feeds such tiers the
    quantized variable tree and keys their executables separately in
    both the compile-cost registry and the persistent disk cache)."""

    name: str
    exit_threshold_px: float
    min_iters: int = 1
    quant: str = "off"

    def apply(self, cfg: RaftStereoConfig) -> RaftStereoConfig:
        """The model config this tier's requests compile: the base
        architecture with the early-exit + quantization knobs swapped
        in.  A tier that changes nothing maps back to the base config
        exactly, which is how the engine detects shareable executables."""
        return dataclasses.replace(
            cfg, exit_threshold_px=self.exit_threshold_px,
            exit_min_iters=self.min_iters, exit_max_iters=None,
            quant=self.quant)


# Threshold units are px of mean |Δdisparity| per iteration at feature
# resolution.  Defaults sit on the measured convergence curve
# (train_gru_delta_px telemetry; swept on the four validators by
# tools/early_exit_report.py, not timed on the v5e): "interactive" trades
# ~hundredths of a px of EPE for the biggest latency cut, "balanced"
# stops once updates are metric-noise, "quality" is the reference
# fixed-depth program.  "turbo" is the quantized tier (v2 since r22):
# interactive's exit knobs on the int8 COMPUTE path ("int8_mxu" —
# int8×int8→int32 encoder convs + int8 correlation pyramid,
# quant/matmul.py) — the bottom rung of the brownout cost ladder, gated
# by the measured drift (tools/quant_drift.py).
# The r15 weights-only path stays addressable as an inline
# "name:threshold:min:int8" spec.
REQUEST_TIERS: Dict[str, RequestTier] = {
    "interactive": RequestTier("interactive", exit_threshold_px=0.05,
                               min_iters=2),
    "balanced": RequestTier("balanced", exit_threshold_px=0.01,
                            min_iters=3),
    "quality": RequestTier("quality", exit_threshold_px=0.0, min_iters=1),
    "turbo": RequestTier("turbo", exit_threshold_px=0.05, min_iters=2,
                         quant="int8_mxu"),
}


def parse_tier(spec: Union[str, RequestTier]) -> RequestTier:
    """A tier from a preset name or an inline
    ``name:threshold[:min[:quant]]`` spec — ``"interactive"`` uses the
    preset, ``"fast:0.1:2"`` defines an ad-hoc tier, and
    ``"fast8:0.1:2:int8"`` puts it on the int8 path (smoke
    harnesses pin exact knobs this way)."""
    if isinstance(spec, RequestTier):
        return spec
    parts = str(spec).split(":")
    if len(parts) == 1:
        tier = REQUEST_TIERS.get(parts[0])
        if tier is None:
            raise ValueError(
                f"unknown tier {parts[0]!r}: use one of "
                f"{sorted(REQUEST_TIERS)} or an inline "
                f"'name:threshold_px[:min_iters[:quant]]' spec")
        return tier
    if len(parts) not in (2, 3, 4) or not parts[0]:
        raise ValueError(f"tier spec {spec!r}: expected "
                         f"'name:threshold_px[:min_iters[:quant]]'")
    try:
        threshold = float(parts[1])
        min_iters = int(parts[2]) if len(parts) >= 3 else 1
    except ValueError as e:
        raise ValueError(f"tier spec {spec!r}: expected "
                         f"'name:threshold_px[:min_iters[:quant]]'") from e
    quant = parts[3] if len(parts) == 4 else "off"
    if quant not in ("off", "int8", "int8_mxu"):
        raise ValueError(f"tier spec {spec!r}: quant {quant!r} not in "
                         f"('off', 'int8', 'int8_mxu')")
    return RequestTier(parts[0], exit_threshold_px=threshold,
                       min_iters=min_iters, quant=quant)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training hyper-parameters (reference: train_stereo.py:221-247)."""

    batch_size: int = 8
    train_iters: int = 22          # GRU iterations during training
    valid_iters: int = 32          # GRU iterations at validation
    lr: float = 2e-4
    num_steps: int = 200_000
    wdecay: float = 1e-5
    epsilon: float = 1e-8
    clip_grad_norm: float = 1.0
    image_size: Tuple[int, int] = (320, 720)
    train_datasets: Tuple[str, ...] = ("sceneflow",)
    # Sequence-loss schedule (reference: train_stereo.py:52-54)
    loss_gamma: float = 0.9
    max_flow: float = 700.0
    # Augmentation (reference: train_stereo.py:243-247)
    img_gamma: Optional[Tuple[float, float]] = None
    saturation_range: Optional[Tuple[float, float]] = None
    do_flip: Optional[str] = None  # None | "h" | "v"
    spatial_scale: Tuple[float, float] = (-0.2, 0.4)
    noyjitter: bool = False
    # Move photometric jitter (ColorJitter + gamma) from the host loader
    # into the jitted train step (data/device_jitter.py).  On a host with
    # few cores the jitter dominates the per-sample CPU budget (~63 of
    # 80 ms/sample measured at SceneFlow frames) while the chip absorbs the
    # same elementwise work in milliseconds.  Distribution-equivalent, not
    # bit-equal, to host jitter (it runs after the crop and skips uint8
    # rounding between ops); the host path stays the default.
    device_photometric: bool = False
    # Compact host->device batch upload: flow ships fp16 and valid ships
    # uint8 (lossless {0,1} mask), cast back to f32 on device.  fp16 GT
    # rounding grows with magnitude: ulp is 0.125 px for |d| in [128, 256)
    # but the loss mask admits |flow| up to max_flow=700 and SceneFlow GT
    # regularly exceeds 256 px, so the honest worst case below 1024 px is
    # 0.5 px (ulp at |d| in [512, 1024); mean rounding error ~ulp/4).
    # Still below the loss's useful signal at those disparities — the
    # per-pixel L1 terms there are dominated by multi-px prediction error —
    # but 4x larger than this comment's original 0.125 px claim.  At the
    # published config this cuts the per-step upload 25.8 -> 15.7 MB.
    # Deterministic (fp16 rounding is a pure function); exact resume stays
    # bit-identical.  False = upload GT uncompressed.
    compact_upload: bool = True
    # GRU convergence telemetry (telemetry/train_metrics.py): the step also
    # returns per-iteration mean |disparity update| magnitudes, so the
    # observed convergence curve — not the paper's fixed 7/32 — drives
    # iteration-count choices.  The (train_iters-1,) vector rides the
    # existing buffered metric fetch (no extra device sync); off by default
    # because it adds a small on-device reduction per iteration.
    gru_telemetry: bool = False
    # Fraction of train steps whose span tree is recorded
    # (telemetry/spans.py: step root with data-wait / dispatch / drain /
    # checkpoint children, exported as Chrome trace JSON via GET
    # /debug/spans).  0.0 (default) disables tracing; the spans are
    # reconstructed from timings the loop already clocks, so even 1.0 adds
    # no extra clock reads or device fetches to the hot loop.
    trace_sample_rate: float = 0.0
    # Runtime
    validation_frequency: int = 10_000
    seed: int = 1234
    # Parallelism: devices along the data axis; 0 = all available.
    data_parallel: int = 0
    # --- Divergence-proof training (round 20, training/anomaly.py) ---
    # Master switch for the anomaly policy: the jitted step gains an
    # on-device skip gate (non-finite loss/grads — and loss spikes when
    # anomaly_spike_factor > 0 — leave params/optimizer/step untouched,
    # flagged through the buffered metric drain, zero extra host syncs)
    # and the loop rewinds to the newest GOOD checkpoint after
    # anomaly_rewind_after CONSECUTIVE dropped steps, reshuffling the
    # remaining epoch order so the poison batch is not replayed.  Off
    # (default) keeps the step program and loop byte-identical to the
    # pre-round-20 path.
    anomaly_policy: bool = False
    # Drop a finite loss above spike_factor x the device-side loss EWMA
    # (0 = non-finite only).  The EWMA is threaded through the step like
    # the train state and checkpointed, so resume keeps the baseline.
    anomaly_spike_factor: float = 0.0
    anomaly_ewma_beta: float = 0.98
    # Consecutive dropped steps that trigger a checkpoint rewind
    # (0 = skip-only, never rewind).
    anomaly_rewind_after: int = 3
    # Rewinds allowed before the run fails typed (TrainingDiverged).
    anomaly_max_rewinds: int = 2
    # Keep-last-K retention for periodic <step>_<name> checkpoints
    # (0 = keep all).  The newest GOOD-stamped checkpoint is never
    # pruned — it is the rewind target.
    checkpoint_keep: int = 0

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TrainConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        d = dict(d)
        for k in ("image_size", "train_datasets", "img_gamma",
                  "saturation_range", "spatial_scale"):
            if k in d and isinstance(d[k], list):
                d[k] = tuple(d[k])
        return cls(**{k: v for k, v in d.items() if k in known})
