"""RAFT-Stereo top-level model (reference: core/raft_stereo.py).

TPU-first re-design:
* The GRU refinement loop is a ``jax.lax.scan`` — one compiled, weight-tied
  step instead of the reference's Python loop (core/raft_stereo.py:108-136).
  Per-iteration upsampled predictions fall out as scan ys for the sequence
  loss; in test mode the scan carries only state and upsampling happens once.
* Disparity state is a single x-channel field (the reference carries a full
  2-channel coordinate grid and zeroes the y update every iteration —
  core/raft_stereo.py:120).  A zero y-channel is materialized only for the
  motion encoder's 2-channel flow input (checkpoint compatibility).
* Mixed precision = bf16 compute dtype on encoders + update block, with the
  correlation volume in fp32 for reg/alt, mirroring the reference's autocast
  boundaries (core/raft_stereo.py:77,90-99,112).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from raft_stereo_tpu.config import RaftStereoConfig
from raft_stereo_tpu.models.corr import make_corr_fn
from raft_stereo_tpu.models.extractor import (BasicEncoder, MultiBasicEncoder,
                                              ResidualBlock, conv)
from raft_stereo_tpu.models.update import BasicMultiUpdateBlock
from raft_stereo_tpu.ops.grids import coords_grid_x
from raft_stereo_tpu.ops.upsample import convex_upsample
from raft_stereo_tpu.profiling import annotate

# Extra peak-HBM bytes PER PIXEL the batch-2 fnet concat costs over the
# sequential path when the stem runs at full resolution (n_downsample<=2):
# XLA holds both images' full-resolution stem working sets live at once.
# Measured in float32 on an earlier runtime, from the compiled programs'
# memory analysis: 1190 / 1179 / 1166 B/px at 544x960 / 1088x1984
# / 1984x2880.  Read again on the TPU v5e in bfloat16 (PERF.md section 6,
# PR 28, one 1984x2880 pair a call, 32 iterations): the batched path
# reserves 10.75e9 B at its peak against the sequential path's 5.98e9 B,
# 836 B/px, and a call takes 2.005 s against 2.003 s.  So the sequential
# path costs no time at that size and the gate is a memory decision alone.
_STEM_EXTRA_BYTES_PER_PIXEL = 1180
# Fraction of device HBM the batched path's EXTRA working set may occupy
# before the sequential path is chosen.  With the constant above and the
# 16.9e9 B a v5e reports this lands the threshold at 1,432,994 pixels of ONE
# image, whatever the batch: KITTI and SceneFlow shapes stay batched at every
# batch size the serving ladder has, Middlebury-F-class frames go sequential.
_SEQ_FNET_HBM_FRACTION = 0.10

# Confidence-map scale (px at feature resolution): the per-pixel
# convergence score (final |Δdisparity| + half the trajectory EWMA) maps
# to confidence as exp(-score/scale), so a pixel whose update magnitude
# settled at the scale reads ~0.37 and a fully-settled pixel reads ~1.0.
# Sized to the early-exit band the repo already operates in
# (config.REQUEST_TIERS: thresholds 0.01..0.05 px MEAN |Δ| — individual
# unconverged pixels sit orders of magnitude above that).
CONFIDENCE_SCALE_PX = 0.25
# Trajectory-decay EWMA weight: how much of the per-pixel update history
# survives each iteration.  0.8 remembers roughly the last five updates —
# enough to distinguish "just went quiet" from "has been quiet".
CONFIDENCE_EWMA_DECAY = 0.8


def sequential_fnet_threshold(cfg: RaftStereoConfig) -> int:
    """Pixel count of one image (H x W; the batch is not counted) from
    which fnet runs the two images sequentially.

    ``cfg.sequential_fnet_pixels`` overrides; otherwise derived from the
    device's HBM so bigger chips keep the batched path longer and smaller
    chips fall back sooner: threshold = fraction * HBM / extra
    bytes-per-pixel (1,432,994 on a v5e).  On the v5e at 1984x2880 the
    sequential path costs no time (2.003 s a call against 2.005 s batched)
    and reserves 4.8e9 B less, so the gate is a memory decision alone;
    whether that holds at the KITTI sizes below the threshold has not been
    read on this chip."""
    if cfg.sequential_fnet_pixels is not None:
        return cfg.sequential_fnet_pixels
    from raft_stereo_tpu.profiling import device_hbm_bytes
    return int(_SEQ_FNET_HBM_FRACTION * device_hbm_bytes()
               / _STEM_EXTRA_BYTES_PER_PIXEL)


def _fnet_sequential(cfg: RaftStereoConfig, shape, custom_trunk: bool,
                     record: bool) -> bool:
    """Whether fnet scans the two images one after the other (a custom
    trunk always does; else ``sequential_fnet_threshold``, a test on one
    image's H x W whatever the batch), with the choice recorded as the
    kernels record theirs."""
    from raft_stereo_tpu.kernels.corr_lookup import log_path_once

    _, h, w, _ = shape
    if custom_trunk:
        sequential, why = True, "a custom trunk streams or shards it"
    else:
        threshold = sequential_fnet_threshold(cfg)
        sequential = h * w >= threshold
        why = (f"{h * w} px an image {'>=' if sequential else '<'} "
               f"{threshold}")
    if record:
        log_path_once(f"fnet {h}x{w}: "
                      f"{'sequential' if sequential else 'batched'} ({why})")
    return sequential


class RAFTStereo(nn.Module):
    config: RaftStereoConfig

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.config.mixed_precision else jnp.float32

    def setup(self):
        cfg = self.config
        dtype = self.compute_dtype
        self.cnet = MultiBasicEncoder(
            output_dims=(cfg.hidden_dims, cfg.context_dims),
            norm_fn=cfg.context_norm, downsample=cfg.n_downsample,
            num_layers=cfg.n_gru_layers, dual_inp=cfg.shared_backbone,
            dtype=dtype, name="cnet")
        self.update_block = BasicMultiUpdateBlock(cfg, dtype=dtype,
                                                  name="update_block")
        # Per-level 3×3 convs producing the GRU context biases once per forward
        # (reference: core/raft_stereo.py:32,87-88).
        self.context_zqr_convs = [
            conv(cfg.hidden_dims[l] * 3, 3, 1, dtype=dtype,
                 name=f"context_zqr_conv{l}")
            for l in range(cfg.n_gru_layers)]
        if cfg.shared_backbone:
            self.conv2_res = ResidualBlock(128, "instance", 1, dtype=dtype,
                                           name="conv2_res")
            self.conv2_out = conv(cfg.fnet_dim, 3, 1, dtype=dtype,
                                  name="conv2_out")
        else:
            self.fnet = BasicEncoder(output_dim=cfg.fnet_dim,
                                     norm_fn=cfg.fnet_norm,
                                     downsample=cfg.n_downsample,
                                     dtype=dtype, name="fnet")

    def __call__(self, image1: jnp.ndarray, image2: jnp.ndarray,
                 iters: int = 12, flow_init: Optional[jnp.ndarray] = None,
                 test_mode: bool = False, unroll_gru: bool = False,
                 ctx_init=None, return_ctx: bool = False,
                 hidden_init=None, return_hidden: bool = False,
                 return_confidence: bool = False):
        """Estimate disparity for a rectified stereo pair.

        Args:
          image1, image2: (B, H, W, 3) uint8-range images (0..255), NHWC.
          iters: number of GRU refinement iterations (static).
          flow_init: optional (B, H/f, W/f) initial x-flow.
          test_mode: if True return ``(flow_low, flow_up)`` like the reference
            (core/raft_stereo.py:138-139); else the per-iteration list of
            full-resolution x-flow predictions, shape (iters, B, H, W).
            With ``config.exit_threshold_px > 0`` the test-mode loop is
            convergence-gated (``lax.while_loop``): it exits once the
            worst batch member's mean |Δdisparity| falls below the
            threshold, bounded by ``exit_min_iters`` and
            ``min(iters, exit_max_iters)``, and the return grows a third
            element — ``(flow_low, flow_up, iters_used)`` with
            ``iters_used`` an int32 scalar.  Threshold <= 0 keeps this
            fixed-depth scan program bitwise-unchanged.
          unroll_gru: test-mode only — run the refinement loop as an
            unrolled Python loop instead of ``lax.scan``.  Same math, same
            weights; the compiled program inlines every iteration, which
            matters to a reader of the executable because XLA's
            ``cost_analysis`` counts a while-loop body ONCE regardless of
            trip count, so only an unrolled executable carries honest
            per-iteration flops.  Not for deployment: compile time grows
            with ``iters``.
          ctx_init: test-mode only — a CONTEXT bundle from an earlier
            frame's ``return_ctx`` output: ``(net_list, context)`` with
            ``net_list`` the per-level post-tanh initial hidden states
            and ``context`` the per-level (cz, cr, cq) GRU biases.  When
            given, the context encoder (cnet + the context_zqr convs) is
            SKIPPED entirely and the bundle is used in its place — the
            per-session ctx cache behind streaming serving: for a static
            camera the context of the scene does not change frame to
            frame, and cnet is the dominant per-frame encoder cost at
            streaming shapes (not re-measured on the v5e).  Unsupported with
            ``shared_backbone`` (fnet is computed FROM the cnet trunk
            there, so nothing is saved) and with ``rows_gru``.
          return_ctx: test-mode only — also return that context bundle
            (appended as the LAST element of the return tuple) so a
            streaming session can carry it to the next frame.
          hidden_init: test-mode only — the EVOLVED per-level GRU hidden
            states a previous frame's ``return_hidden`` output carried
            (a tuple of (B, H/2^(d+l), W/2^(d+l), hidden_dims[l])
            arrays).  When given, the refinement loop starts from these
            states instead of the context encoder's fresh
            ``tanh(hidden_head)`` init — the half of RAFT's temporal
            state the round-14 ``flow_init`` warm start left cold.  The
            context BIASES (cz, cr, cq) still come from this frame's
            context encoder (or from ``ctx_init`` when both compose):
            they parameterize the scene, while the hidden state carries
            the optimization trajectory.  Unsupported with ``rows_gru``
            (the sharded loop executor owns its own state layout).
          return_hidden: test-mode only — also return the FINAL
            per-level hidden states (appended after ``iters_used`` and
            before the ctx bundle) so a streaming session can chain
            them.
          return_confidence: test-mode only — also return a per-pixel
            CONFIDENCE estimate derived from signals the refinement loop
            already computes: the final iteration's per-pixel
            |Δdisparity| magnitude, a decaying EWMA of the per-pixel
            update trajectory (``CONFIDENCE_EWMA_DECAY``), and — on the
            convergence-gated path — the fraction of the iteration
            budget actually spent (``iters_used``; hitting the cap
            without converging is the same distrust signal the keyframe
            guard acts on).  The element is one 2-tuple
            ``(conf_low, conf_up)``: the (B, H/f, W/f) feature-resolution
            map in (0, 1] and its convex-upsampled (B, H, W) full-res
            counterpart (reusing the final upsample mask — a convex
            combination of confidences is itself a valid confidence).
            Appended after ``iters_used`` and before ``hidden``/``ctx``.
            Off (default) traces NO extra ops: the program stays
            bitwise-identical (pinned by tests).  Unsupported with
            ``rows_gru`` (the sharded loop executor owns its own state
            layout).

        Return order (test mode): ``(flow_low, flow_up[, iters_used]
        [, confidence][, hidden][, ctx])`` — the optional tails appear
        only when their flag is set, in that fixed order.
        """
        cfg = self.config
        dtype = self.compute_dtype
        reuse_ctx = ctx_init is not None and not self.is_initializing()
        reuse_hidden = hidden_init is not None and not self.is_initializing()
        if (ctx_init is not None or return_ctx) and not test_mode:
            raise ValueError("ctx_init/return_ctx are test-mode only "
                             "(the streaming ctx cache is an inference "
                             "feature)")
        if (hidden_init is not None or return_hidden) and not test_mode:
            raise ValueError("hidden_init/return_hidden are test-mode "
                             "only (hidden-state warm start is an "
                             "inference feature)")
        if (hidden_init is not None or return_hidden) and cfg.rows_gru:
            raise ValueError("hidden_init/return_hidden are unsupported "
                             "with rows_gru (the sharded loop executor "
                             "owns its own state layout)")
        if return_confidence and not test_mode:
            raise ValueError("return_confidence is test-mode only (the "
                             "confidence map is an inference product)")
        if return_confidence and cfg.rows_gru:
            raise ValueError("return_confidence is unsupported with "
                             "rows_gru (the sharded loop executor owns "
                             "its own state layout)")
        if reuse_ctx and cfg.shared_backbone:
            raise ValueError(
                "ctx_init is unsupported with shared_backbone: fnet is "
                "computed from the cnet trunk there, so the context "
                "encoder cannot be skipped")
        if (ctx_init is not None or return_ctx) and cfg.rows_gru:
            raise ValueError("ctx_init/return_ctx are unsupported with "
                             "rows_gru (the sharded loop executor owns "
                             "its own context layout)")
        with annotate("prep"):
            image1 = (2 * (image1 / 255.0) - 1.0).astype(dtype)
            image2 = (2 * (image2 / 255.0) - 1.0).astype(dtype)

        # Alternative executors for the encoders' full-resolution segment:
        # banded streams it (one-chip memory ceiling), rows-sharded splits
        # it across a mesh axis (context parallelism).  Both inject through
        # the same trunk_out hook on the SAME parameter tree.
        use_banded = (cfg.banded_encoder and not self.is_initializing())
        use_rows = (cfg.rows_shards > 1 and not self.is_initializing())
        custom_trunk = None
        if use_banded or use_rows:
            from raft_stereo_tpu.models.banded import banded_supported
            for norm in (cfg.context_norm,
                         *((cfg.fnet_norm,) if not cfg.shared_backbone
                           else ())):
                if not banded_supported(norm, cfg.n_downsample):
                    raise ValueError(
                        f"banded_encoder/rows_shards: norm {norm!r} with "
                        f"n_downsample={cfg.n_downsample} is unsupported")
        if use_banded:
            from raft_stereo_tpu.models.banded import banded_trunk_apply

            def custom_trunk(module, x, norm_fn):
                mvars = module.variables
                return banded_trunk_apply(
                    mvars["params"]["trunk"],
                    mvars.get("batch_stats", {}).get("trunk", {}),
                    x, norm_fn, dtype, band=cfg.band_rows)
        elif use_rows:
            from raft_stereo_tpu.parallel.rows_sharded import (
                active_rows_mesh, rows_sharded_trunk_apply)
            active = active_rows_mesh()
            if active is None:
                raise RuntimeError(
                    f"rows_shards={cfg.rows_shards} needs an active mesh: "
                    "trace the model under "
                    "parallel.rows_sharded.rows_sharding(mesh)")
            rows_mesh, rows_axis = active
            if rows_mesh.shape[rows_axis] != cfg.rows_shards:
                raise ValueError(
                    f"rows_shards={cfg.rows_shards} != mesh axis "
                    f"{rows_axis!r} size {rows_mesh.shape[rows_axis]}")

            def custom_trunk(module, x, norm_fn):
                mvars = module.variables
                return rows_sharded_trunk_apply(
                    mvars["params"]["trunk"],
                    mvars.get("batch_stats", {}).get("trunk", {}),
                    x, norm_fn, dtype, mesh=rows_mesh, axis=rows_axis)

        # Layer scopes (profiling.annotate, profiling.LAYERS): every op
        # staged below carries one in its HLO metadata, which the
        # benchmark joins to the trace's operations (PERF.md section 3).
        if cfg.shared_backbone:
            with annotate("cnet"):
                both = jnp.concatenate([image1, image2], axis=0)
                if custom_trunk is not None:
                    levels, v = self.cnet(
                        both, trunk_out=custom_trunk(self.cnet, both,
                                                     cfg.context_norm))
                else:
                    levels, v = self.cnet(both)
            with annotate("fnet"):
                fmap = self.conv2_out(self.conv2_res(v))
                fmap1, fmap2 = jnp.split(fmap, 2, axis=0)
        elif _fnet_sequential(cfg, image1.shape, custom_trunk is not None,
                              record=not self.is_initializing()):
            # Full-resolution inputs: the stem runs at FULL image resolution
            # when n_downsample <= 2 (matching the reference's stride gate,
            # core/extractor.py:140), so its activations dominate peak HBM.
            # Scanning fnet over the two images SEQUENTIALLY (weights shared,
            # lax.scan => strictly ordered) halves that peak vs the batch-2
            # concat — the difference between fitting Middlebury-F-class
            # frames on a 16 GB chip or not (_STEM_EXTRA_BYTES_PER_PIXEL).
            # With banded_encoder, each trunk additionally streams its
            # full-resolution stages band by band (models/banded.py).
            if not reuse_ctx:
                with annotate("cnet"):
                    levels, _ = self.cnet(
                        image1, trunk_out=custom_trunk(self.cnet, image1,
                                                       cfg.context_norm)
                        if custom_trunk is not None else None)

            def fnet_one(module, carry, img):
                trunk_out = (custom_trunk(module.fnet, img, cfg.fnet_norm)
                             if custom_trunk is not None else None)
                return carry, module.fnet(img, trunk_out=trunk_out)

            with annotate("fnet"):
                fnet_scan = nn.scan(
                    fnet_one, variable_broadcast=("params", "batch_stats"),
                    split_rngs={"params": False})
                _, fmaps = fnet_scan(self, None, jnp.stack([image1, image2]))
                fmap1, fmap2 = fmaps[0], fmaps[1]
        else:
            if not reuse_ctx:
                with annotate("cnet"):
                    levels, _ = self.cnet(image1)
            with annotate("fnet"):
                both = self.fnet(jnp.concatenate([image1, image2], axis=0))
                fmap1, fmap2 = jnp.split(both, 2, axis=0)

        with annotate("cnet"):
            if reuse_ctx:
                # The per-session ctx cache: the GRU's initial hidden
                # states and context biases come from an earlier frame's
                # bundle — cnet and the context_zqr convs never run in
                # this program.
                net_list = [jnp.asarray(n).astype(dtype)
                            for n in ctx_init[0]]
                context = [tuple(jnp.asarray(c).astype(dtype) for c in cs)
                           for cs in ctx_init[1]]
            else:
                # levels[l] = [hidden_head, context_head] at level l
                # (fine→coarse)
                net_list = [jnp.tanh(lv[0]) for lv in levels]
                # Precompute GRU context biases cz, cr, cq once
                # (reference: core/raft_stereo.py:87-88).
                context = []
                for l, lv in enumerate(levels):
                    biases = self.context_zqr_convs[l](nn.relu(lv[1]))
                    context.append(tuple(jnp.split(biases, 3, axis=-1)))
        # The carry-forward bundle: captured BEFORE the refinement loop
        # (the initial states, not the evolved ones) so a later frame
        # reusing it starts exactly where a cold frame would.
        ctx_out = ((tuple(net_list), tuple(tuple(c) for c in context))
                   if return_ctx else None)

        if reuse_hidden:
            # Hidden-state warm start: the loop resumes from the previous
            # frame's EVOLVED states.  Replaces whichever init the branch
            # above produced (fresh tanh(hidden_head) or the ctx bundle's
            # saved init) — the context biases keep their source.
            if len(hidden_init) != len(net_list):
                raise ValueError(
                    f"hidden_init carries {len(hidden_init)} levels, "
                    f"model has {len(net_list)} GRU levels")
            with annotate("gru_iter"):
                net_list = [jnp.asarray(h).astype(dtype)
                            for h in hidden_init]

        b, h8, w8, _ = net_list[0].shape
        with annotate("gru_iter"):
            disp = jnp.zeros((b, h8, w8), jnp.float32)
            if flow_init is not None:
                disp = disp + flow_init

        if cfg.rows_gru and not self.is_initializing():
            # Context parallelism through the WHOLE refinement loop: the
            # correlation volume, per-iteration GRU updates, and convex
            # upsampling all run with image rows sharded over the active
            # mesh's rows axis (parallel/rows_gru.py).  ``use_rows`` is
            # necessarily True here (config validation requires
            # rows_shards > 1), so the encoder trunk above already ran
            # sharded on the same, already-validated (rows_mesh, rows_axis).
            from raft_stereo_tpu.parallel.rows_gru import rows_sharded_gru_loop
            return rows_sharded_gru_loop(
                cfg, dtype, self.update_block.variables["params"],
                fmap1, fmap2, net_list, context, disp, iters, test_mode,
                rows_mesh, rows_axis)

        with annotate("corr_pyramid"):
            corr_fn = make_corr_fn(cfg, fmap1, fmap2)
        with annotate("gru_iter"):
            grid_x = coords_grid_x(b, h8, w8, dtype=jnp.float32)

        n = cfg.n_gru_layers

        def gru_step(module, net_list, disp, mask=False):
            """One refinement iteration (reference: core/raft_stereo.py:108-123):
            ``(net_list, disp)``, and with ``mask`` the upsampling mask of
            the new state last.  Only training reads every iteration's mask;
            an inference loop takes ``final_mask`` of its last state."""
            with annotate("gru_iter"):
                return _gru_step_body(module, net_list, disp, mask)

        def _gru_step_body(module, net_list, disp, mask):
            disp = jax.lax.stop_gradient(disp)
            # Named so the remat policy below can SAVE this lookup's output:
            # the backward then reuses it instead of re-running the Pallas
            # kernel (~10% of step time on an earlier runtime; config.py).
            corr = checkpoint_name(
                corr_fn(grid_x + disp).astype(dtype), "corr_lookup")
            flow2 = jnp.stack([disp, jnp.zeros_like(disp)],
                              axis=-1).astype(dtype)

            net_list = list(net_list)
            if n == 3 and cfg.slow_fast_gru:
                net_list = module.update_block(net_list, context,
                                               iter_fine=False, iter_mid=False,
                                               update=False)
            if n >= 2 and cfg.slow_fast_gru:
                net_list = module.update_block(net_list, context,
                                               iter_fine=False,
                                               iter_coarse=(n == 3),
                                               update=False)
            net_list, *up_mask, delta_flow = module.update_block(
                net_list, context, corr, flow2,
                iter_mid=(n >= 2), iter_coarse=(n == 3), mask=mask)

            # Epipolar projection: only the x component updates
            # (reference: core/raft_stereo.py:120).
            disp = disp + delta_flow[..., 0].astype(jnp.float32)
            return (net_list, disp, *up_mask)

        def final_mask(net_fin, loop):
            """The upsampling mask of the loop's final state, computed once
            after it: the inference loops carry no mask, since only the
            last iteration's would be read."""
            if not self.is_initializing():
                from raft_stereo_tpu.kernels.corr_lookup import log_path_once
                log_path_once(f"upsampling mask: once after the loop "
                              f"({loop})")
            with annotate("upsample"):
                return self.update_block.mask_head(net_fin[0])

        ctx_tail = (ctx_out,) if return_ctx else ()

        def hidden_tail(net_fin):
            return (tuple(net_fin),) if return_hidden else ()

        # Each loop below runs under ``gru_iter`` with its set-up: the
        # loop's own operations (the ``while``, its counter, the carries,
        # in training the weight gradients it accumulates) are the loop's.
        # The upsampling after it is not.
        if test_mode and unroll_gru:
            with annotate("gru_iter"):
                if return_confidence:
                    dmag = jnp.zeros((b, h8, w8), jnp.float32)
                    ewma = jnp.zeros((b, h8, w8), jnp.float32)
                for _ in range(iters):
                    net_list, new_disp = gru_step(self, net_list, disp)
                    if return_confidence:
                        dmag = jnp.abs(new_disp - disp)
                        ewma = (CONFIDENCE_EWMA_DECAY * ewma
                                + (1.0 - CONFIDENCE_EWMA_DECAY) * dmag)
                    disp = new_disp
            mask = final_mask(net_list, f"{iters} iterations, unrolled")
            flow_up = self._upsample(disp, mask)
            if return_confidence:
                conf = self._confidence_maps(dmag, ewma, mask,
                                             jnp.float32(1.0))
                return ((disp, flow_up, conf)
                        + hidden_tail(net_list) + ctx_tail)
            return (disp, flow_up) + hidden_tail(net_list) + ctx_tail

        if (test_mode and cfg.exit_threshold_px > 0
                and not self.is_initializing()):
            # Convergence-gated refinement: the scan becomes a
            # ``lax.while_loop`` that computes each iteration's mean
            # |Δdisparity| per image (the quantity gru_telemetry measures)
            # and exits once the WORST batch member falls below the
            # threshold — max-over-batch keeps one executable per bucket;
            # an easy frame sharing a batch with a hard one simply rides
            # to the hard frame's depth.  ``is_initializing`` falls
            # through to the scan below: nn.while_loop cannot create
            # variables in its body, and init only needs the parameter
            # tree, which both loops build identically.
            limit = (iters if cfg.exit_max_iters is None
                     else min(iters, cfg.exit_max_iters))
            min_iters = max(1, min(cfg.exit_min_iters, limit))
            threshold = jnp.float32(cfg.exit_threshold_px)
            exit_loop = f"{min_iters} to {limit} iterations, exit-gated"

            if return_confidence:
                # Confidence variant: the carry additionally tracks the
                # per-pixel update magnitude (whose batch-mean max IS the
                # exit predicate — computed once, used for both) and its
                # decaying EWMA.  A distinct program by construction; the
                # plain branch below stays bitwise-untouched.
                def cond_exit_conf(module, carry):
                    _net, _disp, it, delta, _dm, _ew = carry
                    return jnp.logical_or(
                        it < min_iters,
                        jnp.logical_and(it < limit, delta >= threshold))

                def body_exit_conf(module, carry):
                    net_list, disp, it, _delta, _dm, ewma = carry
                    net_list, new_disp = gru_step(module, list(net_list),
                                                  disp)
                    dmag = jnp.abs(new_disp - disp).astype(jnp.float32)
                    delta = jnp.max(jnp.mean(dmag, axis=(1, 2)))
                    ewma = (CONFIDENCE_EWMA_DECAY * ewma
                            + (1.0 - CONFIDENCE_EWMA_DECAY) * dmag)
                    return (tuple(net_list), new_disp, it + jnp.int32(1),
                            delta, dmag, ewma)

                with annotate("gru_iter"):
                    zero_px = jnp.zeros((b, h8, w8), jnp.float32)
                    carry = (tuple(net_list), disp, jnp.int32(0),
                             jnp.float32(jnp.inf), zero_px, zero_px)
                    (net_fin, disp_fin, iters_used, _delta, dmag_fin,
                     ewma_fin) = nn.while_loop(cond_exit_conf,
                                               body_exit_conf, self, carry)
                    depth_frac = iters_used.astype(jnp.float32) / limit
                mask_fin = final_mask(net_fin, exit_loop)
                flow_up = self._upsample(disp_fin, mask_fin)
                conf = self._confidence_maps(dmag_fin, ewma_fin,
                                             mask_fin, depth_frac)
                return ((disp_fin, flow_up, iters_used, conf)
                        + hidden_tail(net_fin) + ctx_tail)

            def cond_exit(module, carry):
                _net, _disp, it, delta = carry
                return jnp.logical_or(
                    it < min_iters,
                    jnp.logical_and(it < limit, delta >= threshold))

            def body_exit(module, carry):
                net_list, disp, it, _delta = carry
                net_list, new_disp = gru_step(module, list(net_list), disp)
                # Mean update magnitude per image, worst over the batch.
                # Feeds only the loop predicate — the disparity chain is
                # the same op sequence the fixed-depth scan runs.
                delta = jnp.max(jnp.mean(jnp.abs(new_disp - disp),
                                         axis=(1, 2)))
                return (tuple(net_list), new_disp, it + jnp.int32(1),
                        delta)

            with annotate("gru_iter"):
                carry = (tuple(net_list), disp, jnp.int32(0),
                         jnp.float32(jnp.inf))
                (net_fin, disp_fin, iters_used, _delta) = nn.while_loop(
                    cond_exit, body_exit, self, carry)
            mask_fin = final_mask(net_fin, exit_loop)
            flow_up = self._upsample(disp_fin, mask_fin)
            return ((disp_fin, flow_up, iters_used)
                    + hidden_tail(net_fin) + ctx_tail)

        if test_mode:
            # No per-iteration outputs needed; the scan carries state, and
            # the mask and the upsampling run once at the end (reference
            # skips intermediate upsampling in test mode —
            # core/raft_stereo.py:126-127).
            if return_confidence:
                # Confidence variant of the fixed-depth scan: the carry
                # additionally tracks the per-pixel update magnitude and
                # its EWMA.  Fixed depth spends the whole budget, so the
                # depth fraction is 1 by construction.
                def body_test_conf(module, carry, _):
                    net_list, disp, _dm, ewma = carry
                    net_list, new_disp = gru_step(module, net_list, disp)
                    dmag = jnp.abs(new_disp - disp).astype(jnp.float32)
                    ewma = (CONFIDENCE_EWMA_DECAY * ewma
                            + (1.0 - CONFIDENCE_EWMA_DECAY) * dmag)
                    return (tuple(net_list), new_disp, dmag, ewma), None

                scan_conf = nn.scan(
                    body_test_conf,
                    variable_broadcast=("params", "batch_stats"),
                    split_rngs={"params": False}, length=iters)
                with annotate("gru_iter"):
                    zero_px = jnp.zeros((b, h8, w8), jnp.float32)
                    (net_fin, disp_fin, dmag_fin, ewma_fin), _ = scan_conf(
                        self, (tuple(net_list), disp, zero_px, zero_px),
                        None)
                mask_fin = final_mask(net_fin, f"{iters} iterations")
                flow_up = self._upsample(disp_fin, mask_fin)
                conf = self._confidence_maps(dmag_fin, ewma_fin,
                                             mask_fin, jnp.float32(1.0))
                return ((disp_fin, flow_up, conf)
                        + hidden_tail(net_fin) + ctx_tail)

            def body_test(module, carry, _):
                net_list, disp = gru_step(module, *carry)
                return (tuple(net_list), disp), None

            scan_test = nn.scan(body_test, variable_broadcast=("params", "batch_stats"),
                                split_rngs={"params": False}, length=iters)
            with annotate("gru_iter"):
                (net_fin, disp_fin), _ = scan_test(
                    self, (tuple(net_list), disp), None)
            mask_fin = final_mask(net_fin, f"{iters} iterations")
            flow_up = self._upsample(disp_fin, mask_fin)
            return (disp_fin, flow_up) + hidden_tail(net_fin) + ctx_tail

        def body_train(module, carry, _):
            net_list, disp = carry
            net_list, disp, up_mask = gru_step(module, net_list, disp,
                                               mask=True)
            # Upsample inside the scan so per-iteration masks never
            # accumulate in HBM.
            flow_up = module._upsample(disp, up_mask)
            return (tuple(net_list), disp), flow_up

        if cfg.remat_gru:
            # Backward recomputes each iteration from its carry instead of
            # storing every update-block activation (see config.remat_gru).
            # Exception: the intermediates named in cfg.remat_save are kept
            # — by default the correlation lookup output (small at ~2
            # MB/iter while its recompute is a full Pallas kernel launch
            # per backward iteration, the single largest remat overhead in
            # the round-3 trace); "gru_gates"/"motion_features" extend the
            # trade (config.remat_save).  prevent_cse=False is safe (and
            # recommended) under scan.
            body_train = nn.remat(
                body_train, prevent_cse=False,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *cfg.remat_save))
        scan_train = nn.scan(body_train, variable_broadcast=("params", "batch_stats"),
                             split_rngs={"params": False}, length=iters)
        with annotate("gru_iter"):
            (net_fin, disp_fin), flow_ups = scan_train(
                self, (tuple(net_list), disp), None)
        return flow_ups  # (iters, B, H, W)

    def _upsample(self, disp: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
        """Convex-upsample a (B,h,w) disparity to full resolution (B,H,W)."""
        with annotate("upsample"):
            up = convex_upsample(disp[..., None], mask.astype(jnp.float32),
                                 self.config.downsample_factor)
            return up[..., 0]

    def _confidence_maps(self, dmag: jnp.ndarray, ewma: jnp.ndarray,
                         mask: jnp.ndarray, depth_frac: jnp.ndarray):
        """The ``return_confidence`` element: (conf_low, conf_up).

        Per-pixel convergence score = final |Δdisparity| plus half the
        trajectory EWMA (px at feature resolution), scaled up by the
        fraction of the iteration budget spent (adaptive loops that
        exited early earn a mild trust bonus; a loop that rode to its
        cap gets none — the keyframe-guard distrust signal).  Confidence
        is exp(-score/scale): 1.0 for fully-settled pixels, decaying on
        the CONFIDENCE_SCALE_PX length scale.  The full-res map reuses
        the final convex-upsample mask — a convex combination of
        confidences is itself a confidence."""
        with annotate("confidence"):
            score = (dmag + 0.5 * ewma).astype(jnp.float32)
            conf_low = jnp.exp(-score * (0.5 + 0.5 * depth_frac)
                               / CONFIDENCE_SCALE_PX)
            conf_up = jnp.clip(self._upsample(conf_low, mask), 0.0, 1.0)
            return conf_low, conf_up


def create_model(cfg):
    """The model a configuration describes: ``RAFTStereo`` for a
    ``RaftStereoConfig``, ``models.igev.IGEVStereo`` for an
    ``IgevStereoConfig``."""
    from raft_stereo_tpu.config import IgevStereoConfig

    if isinstance(cfg, IgevStereoConfig):
        from raft_stereo_tpu.models.igev import IGEVStereo
        return IGEVStereo(cfg)
    return RAFTStereo(cfg)
