"""Recurrent update block (reference: core/update.py).

Level indexing convention: level 0 is the FINEST resolution
(1/2^n_downsample); the reference's gru08/gru16/gru32 are our levels 0/1/2.
The context-bias triples (cz, cr, cq) are precomputed once per forward by the
model (reference: core/raft_stereo.py:87-88) and passed in per level.

``SepConvGRU`` (core/update.py:34-62) is dead code in the reference and not
rebuilt (SURVEY.md §2).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

from raft_stereo_tpu.config import RaftStereoConfig
from raft_stereo_tpu.models.extractor import conv, kaiming_out
from raft_stereo_tpu.ops.pooling import pool2x
from raft_stereo_tpu.ops.resize import interp_like


class FlowHead(nn.Module):
    """2-conv disparity-delta head (reference: core/update.py:6-14)."""

    hidden_dim: int = 256
    output_dim: int = 2
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        y = nn.relu(conv(self.hidden_dim, 3, 1, dtype=self.dtype, name="conv1")(x))
        return conv(self.output_dim, 3, 1, dtype=self.dtype, name="conv2")(y)


class _GateConvParams(nn.Module):
    """Parameter twin of one Flax gate conv: declares exactly the param tree
    ``nn.Conv`` builds (HWIO ``kernel`` + ``bias``, same initializers, fp32)
    and hands the raw arrays to the fused kernel instead of running the
    conv.  Named ``convzr``/``convq`` it is checkpoint-interchangeable with
    the Flax path — same pytree paths, shapes, and init values."""

    features: int
    in_features: int
    kernel_size: int

    @nn.compact
    def __call__(self):
        k = self.kernel_size
        kernel = self.param("kernel", kaiming_out,
                            (k, k, self.in_features, self.features))
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        return kernel, bias


class ConvGRU(nn.Module):
    """ConvGRU with pre-computed context biases (reference: core/update.py:16-32).

    The z and r gates both convolve the same ``[h, x]`` concat, so they run
    as ONE conv producing ``2*hidden`` channels, split afterwards — half the
    conv dispatches in the scan body's hottest block for identical math (the
    reference keeps two convs, core/update.py:18-19; the torch importer
    concatenates their weights into ``convzr`` so checkpoints stay
    compatible).  q cannot join: its input ``[r*h, x]`` depends on r.

    ``fused`` (= config.fused_gru) routes the whole gate pipeline — both
    convs and the r coupling — through the Pallas kernel
    (kernels/gru_fused.py) when the backend supports it and the level's
    working set fits VMEM; the pointwise tail stays in XLA so the
    "gru_gates" remat tag keeps its meaning (saved gates ⇒ the backward
    recompute is pointwise-only).  Dispatch is per level at trace time;
    init always takes the Flax branch so the parameter tree is created by
    ``nn.Conv`` regardless of mode."""

    hidden_dim: int
    kernel_size: int = 3
    dtype: Optional[Any] = None
    fused: str = "off"   # config.fused_gru: "auto" | "on" | "off"

    @nn.compact
    def __call__(self, h, context, *x_list):
        from jax.ad_checkpoint import checkpoint_name

        cz, cr, cq = context
        x = jnp.concatenate(x_list, axis=-1)
        k = self.kernel_size
        hd = self.hidden_dim

        use_fused = False
        if self.fused != "off" and not self.is_initializing():
            from raft_stereo_tpu.kernels.gru_fused import gru_fused_should_use
            use_fused = gru_fused_should_use(
                self.fused, kernel_size=k, w=h.shape[2],
                cin=h.shape[-1] + x.shape[-1], ch=hd,
                itemsize=h.dtype.itemsize)
        if use_fused:
            from raft_stereo_tpu.kernels.gru_fused import gru_gates_fused
            cin = h.shape[-1] + x.shape[-1]
            wzr, bzr = _GateConvParams(2 * hd, cin, k, name="convzr")()
            wq, bq = _GateConvParams(hd, cin, k, name="convq")()
            from raft_stereo_tpu.parallel.data_sharded import over_data_axis
            zr, qpre = over_data_axis(gru_gates_fused, (h, x, cr),
                                      (wzr, bzr, wq, bq))
            # Same remat tags at the same sites as the Flax branch below —
            # tests/test_remat_names.py pins that every config.remat_save
            # name survives in the traced graph on both paths.
            zr = checkpoint_name(zr, "gru_gates")
            qpre = checkpoint_name(qpre, "gru_gates")
            z = nn.sigmoid(zr[..., :hd] + cz)
            q = nn.tanh(qpre + cq)
            return (1 - z) * h + z * q

        hx = jnp.concatenate([h, x], axis=-1)
        # Pre-activation gate convs carry a remat name: with "gru_gates" in
        # config.remat_save the backward reuses them instead of re-running
        # the scan body's two largest convs (see the remat policy in
        # models/raft_stereo.py).
        zr = checkpoint_name(
            conv(2 * self.hidden_dim, k, 1, dtype=self.dtype,
                 name="convzr")(hx), "gru_gates")
        z = nn.sigmoid(zr[..., :self.hidden_dim] + cz)
        r = nn.sigmoid(zr[..., self.hidden_dim:] + cr)
        q = nn.tanh(checkpoint_name(
            conv(self.hidden_dim, k, 1, dtype=self.dtype, name="convq")(
                jnp.concatenate([r * h, x], axis=-1)), "gru_gates") + cq)
        return (1 - z) * h + z * q


class BasicMotionEncoder(nn.Module):
    """Encode correlation + flow into 128-ch motion features
    (reference: core/update.py:64-85)."""

    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, flow, corr):
        cor = nn.relu(conv(64, 1, 1, dtype=self.dtype, name="convc1")(corr))
        cor = nn.relu(conv(64, 3, 1, dtype=self.dtype, name="convc2")(cor))
        flo = nn.relu(conv(64, 7, 1, dtype=self.dtype, name="convf1")(flow))
        flo = nn.relu(conv(64, 3, 1, dtype=self.dtype, name="convf2")(flo))
        out = nn.relu(conv(128 - 2, 3, 1, dtype=self.dtype, name="conv")(
            jnp.concatenate([cor, flo], axis=-1)))
        from jax.ad_checkpoint import checkpoint_name
        # named for config.remat_save ("motion_features"): saving this
        # output lets the backward skip the whole 5-conv encoder recompute
        return checkpoint_name(jnp.concatenate([out, flow], axis=-1),
                               "motion_features")


class BasicMultiUpdateBlock(nn.Module):
    """Up to 3 cross-coupled ConvGRUs + flow/mask heads
    (reference: core/update.py:97-138)."""

    config: RaftStereoConfig
    dtype: Optional[Any] = None
    # Cross-resolution upsampling override.  The align-corners bilinear
    # interp's sampling grid depends on GLOBAL tensor heights, so the
    # row-sharded context-parallel executor (parallel/rows_gru.py) supplies
    # per-device window-restricted matrices here; None = the ordinary
    # whole-tensor ``interp_like``.  No effect on parameters.
    interp_fn: Optional[Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]] = None

    @nn.compact
    def __call__(self, net: Sequence[jnp.ndarray],
                 context: Sequence[Tuple[jnp.ndarray, ...]],
                 corr: Optional[jnp.ndarray] = None,
                 flow: Optional[jnp.ndarray] = None,
                 iter_fine: bool = True, iter_mid: bool = True,
                 iter_coarse: bool = True, update: bool = True):
        cfg = self.config
        hd = cfg.hidden_dims  # fine → coarse
        n = cfg.n_gru_layers
        net = list(net)
        interp = self.interp_fn or interp_like

        # GRU input dims mirror reference core/update.py:104-106 under our
        # fine→coarse indexing.  Every level inherits config.fused_gru; the
        # fused-vs-Flax dispatch itself happens per level inside ConvGRU
        # (per-level W/Cin decide the VMEM fit).
        fused = cfg.fused_gru
        if iter_coarse and n == 3:
            net[2] = ConvGRU(hd[2], dtype=self.dtype, fused=fused,
                             name="gru32")(
                net[2], context[2], pool2x(net[1]))
        if iter_mid and n >= 2:
            if n > 2:
                net[1] = ConvGRU(hd[1], dtype=self.dtype, fused=fused,
                                 name="gru16")(
                    net[1], context[1], pool2x(net[0]),
                    interp(net[2], net[1]))
            else:
                net[1] = ConvGRU(hd[1], dtype=self.dtype, fused=fused,
                                 name="gru16")(
                    net[1], context[1], pool2x(net[0]))
        if iter_fine:
            motion = BasicMotionEncoder(dtype=self.dtype, name="encoder")(
                flow, corr)
            if n > 1:
                net[0] = ConvGRU(hd[0], dtype=self.dtype, fused=fused,
                                 name="gru08")(
                    net[0], context[0], motion, interp(net[1], net[0]))
            else:
                net[0] = ConvGRU(hd[0], dtype=self.dtype, fused=fused,
                                 name="gru08")(
                    net[0], context[0], motion)

        if not update:
            return net

        delta_flow = FlowHead(256, 2, dtype=self.dtype, name="flow_head")(net[0])

        # mask scaled ×0.25 "to balance gradients" (core/update.py:136-137)
        m = nn.relu(conv(256, 3, 1, dtype=self.dtype, name="mask_conv1")(net[0]))
        mask = 0.25 * conv(cfg.mask_channels, 1, 1, dtype=self.dtype,
                           name="mask_conv2")(m)
        return net, mask, delta_flow
