"""The jitted training step, single-device or SPMD over a mesh.

Replaces the reference's hot loop body (train_stereo.py:159-181): forward over
all GRU iterations, sequence loss, backward, global-norm clip, AdamW update —
one compiled XLA program.  There is no GradScaler: bf16 on TPU has fp32-range
exponents, so mixed precision needs no loss scaling (the reference's AMP
scaffolding at train_stereo.py:18-32,155,173-179 has no TPU equivalent to
build).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_stereo_tpu.config import TrainConfig
from raft_stereo_tpu.data.device_jitter import (JitterParams,
                                                apply_photometric,
                                                params_for_datasets)
from raft_stereo_tpu.parallel.data_sharded import data_sharding
from raft_stereo_tpu.parallel.mesh import DATA_AXIS
from raft_stereo_tpu.training.anomaly import (SKIP_KEY, SKIP_NONFINITE_KEY,
                                              SKIP_SPIKE_KEY, AnomalyPolicy)
from raft_stereo_tpu.training.loss import sequence_loss
from raft_stereo_tpu.training.state import TrainState


def train_step(state: TrainState, batch: Dict[str, jnp.ndarray],
               *, iters: int, loss_gamma: float, max_flow: float,
               jitter: Optional[JitterParams] = None,
               jitter_seed: int = 0,
               gru_telemetry: bool = False
               ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
    """One optimization step.

    ``batch``: image1/image2 (B,H,W,3) uint8 or float32 0..255 (the loader
    ships uint8 to quarter the host->device transfer; the model normalizes
    either on device), flow (B,H,W) x-flow (= -disparity) in float32 or
    float16 (TrainConfig.compact_upload halves the flow upload; cast back
    to f32 here on device), valid (B,H,W) in {0,1}, any dtype.
    ``jitter``: on-device photometric augmentation params
    (TrainConfig.device_photometric); the PRNG key is folded from
    ``(jitter_seed, state.step)`` so the factor stream is deterministic
    per step and bit-identical across an exact resume.
    """

    # Tolerate states built without create_train_state (batch_stats=None).
    batch_stats = state.batch_stats if state.batch_stats is not None else {}

    if jitter is not None:
        key = jax.random.fold_in(jax.random.PRNGKey(jitter_seed), state.step)
        img1, img2 = apply_photometric(batch["image1"], batch["image2"],
                                       key, jitter)
        batch = dict(batch, image1=img1, image2=img2)

    # compact uploads arrive fp16/uint8; all loss math runs f32 on device
    flow_gt = batch["flow"].astype(jnp.float32)
    valid_gt = batch["valid"].astype(jnp.float32)

    def loss_fn(params):
        preds = state.apply_fn(
            {"params": params, "batch_stats": batch_stats},
            batch["image1"], batch["image2"], iters=iters)
        loss, metrics = sequence_loss(preds, flow_gt, valid_gt,
                                      loss_gamma=loss_gamma, max_flow=max_flow)
        if gru_telemetry and iters > 1:
            # GRU convergence curve (TrainConfig.gru_telemetry): mean
            # |disparity update| per refinement iteration, a (iters-1,)
            # vector riding the metrics dict — fetched with the buffered
            # drain, never a per-step sync.  stop_gradient: telemetry must
            # not perturb the backward.
            p = jax.lax.stop_gradient(preds)
            metrics = dict(metrics, gru_delta_px=jnp.mean(
                jnp.abs(p[1:] - p[:-1]), axis=(1, 2, 3)))
        return loss, metrics

    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        state.params)
    new_state = state.apply_gradients(grads=grads)
    # Global gradient norm rides the metrics dict: the optimizer computes
    # the same reduction for clipping (XLA dedups it), it reaches the host
    # through the existing buffered drain — no extra sync — and it is the
    # grad half of the non-finite sentinel (telemetry/watchdog.py): a
    # diverging run's grad_norm goes non-finite a window before the loss
    # does when clipping masks the blow-up.
    metrics = dict(metrics, loss=loss, grad_norm=optax.global_norm(grads))
    return new_state, metrics


def anomaly_train_step(state: TrainState, batch: Dict[str, jnp.ndarray],
                       loss_ewma: jnp.ndarray, *, iters: int,
                       loss_gamma: float, max_flow: float,
                       policy: AnomalyPolicy,
                       jitter: Optional[JitterParams] = None,
                       jitter_seed: int = 0,
                       gru_telemetry: bool = False):
    """``train_step`` wrapped in the on-device anomaly gate.

    The forward/backward is the plain step's; the update is then merged
    through ``jnp.where``: a non-finite loss or grad norm — or, when
    ``policy.spike_factor > 0``, a finite loss above ``spike_factor ×``
    the device-side loss EWMA — keeps EVERY leaf of the old state
    (params, optimizer moments, step counter), so a poison batch is a
    no-op update instead of a poisoned run.  ``loss_ewma`` is a device
    f32 scalar the loop threads step-to-step (0 = no baseline yet; the
    first finite loss seeds it), checkpointed in the runtime blob so an
    exact resume keeps the spike baseline bitwise.  The skip decision and
    flags stay on device and reach the host through the existing
    buffered metric drain — zero extra syncs (the r13 contract).
    """
    new_state, metrics = train_step(
        state, batch, iters=iters, loss_gamma=loss_gamma, max_flow=max_flow,
        jitter=jitter, jitter_seed=jitter_seed, gru_telemetry=gru_telemetry)
    loss = metrics["loss"]
    grad_norm = metrics["grad_norm"]
    nonfinite = jnp.logical_not(jnp.logical_and(jnp.isfinite(loss),
                                                jnp.isfinite(grad_norm)))
    if policy.spike_factor > 0:
        spike = jnp.logical_and(
            jnp.logical_not(nonfinite),
            jnp.logical_and(loss_ewma > 0,
                            loss > loss_ewma * policy.spike_factor))
    else:
        spike = jnp.zeros((), jnp.bool_)
    skip = jnp.logical_or(nonfinite, spike)
    # where() selects, never mixes: a NaN in the discarded branch cannot
    # leak (no arithmetic with it), and the kept branch is bit-identical
    # to whichever state survives.
    merged = jax.tree_util.tree_map(
        lambda old, new: jnp.where(skip, old, new), state, new_state)
    beta = policy.ewma_beta
    updated_ewma = jnp.where(loss_ewma > 0,
                             beta * loss_ewma + (1.0 - beta) * loss,
                             loss)
    new_ewma = jnp.where(skip, loss_ewma, updated_ewma)
    f32 = jnp.float32
    metrics = dict(metrics, **{
        SKIP_KEY: skip.astype(f32),
        SKIP_NONFINITE_KEY: nonfinite.astype(f32),
        SKIP_SPIKE_KEY: spike.astype(f32)})
    return merged, metrics, new_ewma


def make_train_step(train_cfg: TrainConfig, mesh: Optional[Mesh] = None,
                    donate: bool = True,
                    anomaly: Optional[AnomalyPolicy] = None):
    """Compile the step.  With a ``mesh``, the batch is sharded along
    ``data`` and the state replicated; XLA derives the gradient all-reduce
    (psum over ICI) from the shardings — the SPMD replacement for
    ``nn.DataParallel`` (reference: train_stereo.py:134).

    ``anomaly=None`` (default) compiles the exact pre-round-20 two-arg
    program; with an ``AnomalyPolicy`` the step signature becomes
    ``(state, batch, loss_ewma) -> (state, metrics, loss_ewma)`` with the
    on-device skip gate of ``anomaly_train_step``."""
    jitter = None
    if train_cfg.device_photometric:
        jitter = params_for_datasets(train_cfg.train_datasets,
                                     saturation_range=train_cfg.saturation_range,
                                     img_gamma=train_cfg.img_gamma)
    common = dict(iters=train_cfg.train_iters,
                  loss_gamma=train_cfg.loss_gamma,
                  max_flow=train_cfg.max_flow,
                  jitter=jitter, jitter_seed=train_cfg.seed,
                  gru_telemetry=train_cfg.gru_telemetry)
    if anomaly is not None:
        step = functools.partial(anomaly_train_step, policy=anomaly,
                                 **common)
        n_out = 3
    else:
        step = functools.partial(train_step, **common)
        n_out = 2
    if mesh is None:
        return jax.jit(step, donate_argnums=(0,) if donate else ())

    def step_on_mesh(*args):
        # The body runs at trace time: the Pallas kernel calls inside it
        # are batch-split over ``data`` by hand (parallel/data_sharded.py),
        # everything else by XLA from the shardings below.
        with data_sharding(mesh):
            return step(*args)

    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P(DATA_AXIS))
    return jax.jit(
        step_on_mesh,
        in_shardings=(repl, data) + ((repl,) if n_out == 3 else ()),
        out_shardings=(repl,) * n_out,
        donate_argnums=(0,) if donate else (),
    )
