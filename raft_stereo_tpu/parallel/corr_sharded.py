"""Disparity-axis (W2) sharded correlation — the "long-context" path.

The reg correlation volume is O(B·H·W1·W2) memory; at full Middlebury-F
resolution it dominates HBM.  The reference's answer is to avoid the volume
entirely ("alt", reference: core/corr.py:64-107) or downsample more
(reference: train_stereo.py:237).  A TPU pod offers a third axis the
reference never had: shard the disparity-*search* dimension W2 across chips
(SURVEY.md §5 — the stereo analog of sequence parallelism).

Design (SPMD via ``shard_map`` over the ``corr`` mesh axis):

* **Build** — each chip holds a W-slice of the right feature map and computes
  its (B, H, W1, W2/n) slice of the volume as a local MXU matmul; the pyramid
  is pooled locally (shard widths are kept divisible by 2^(levels-1), so
  2-wide stride-2 pooling never crosses a shard boundary and matches the
  reference's global floor semantics — core/corr.py:124).  The full volume is
  never materialized on any one chip.
* **Lookup** — linear interpolation is a 2-tap weighted sum, so each chip
  samples its local slice with shard-local coordinates (taps falling outside
  the shard contribute zero, exactly the zero-padding semantics of
  ``ops.sampler.linear_sampler_1d``) and a ``psum`` over ``corr`` assembles
  the exact global window: every global bin is owned by exactly one shard.
  The per-iteration collective is the small (B, H, W1, levels·(2r+1)) lookup
  result riding ICI — never the volume.

Exactness: W2 is zero-padded up to ``n_corr · 2^(levels-1)`` divisibility
(zero right-features ⇒ zero correlation), and after every pooling step bins
whose *global* index falls at or beyond the reference's floor-semantics level
width are zeroed, so boundary taps read zero exactly where the reference's
out-of-range sampling does.  ``tests/test_parallel.py`` asserts bit-level
agreement (values and gradients) with the unsharded ``reg`` backend.
"""

from __future__ import annotations

import contextlib
from typing import List, Optional, Tuple

import jax
import jax.lax as lax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from raft_stereo_tpu.config import RaftStereoConfig
from raft_stereo_tpu.models.corr import (_window_coords, build_corr_volume,
                                         pool_axis)
from raft_stereo_tpu.ops.sampler import linear_sampler_1d
from raft_stereo_tpu.parallel.mesh import CORR_AXIS

_active_mesh: Optional[Mesh] = None


@contextlib.contextmanager
def corr_sharding(mesh: Mesh):
    """Activate ``mesh`` for W2-sharded correlation within the block.

    Wrap the *tracing* of any jitted function whose model config has
    ``corr_w2_shards > 1`` (training step, eval forward, dry-run)."""
    global _active_mesh
    if CORR_AXIS not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no {CORR_AXIS!r} axis")
    prev, _active_mesh = _active_mesh, mesh
    try:
        yield mesh
    finally:
        _active_mesh = prev


def active_corr_mesh() -> Optional[Mesh]:
    return _active_mesh


def _level_widths(w2: int, num_levels: int) -> List[int]:
    """True (unpadded) level widths under the reference's floor pooling."""
    widths = [w2]
    for _ in range(num_levels - 1):
        widths.append(widths[-1] // 2)
    return widths


def make_corr_fn_w2_sharded(cfg: RaftStereoConfig, fmap1: jnp.ndarray,
                            fmap2: jnp.ndarray, mesh: Mesh):
    """Sharded-volume counterpart of ``models.corr.make_corr_fn_reg``.

    Returns a ``CorrFn``; call under ``corr_sharding(mesh)`` during tracing.
    """
    n = cfg.corr_w2_shards
    axis_size = mesh.shape[CORR_AXIS]
    if axis_size != n:
        raise ValueError(
            f"config asks for corr_w2_shards={n} but mesh {CORR_AXIS!r} axis "
            f"has {axis_size} devices")
    num_levels = cfg.corr_levels
    radius = cfg.corr_radius

    # reg semantics: build in fp32.  With the reg_fused backend the shard
    # volumes are then *stored* in the incoming compute dtype (bf16 under
    # mixed precision — halving per-shard HBM, the same trade the unsharded
    # fused backend makes in models/corr.py).
    store_dtype = fmap1.dtype if cfg.corr_backend == "reg_fused" \
        else jnp.float32
    fmap1 = fmap1.astype(jnp.float32)
    fmap2 = fmap2.astype(jnp.float32)
    w2 = fmap2.shape[2]
    widths = _level_widths(w2, num_levels)

    # Pad W2 so every pooled level splits evenly across shards.
    quantum = n * 2 ** (num_levels - 1)
    w2p = -(-w2 // quantum) * quantum
    if w2p != w2:
        fmap2 = jnp.pad(fmap2, ((0, 0), (0, 0), (0, w2p - w2), (0, 0)))

    # Per-shard lookup.  Two implementations of the same contract (below);
    # the kernel reads its shard volumes TRANSPOSED, (B, H, W2/n, W1), bins
    # on the sublanes (kernels/corr_lookup.py), so where it runs the local
    # volume is built so and pools and shards along axis -2.
    from raft_stereo_tpu.kernels import corr_lookup as _kernels

    use_kernel = (cfg.corr_backend == "reg_fused"
                  and _kernels.fused_lookup_available())
    w2_axis = -2 if use_kernel else -1
    vol_spec = (P(None, None, CORR_AXIS, None) if use_kernel
                else P(None, None, None, CORR_AXIS))

    def build_local(f1: jnp.ndarray, f2_local: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, ...]:
        vol = (build_corr_volume(f2_local, f1) if use_kernel
               else build_corr_volume(f1, f2_local))
        shard = lax.axis_index(CORR_AXIS)
        pyramid = []
        for level in range(num_levels):
            if level:
                # Shard widths stay even at every level (padding quantum), so
                # local pooling equals the reference's global floor pooling.
                vol = pool_axis(vol, w2_axis)
            lw = vol.shape[w2_axis]
            # Zero bins at/after the reference's floor-semantics level width
            # so boundary taps read zero exactly like out-of-range sampling.
            global_bin = shard * lw + jnp.arange(lw)
            keep = global_bin < widths[level]
            vol = jnp.where(keep[:, None] if use_kernel else keep, vol, 0.0)
            pyramid.append(vol.astype(store_dtype))
        return tuple(pyramid)

    # Manual only over ``corr``; the batch axis stays automatic so the outer
    # jit's data-parallel sharding (or a batch of 1 at init) passes through.
    pyramid = jax.shard_map(
        build_local, mesh=mesh, axis_names={CORR_AXIS},
        in_specs=(P(), P(None, None, CORR_AXIS, None)),
        out_specs=tuple(vol_spec for _ in range(num_levels)),
    )(fmap1, fmap2)

    # * reg_fused → the Pallas kernel with shard-shifted centers, inside a
    #   FULL-manual shard_map (every mesh axis manual, check_vma=False —
    #   partial-manual cannot vma-check the Pallas primitive, and full-manual
    #   is the standard pallas+shard_map pattern).  Out-of-shard taps get
    #   zero hat weights, so the psum assembles the exact global window.
    # * reg → the XLA sampler in a partial-manual shard_map (batch axis
    #   automatic) — the pure-XLA correctness reference, exactly like the
    #   unsharded backend split.
    if use_kernel:
        # Full-manual requires explicit batch placement: split over the data
        # axis when the static batch divides it (the training/eval case),
        # else replicate (e.g. batch-1 init under a multi-device mesh).
        from raft_stereo_tpu.parallel.mesh import DATA_AXIS
        n_data = int(mesh.shape.get(DATA_AXIS, 1))
        split = (DATA_AXIS in mesh.axis_names and n_data > 1
                 and fmap1.shape[0] % n_data == 0)
        bspec = DATA_AXIS if split else None

        def lookup_local(pyr: Tuple[jnp.ndarray, ...], coords: jnp.ndarray
                         ) -> jnp.ndarray:
            # One shifted coordinate serves every level: level i's local
            # center is (coords - shard·lw_0)/2^i = coords/2^i - shard·lw_i
            # exactly (lw_i = lw_0/2^i by the padding quantum; scaling by a
            # power of two is fp-exact), so the whole pyramid samples in the
            # SINGLE multi-level launch (VMEM-gated) — not one launch per
            # level, which would reintroduce the per-custom-call overhead
            # a training trace showed (kernels/corr_lookup.py).
            shard = lax.axis_index(CORR_AXIS)
            offset = (shard * pyr[0].shape[-2]).astype(coords.dtype)
            out = _kernels.lookup_pyramid_fused(list(pyr), coords - offset,
                                                radius)
            return lax.psum(out.astype(jnp.float32), CORR_AXIS)

        lookup = jax.shard_map(
            lookup_local, mesh=mesh, axis_names=set(mesh.axis_names),
            in_specs=(tuple(P(bspec, None, CORR_AXIS, None)
                            for _ in range(num_levels)), P(bspec)),
            out_specs=P(bspec),
            check_vma=False,
        )
    else:
        def lookup_local(pyr: Tuple[jnp.ndarray, ...], coords: jnp.ndarray
                         ) -> jnp.ndarray:
            shard = lax.axis_index(CORR_AXIS)
            outs = []
            for level, vol in enumerate(pyr):
                offset = (shard * vol.shape[-1]).astype(coords.dtype)
                taps = _window_coords(coords, level, radius) - offset
                outs.append(linear_sampler_1d(vol.astype(jnp.float32), taps))
            # Each global bin is owned by exactly one shard; out-of-shard
            # taps contributed zero, so the cross-shard sum IS the global
            # interpolated window.
            return lax.psum(jnp.concatenate(outs, axis=-1), CORR_AXIS)

        lookup = jax.shard_map(
            lookup_local, mesh=mesh, axis_names={CORR_AXIS},
            in_specs=(tuple(P(None, None, None, CORR_AXIS)
                            for _ in range(num_levels)), P()),
            out_specs=P(),
        )

    def corr_fn(coords: jnp.ndarray) -> jnp.ndarray:
        return lookup(pyramid, coords.astype(jnp.float32))

    return corr_fn
