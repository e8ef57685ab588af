"""Row-sharded (context-parallel) full-resolution encoding.

The long-context analog of sequence parallelism for stereo: at
full-resolution inputs the ENCODER STEM's activations — not the
correlation volume — set peak HBM (PERF.md section 4, full resolution), and
stereo correlation itself is per-image-row, so the image-row (H) axis is
the natural context axis.  This module runs the trunk's full-resolution
segment with H sharded across a mesh axis:

* each device holds 1/N of the full-resolution activations (the memory
  ceiling drops ~linearly in N);
* convolution halos are exchanged ONCE at the input via ``lax.ppermute``
  (neighbor devices trade ``halo`` boundary rows; edge devices receive
  zeros, which the segment's row mask turns into the exact same zero
  padding the full-image convolution sees — models/banded.py `_segment`);
* instance-norm statistics are the only global coupling: per-device masked
  (mean, M2, count) moments are ``all_gather``-ed (a few KB) and combined
  with Chan's parallel-variance formula — the same numerically-stable
  combination the banded executor uses across bands;
* the cheap ≤1/2-resolution tail then runs on the reassembled tensors
  (models/banded.trunk_tail), where XLA is free to keep them sharded.

Composes with the W2-sharded correlation volume (parallel/corr_sharded.py)
for 2-D sharding of the long-context path: rows across one mesh axis,
disparity bins across the other.

Reference parity note: the reference has no multi-device full-res path at
all (its alt backend exists precisely because one GPU cannot hold the
volume — core/corr.py:64-107); this module is capability beyond it.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from raft_stereo_tpu.models.banded import (_HALO, _segment, chan_combine,
                                           masked_moments, trunk_tail)
from raft_stereo_tpu.parallel.mesh import DATA_AXIS

# Halo rows exchanged with each neighbor: must cover the receptive-field
# half-width of the full-resolution segment (stem 7x7 + four 3x3 + the
# stride-2 entry = 8 rows, models/banded._HALO) — 16 gives 2x margin and
# stays stride-2/4-aligned.
DEFAULT_HALO = 2 * _HALO

_active: Optional[Tuple[Mesh, str]] = None


@contextlib.contextmanager
def rows_sharding(mesh: Mesh, axis: str = DATA_AXIS):
    """Activate ``(mesh, axis)`` for row-sharded encoding within the block.

    Wrap the *tracing* of any jitted function whose model config has
    ``rows_shards > 1`` — the same pattern as
    ``parallel.corr_sharded.corr_sharding``; the two compose on one mesh
    (rows over one axis, disparity bins over the other)."""
    global _active
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no {axis!r} axis")
    prev, _active = _active, (mesh, axis)
    try:
        yield mesh
    finally:
        _active = prev


def active_rows_mesh() -> Optional[Tuple[Mesh, str]]:
    return _active


def rows_sharded_trunk_apply(trunk_params, batch_stats, x, norm_fn, dtype,
                             mesh: Mesh, axis: str = DATA_AXIS,
                             halo: int = DEFAULT_HALO):
    """``_Trunk`` (downsample=2) forward with H sharded over ``mesh[axis]``.

    ``x``: (B, H, W, 3) global array; H must be divisible by
    ``4 * mesh.shape[axis]`` (stride-2 stages twice).  Returns the
    1/4-resolution trunk output (B, H/4, W/4, 128), numerically equal to
    the unsharded trunk (tests/test_rows_sharded.py).
    """
    n = mesh.shape[axis]
    b, h, w, _ = x.shape
    if h % (4 * n):
        raise ValueError(f"H={h} must be divisible by 4*n_shards={4 * n}")
    if halo % 4:
        raise ValueError(f"halo={halo} must be divisible by 4")
    slab_h = h // n
    if slab_h < halo:
        # a single ppermute can only supply rows from the ADJACENT slab
        raise ValueError(
            f"per-shard height H/n = {slab_h} is smaller than halo={halo}; "
            f"use fewer shards or a smaller halo (>= {2 * _HALO} rows of "
            f"receptive field are required for exactness)")

    param_specs = jax.tree_util.tree_map(lambda _: P(), (trunk_params,
                                                         batch_stats))

    # Manual only over the rows axis; the batch dim stays AUTOMATIC so the
    # outer jit's data-parallel sharding passes straight through — the same
    # partial-manual pattern as the W2-sharded volume build
    # (parallel/corr_sharded.py) — making this trunk usable inside the
    # data-sharded TRAINING step, not just replicated-batch inference.
    @functools.partial(
        jax.shard_map, mesh=mesh, axis_names={axis},
        in_specs=(param_specs[0], param_specs[1], P(None, axis)),
        out_specs=(P(None, axis), P(None, axis)))
    def segment_sharded(tp, bs, slab):
        idx = jax.lax.axis_index(axis)
        # Neighbor halo exchange.  ppermute zero-fills devices with no
        # source, giving edge devices zero halos — masked below into the
        # exact zero padding the full-image conv sees at image borders.
        down = [(j, j + 1) for j in range(n - 1)]   # send towards larger idx
        up = [(j + 1, j) for j in range(n - 1)]
        from_above = jax.lax.ppermute(slab[:, -halo:], axis, down)
        from_below = jax.lax.ppermute(slab[:, :halo], axis, up)
        haloed = jnp.concatenate([from_above, slab, from_below], axis=1)

        # Global row index of each haloed row; all real here except past
        # the image at the outer devices.
        g = jnp.arange(slab_h + 2 * halo) + idx * slab_h - halo
        in_image = (g >= 0) & (g < h)
        # Rows THIS device owns — stats must count each image row once.
        owned = (g >= idx * slab_h) & (g < (idx + 1) * slab_h)

        # Unlike the banded executor (which streams bands and must RECOMPUTE
        # the segment per stats sweep), every device holds its whole slab —
        # so the segment runs ONCE, pausing at each instance norm for a
        # few-KB cross-device moment exchange supplied as a stats callback.
        stats = []
        if norm_fn == "instance":
            m_own = owned[None, :, None, None]

            def stats(_k, t):
                mean_d, m2_d, cnt = masked_moments(t, m_own, w)
                # tiny per-device moments -> every device sees all of them
                mean, var = chan_combine(
                    jax.lax.all_gather(mean_d, axis),            # (n, B, C)
                    jax.lax.all_gather(m2_d, axis),
                    jax.lax.all_gather(cnt, axis))               # (n,)
                return mean[:, None, None, :], var[:, None, None, :]

        u, v = _segment(tp, bs, haloed, norm_fn, dtype, stats, upto=6,
                        row_mask=in_image)
        crop = slice(halo // 2, halo // 2 + slab_h // 2)
        return u[:, crop], v[:, crop]

    u, v = segment_sharded(trunk_params, batch_stats, x)
    # Re-enter the auto-sharded world.  H stays SHARDED over the rows axis
    # when no other mesh axis is in play (pure context parallelism — the
    # full-resolution-training regime, where the ≤1/2-res tail's backward
    # stores are still O(H) gigabytes); but with a data axis > 1 H is
    # pinned UNSHARDED: XLA's SPMD conv-KERNEL-gradient partitioning
    # double-counts when a conv is sharded over (batch x rows)
    # simultaneously — every tail conv kernel grad came out exactly
    # n_data x with bias/norm grads correct (reproduced on jax 0.9 CPU
    # meshes (2,2)/(2,4); clean on (1,2) and (2,1)).
    from jax.sharding import NamedSharding
    unconstr = P.UNCONSTRAINED
    n_other = mesh.devices.size // mesh.shape[axis]
    h_spec = axis if n_other == 1 else None
    spec = NamedSharding(mesh, P(unconstr, h_spec, unconstr, unconstr))
    u = jax.lax.with_sharding_constraint(u, spec)
    v = jax.lax.with_sharding_constraint(v, spec)
    # <=1/2-res tail on the reassembled tensors (instance norms here see
    # the full tensors, so no further collectives are needed by hand).
    return trunk_tail(trunk_params, batch_stats, u, v, norm_fn, dtype)
