"""Batch-axis ``shard_map`` for the Pallas kernel entries under a data mesh.

``make_train_step(train_cfg, mesh=...)`` is a plain ``jax.jit`` with
``in_shardings``: XLA's SPMD partitioner splits every ordinary op of the
step over the ``data`` axis by itself, but it cannot look inside a Mosaic
kernel ("Mosaic kernels cannot be automatically partitioned. Please wrap
the call in a shard_map").  The three kernel families (correlation lookup,
no-volume lookup, fused ConvGRU gates) are independent along the batch
axis, so the split is stated for them here: under an active data mesh each
call runs inside a full-manual ``jax.shard_map`` whose batch-leading
operands are split over ``data`` and whose remaining operands (the GRU
gate weights) are replicated — every device launches the kernel on its own
slice of the batch and nothing is gathered.

Same activation pattern as ``corr_sharding`` / ``rows_sharding``: a
context that is entered around the TRACING of the step
(``training/step.make_train_step`` does so itself).  Calls made inside
another executor's manual region (rows_gru's partial-manual loop, the
W2-sharded lookup) are left alone: those regions already say where the
kernel runs.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Optional, Sequence

import jax
from jax.sharding import Mesh, PartitionSpec as P

from raft_stereo_tpu.parallel.mesh import DATA_AXIS

_active_mesh: Optional[Mesh] = None


@contextlib.contextmanager
def data_sharding(mesh: Mesh):
    """Activate ``mesh`` for batch-split kernel calls within the block."""
    global _active_mesh
    if DATA_AXIS not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no {DATA_AXIS!r} axis")
    prev, _active_mesh = _active_mesh, mesh
    try:
        yield mesh
    finally:
        _active_mesh = prev


def over_data_axis(fn: Callable, batched: Sequence, replicated: Sequence = ()):
    """``fn(*batched, *replicated)``, split along the leading axis of every
    ``batched`` leaf over the active data mesh.

    ``batched`` / ``replicated`` are sequences of pytrees; every output of
    ``fn`` must be batch-leading.  Runs ``fn`` directly when no data mesh
    is active, its ``data`` axis has one device, the static batch does not
    divide it (batch-1 init under a mesh), or the call already sits inside
    a manual region."""
    mesh = _active_mesh
    n_data = int(mesh.shape[DATA_AXIS]) if mesh is not None else 1
    if (n_data <= 1
            or any(x.shape[0] % n_data
                   for x in jax.tree_util.tree_leaves(batched))
            or jax.sharding.get_abstract_mesh().manual_axes):
        return fn(*batched, *replicated)
    split = jax.tree_util.tree_map(lambda _: P(DATA_AXIS), tuple(batched))
    whole = jax.tree_util.tree_map(lambda _: P(), tuple(replicated))
    # Full-manual and check_vma=False: the standard pallas + shard_map
    # pattern (parallel/corr_sharded.py's lookup does the same) — the
    # replication checker cannot see through the Pallas primitive.
    return jax.shard_map(
        fn, mesh=mesh, in_specs=split + whole, out_specs=P(DATA_AXIS),
        check_vma=False)(*batched, *replicated)
