"""Multi-host (DCN) distributed runtime.

The reference's only parallelism is single-process ``nn.DataParallel``
(reference: train_stereo.py:134) — no NCCL/MPI process groups exist there.
This module is the TPU-native communication backend that *replaces* that
stack: one jax process per host, ``jax.distributed.initialize`` over DCN,
and after that every collective (gradient psum, corr-shard psum) is an XLA
collective riding ICI within a slice and DCN across slices.  Nothing else
in the framework changes — the SPMD train step (training/step.py) and the
``(data, corr)`` mesh (parallel/mesh.py) are already global-view; this
module only supplies process bootstrap and per-process data sharding.

Usage (same program on every host):

    from raft_stereo_tpu.parallel import distributed
    distributed.initialize()            # no-op in single-process runs
    mesh = make_mesh()                  # spans ALL hosts' devices
    loader = StereoLoader(ds, batch_size=global_batch,
                          **distributed.loader_shard_kwargs())
    batch = shard_batch(local_batch, mesh)   # assembles the global array

On Cloud TPU, ``initialize()`` autodetects coordinator/process topology
from the TPU metadata; elsewhere set ``coordinator_address`` /
``num_processes`` / ``process_id`` explicitly (or the standard
``JAX_COORDINATOR_ADDRESS`` etc. environment variables).
"""

from __future__ import annotations

import logging
import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

log = logging.getLogger(__name__)

_initialized = False

# Environment markers of a multi-process topology jax can auto-detect
# (explicit coordinator env, Cloud TPU pod workers, SLURM/OpenMPI ranks).
# Checked WITHOUT touching any jax API: jax.distributed.initialize must run
# before the first device query latches the backend, so the guard must not
# query jax itself.
_TOPOLOGY_ENV = ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
                 "MEGASCALE_COORDINATOR_ADDRESS")


def _env_topology_present() -> bool:
    if any(os.environ.get(k) for k in _TOPOLOGY_ENV):
        return True
    # A TPU pod lists MULTIPLE workers (comma-separated); a single hostname
    # is just a 1-worker slice and needs no process group.
    if "," in os.environ.get("TPU_WORKER_HOSTNAMES", ""):
        return True
    for k in ("SLURM_NTASKS", "OMPI_COMM_WORLD_SIZE"):
        try:
            if int(os.environ.get(k, "1")) > 1:
                return True
        except ValueError:
            pass
    return False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Bootstrap multi-process jax; safe to call in single-process runs.

    Must run before the first device query in the process (jax latches the
    backend on first use) — which is why the single-process guard inspects
    only the environment, never jax state.  Idempotent."""
    global _initialized
    if _initialized or jax.distributed.is_initialized():
        _initialized = True
        return
    if (coordinator_address is None and num_processes is None
            and process_id is None and not _env_topology_present()):
        # Plain single-process run with no detectable topology: nothing to
        # do, and calling jax.distributed.initialize would fail.
        _initialized = True
        return
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    _initialized = True
    log.info("distributed: process %d/%d, %d local of %d global devices",
             jax.process_index(), jax.process_count(),
             jax.local_device_count(), jax.device_count())


def any_process(flag: bool) -> bool:
    """Global OR of a per-process bool.

    This is a COLLECTIVE in multi-process runs — every process must call it
    the same number of times.  The train loop calls it once per loop
    iteration and folds its own loader's exhaustion into ``flag``, so the
    invariant survives sharded loaders of UNEQUAL length: every process
    keeps entering the collective until the global OR fires, then all break
    together at the earliest exhaustion.  It coordinates the preemption
    stop: a SIGTERM landing on one host (or at
    different step boundaries on different hosts) must make EVERY process
    break the loop at the same step, or the processes that kept going would
    dispatch step collectives while the stopping one enters the collective
    checkpoint save — distributed deadlock (the maxtext/t5x
    reached-preemption-sync-point pattern)."""
    if jax.process_count() == 1:
        return bool(flag)
    from jax.experimental import multihost_utils

    flags = multihost_utils.process_allgather(np.asarray(flag, np.int32))
    return bool(np.max(flags))


def local_devices_stable() -> List[jax.Device]:
    """This process's devices in a STABLE, process-independent order.

    ``jax.local_devices()`` order is backend-defined; everything that
    assigns work to devices by index — the serving engine's worker pool,
    xl mesh groups, the multi-host loader slicing contract in
    ``parallel/mesh.py`` — must agree on ONE ordering or two components
    on the same host can claim overlapping devices.  Sorting by device id
    makes the order a pure function of the topology."""
    return sorted(jax.local_devices(), key=lambda d: d.id)


def device_groups(group_size: int, n_groups: Optional[int] = None,
                  devices: Optional[Sequence[jax.Device]] = None,
                  skip: int = 0) -> List[Tuple[jax.Device, ...]]:
    """Partition local devices into DISJOINT ordered groups of
    ``group_size`` — the one helper the serving engine and the parallel
    runtime share for device discovery (an engine worker owns one group;
    an xl mesh group owns ``rows*corr`` devices).

    Args:
      group_size: devices per group (a solo worker is a 1-group; an xl
        ``rows=2,corr=2`` mesh is a 4-group).
      n_groups: how many groups to return; None = as many as fit.
      devices: explicit device list (default ``local_devices_stable()``).
      skip: leading devices to leave unassigned (e.g. the engine's solo
        workers occupy the head of the list; xl groups start after them).

    Returns the groups, each a tuple in stable order.  Returns an EMPTY
    list — never raises — when the devices cannot supply ``n_groups``
    full groups: the caller decides whether that is fatal (a declared
    data_parallel) or a typed skip (a replica without enough devices for
    the fleet's xl mesh, tools/compile_farm.py)."""
    if group_size < 1:
        raise ValueError(f"group_size={group_size} must be >= 1")
    if skip < 0:
        raise ValueError(f"skip={skip} must be >= 0")
    if devices is None:
        devices = local_devices_stable()
    pool = list(devices)[skip:]
    n_avail = len(pool) // group_size
    want = n_avail if n_groups is None else int(n_groups)
    if want < 0 or want > n_avail:
        return []
    return [tuple(pool[i * group_size:(i + 1) * group_size])
            for i in range(want)]


def loader_shard_kwargs() -> Dict[str, int]:
    """Per-process data-sharding kwargs for ``StereoLoader``: each process
    decodes only its contiguous slice of every global batch (the loader
    validates divisibility)."""
    return {"process_index": jax.process_index(),
            "process_count": jax.process_count()}
