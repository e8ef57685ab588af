"""Force CPU-only jax, with virtual devices, in THIS process.

Shared by tests/conftest.py, the subprocess workers
(distributed_worker.py) and the CPU smokes under scripts/: one copy of the
two environment settings so the call sites cannot drift.  Both are read
when jax initialises its backend, so call this before anything touches a
device.
"""

import os
import re


def force_cpu(n_devices: int = 8):
    """CPU backend with ``n_devices`` virtual devices; returns jax.

    Replaces (not merely appends) any inherited device-count flag — a
    subprocess worker spawned from the 8-device test process must get ITS
    requested count."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()

    import jax

    # Also for a caller that imported jax first: the config latched
    # JAX_PLATFORMS at import, the backend is not initialised yet.
    jax.config.update("jax_platforms", "cpu")
    return jax
