"""Test configuration: run all tests on CPU with 8 virtual devices.

Multi-device sharding tests follow SURVEY.md §4's strategy: CPU-backed JAX
standing in for TPU via ``--xla_force_host_platform_device_count``.  The
two environment settings live in tests/_hermetic.py, shared with the
distributed-test subprocess workers.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
# repo root too: tests import the package, chip_smoke.py and benchmark/
# from the checkout, so the suite must resolve them when pytest is invoked
# from any directory.  (The SceneFlow fixture tree, ``build_tree``, lives
# in tests/golden_data.py.)
sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
from _hermetic import force_cpu  # noqa: E402

jax = force_cpu(8)
# No persistent compilation cache in tests: a CPU run would fill the
# in-checkout directory (profiling.setup_compilation_cache) with entries
# the chip tool then copies along with the tree.
jax.config.update("jax_enable_compilation_cache", False)

assert jax.devices()[0].platform == "cpu", "tests must run on CPU"
assert len(jax.devices()) >= 8, "tests expect >= 8 virtual CPU devices"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


def pytest_collection_modifyitems(config, items):
    """Two test tiers (VERDICT round 1 #8): everything not marked ``slow``
    is auto-marked ``quick``, so ``pytest -m quick`` is the <60s regression
    smoke and ``pytest -m slow`` the heavy full-model/sharded tier."""
    for item in items:
        if "slow" not in item.keywords:
            item.add_marker(pytest.mark.quick)
