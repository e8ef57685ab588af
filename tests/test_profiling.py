"""Profiling subsystem (raft_stereo_tpu/profiling.py) on the CPU backend."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_stereo_tpu import profiling


def test_fps_protocol_warmup_discard():
    proto = profiling.FpsProtocol(warmup=2)
    calls = []

    def fn(x):
        calls.append(x)
        return jnp.asarray(x)

    res = proto.measure(fn, [(i,) for i in range(7)])
    assert len(calls) == 7
    assert res.n_timed == 5  # first 2 discarded
    assert res.fps == pytest.approx(1.0 / res.mean_s)
    assert "fps" in str(res)


def test_fps_protocol_needs_more_than_warmup():
    proto = profiling.FpsProtocol(warmup=50)
    with pytest.raises(ValueError, match="warmup"):
        proto.measure(lambda x: x, [(0,), (1,)])


def test_trace_writes_profile(tmp_path):
    d = str(tmp_path / "prof")
    with profiling.trace(d):
        with profiling.annotate("matmul-span"):
            x = jnp.ones((64, 64))
            jax.block_until_ready(jax.jit(lambda a: a @ a)(x))
    found = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
    assert found, "profiler trace produced no files"


def test_device_memory_stats_dict():
    stats = profiling.device_memory_stats()
    assert isinstance(stats, dict)  # CPU backend may legitimately report {}


class _FakeDevice:
    """Stands in for a jax.Device with a controllable memory_stats."""

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_device_memory_stats_backend_fallbacks():
    """Backends without memory stats (CPU) report None — the helper must
    degrade to {} and never raise; backends with stats pass them through."""
    assert profiling.device_memory_stats(_FakeDevice(None)) == {}
    assert profiling.device_memory_stats(
        _FakeDevice({"bytes_in_use": 7})) == {"bytes_in_use": 7}
    # a device object without the method at all (exotic backend plugin)
    assert profiling.device_memory_stats(object()) == {}


def test_device_hbm_bytes_cpu_fallback(monkeypatch):
    """device_hbm_bytes feeds the memory-derived full-res gates; on a
    backend with no bytes_limit it must return the caller's fallback, and
    with one it must return the reported capacity."""
    monkeypatch.setattr(profiling, "device_memory_stats", lambda: {})
    assert profiling.device_hbm_bytes(fallback=123) == 123
    monkeypatch.setattr(profiling, "device_memory_stats",
                        lambda: {"bytes_limit": 0})
    assert profiling.device_hbm_bytes(fallback=456) == 456
    monkeypatch.setattr(profiling, "device_memory_stats",
                        lambda: {"bytes_limit": 32 * 2 ** 30})
    assert profiling.device_hbm_bytes(fallback=456) == 32 * 2 ** 30


def test_annotate_names_traced_ops():
    """annotate() is also an XLA op-name scope: ops staged inside the block
    carry the phase name, so device traces break out the model's phases
    (fnet/cnet/corr_pyramid/gru_iter/upsample)."""
    def f(x):
        with profiling.annotate("myphase"):
            return x * 2.0

    ir = jax.jit(f).lower(jnp.ones((4,))).compiler_ir("stablehlo")
    # scope names live in the MLIR location info, which XLA turns into the
    # op metadata that device traces display
    assert "myphase" in ir.operation.get_asm(enable_debug_info=True)


def test_annotate_nesting_composes_scopes():
    """Nested annotate() blocks compose their named scopes in the traced
    graph — ops staged in the inner block carry "outer/inner", so device
    traces keep the phase hierarchy (e.g. gru_iter wrapping the fused-GRU
    kernel's own span)."""
    def f(x):
        with profiling.annotate("outer"):
            y = x + 1.0
            with profiling.annotate("inner"):
                y = y * 2.0
        return y

    ir = jax.jit(f).lower(jnp.ones((4,))).compiler_ir("stablehlo")
    asm = ir.operation.get_asm(enable_debug_info=True)
    assert "outer/inner" in asm  # composed scope on the inner op
    # host-side nesting works too (TraceAnnotation enters/exits cleanly)
    with profiling.annotate("outer"):
        with profiling.annotate("inner"):
            pass


# ------------------------------------------------- the compile-cache rule
@pytest.fixture
def _restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    before_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      before_min)


def test_compile_cache_goes_to_a_fixed_path_in_the_checkout(
        monkeypatch, _restore_cache_dir):
    monkeypatch.delenv(profiling.COMPILE_CACHE_ENV, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert profiling.setup_compilation_cache() == os.path.join(
        repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        repo, ".jax_cache")
    # the same path every time: a pid, a time or a temp name would never hit
    assert profiling.setup_compilation_cache() == os.path.join(
        repo, ".jax_cache")


def test_compile_cache_yields_to_the_environment(monkeypatch, tmp_path,
                                                 _restore_cache_dir):
    """With JAX_COMPILATION_CACHE_DIR set no code names another directory:
    neither the helper nor serving's --executable_cache_dir hook."""
    from raft_stereo_tpu.serving.persist import (
        enable_persistent_compilation_cache)

    jax.config.update("jax_compilation_cache_dir", "/from/the/environment")
    monkeypatch.setenv(profiling.COMPILE_CACHE_ENV, "/from/the/environment")
    assert profiling.setup_compilation_cache() == "/from/the/environment"
    enable_persistent_compilation_cache(str(tmp_path / "store"))
    assert jax.config.jax_compilation_cache_dir == "/from/the/environment"
    monkeypatch.delenv(profiling.COMPILE_CACHE_ENV)
    enable_persistent_compilation_cache(str(tmp_path / "store"))
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "store")


def test_no_other_site_names_a_cache_directory():
    """One rule, one helper: outside profiling.py and serving/persist.py
    nothing in the tree touches jax_compilation_cache_dir."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    allowed = {os.path.join("raft_stereo_tpu", "profiling.py"),
               os.path.join("raft_stereo_tpu", "serving", "persist.py"),
               os.path.join("tests", "test_profiling.py")}
    hits = []
    for root, dirs, files in os.walk(repo):
        dirs[:] = [d for d in dirs if not d.startswith((".", "_"))
                   and d != "chiprun_out"]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path, errors="replace") as f:
                if "jax_compilation_cache_dir" in f.read():
                    hits.append(os.path.relpath(path, repo))
    assert set(hits) <= allowed, hits
