"""Compiler-cost & efficiency layer: what the compiled programs SHOULD cost.

PR 3/4 made measured time observable (latency histograms, span traces,
anomaly watchdogs).  This module adds the model-side denominator: every jit
compile point can route through the AOT path (``jit(...).lower(...).
compile()``) so the registry records, per executable, what XLA itself says
the program costs — ``cost_analysis()`` flops / bytes accessed and
``memory_analysis()`` argument/output/temp/generated-code bytes — plus the
compile wall time.  RAFT-Stereo's fixed-iteration GRU loop makes device
time a pure function of the padded shape (PAPER.md; serving buckets by it,
serving/batcher.py), so measured-vs-required gaps are fully attributable to
padding waste and hardware underutilization; with these records the gap
becomes a number:

* **MFU** (model FLOP utilization, Chowdhery et al., *PaLM*, 2022):
  achieved FLOP/s = executable flops x dispatches / measured seconds,
  divided by the device's peak (``DEVICE_PEAK_TFLOPS`` auto table, or a
  ``--device_peak_tflops`` override).
* **Arithmetic intensity / roofline**: flops / bytes-accessed against the
  device ridge point classifies an executable compute- vs
  memory-bound.
* **`GET /debug/compiles`**: the executable inventory as JSON on both HTTP
  endpoints (telemetry/http.py ``handle_debug_get``).

Degradation contract: a backend that returns nothing from
``cost_analysis``/``memory_analysis`` (or raises — exotic
plugins) yields a compile-time-only record with ``degraded=True``; the
DISPATCH path never errors because of cost accounting, and when no
``CompileRegistry`` is attached at all the callers keep their exact
pre-existing ``jax.jit`` dispatch (tests pin both properties).
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from raft_stereo_tpu.telemetry.registry import Gauge, MetricsRegistry

log = logging.getLogger(__name__)

# Dense peak FLOP/s per chip (bf16 unless the device only does fp32) for
# devices this repo plausibly meets.  Matching is lowercase-substring over
# ``device_kind`` in ORDER — more specific entries first ("tpu v5 lite"
# must win over "tpu v5").  Values are vendor-published peaks; MFU against
# them is the standard (conservative) convention.
DEVICE_PEAK_TFLOPS: "collections.OrderedDict[str, float]" = (
    collections.OrderedDict([
        ("tpu v5 lite", 197.0), ("tpu v5e", 197.0), ("tpu v5p", 459.0),
        ("tpu v6 lite", 918.0), ("tpu v6e", 918.0),
        ("tpu v4", 275.0), ("tpu v3", 123.0), ("tpu v2", 46.0),
        ("h100", 989.0), ("a100", 312.0),
    ]))

# HBM bandwidth (GB/s per chip), same matching rules — the other roofline
# axis.  ridge point = peak_flops / peak_bytes_per_s.
DEVICE_PEAK_GBPS: "collections.OrderedDict[str, float]" = (
    collections.OrderedDict([
        ("tpu v5 lite", 819.0), ("tpu v5e", 819.0), ("tpu v5p", 2765.0),
        ("tpu v6 lite", 1640.0), ("tpu v6e", 1640.0),
        ("tpu v4", 1228.0), ("tpu v3", 900.0), ("tpu v2", 700.0),
        ("h100", 3350.0), ("a100", 2039.0),
    ]))


def _local_device_kind() -> str:
    try:
        import jax
        return str(getattr(jax.devices()[0], "device_kind", ""))
    except Exception:  # pragma: no cover - backend init failure
        return ""


def _lookup(table: "collections.OrderedDict[str, float]",
            device_kind: Optional[str]) -> Optional[float]:
    kind = (device_kind if device_kind is not None
            else _local_device_kind()).lower()
    for needle, value in table.items():
        if needle in kind:
            return value
    if "tpu" in kind:
        # A chip this repo runs on but has no peak for is a hole in the
        # table, not a reason to report against another chip's roofline.
        raise ValueError(
            f"device_kind {kind!r} is not in telemetry/costs.py's peak "
            f"tables; add its published peaks there")
    return None


def peak_flops_for(device_kind: Optional[str] = None,
                   override_tflops: Optional[float] = None
                   ) -> Optional[float]:
    """Peak FLOP/s for MFU's denominator: the override wins, then the auto
    table keyed by ``device_kind`` (default: local device 0).  None for a
    device with no published peak here (the CPU: MFU is then "not
    measured", the gauges stay 0); a TPU missing from the table raises."""
    if override_tflops is not None:
        return float(override_tflops) * 1e12
    peak = _lookup(DEVICE_PEAK_TFLOPS, device_kind)
    return None if peak is None else peak * 1e12


def peak_bytes_per_s_for(device_kind: Optional[str] = None,
                         override_gbps: Optional[float] = None
                         ) -> Optional[float]:
    """Peak memory bytes/s (roofline's other axis); None when unknown."""
    if override_gbps is not None:
        return float(override_gbps) * 1e9
    peak = _lookup(DEVICE_PEAK_GBPS, device_kind)
    return None if peak is None else peak * 1e9


def ridge_flops_per_byte(peak_flops: Optional[float],
                         peak_bytes_per_s: Optional[float]
                         ) -> Tuple[Optional[float], str]:
    """The roofline ridge point and where it came from: ``(ridge,
    "device")``, or ``(None, "unknown")`` on a device without published
    peaks — never another chip's ridge."""
    if peak_flops and peak_bytes_per_s:
        return peak_flops / peak_bytes_per_s, "device"
    return None, "unknown"


def classify_bound(flops: Optional[float], bytes_accessed: Optional[float],
                   ridge: Optional[float]) -> str:
    """Roofline classification: arithmetic intensity vs the ridge point."""
    if not flops or not bytes_accessed or not ridge:
        return "unknown"
    return "compute" if flops / bytes_accessed >= ridge else "memory"


# ------------------------------------------------------------------ records
@dataclasses.dataclass
class CompileRecord:
    """One compiled executable's cost card."""

    key: str                 # stable label, e.g. "serve.forward(64x96,b1)"
    site: str                # "eval" | "serving" | "train" | "bench"
    compile_s: float
    created_unix: float
    device: str = ""
    # Registered-model coordinate ("name@version") this executable was
    # compiled for; None for the engine's implicit model and every
    # non-serving site.  First-class field (not just embedded in the key
    # string) so /debug/compiles consumers and cost_report.py can group
    # by it without parsing keys.
    model: Optional[str] = None
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    transcendentals: Optional[float] = None
    memory: Optional[Dict[str, int]] = None   # memory_analysis byte fields
    degraded: bool = False   # cost/memory analysis unavailable

    @property
    def arithmetic_intensity(self) -> Optional[float]:
        if not self.flops or not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed

    @property
    def donated_alias_bytes(self) -> Optional[int]:
        """Bytes of output the executable writes into donated input
        buffers (``memory_analysis.alias_size_in_bytes``) — the HBM the
        donation actually saved.  0 means donation was declared but no
        output matched a donated buffer's size; None when the analysis
        degraded."""
        if self.memory is None:
            return None
        return self.memory.get("alias_size_in_bytes")

    @property
    def hbm_bytes(self) -> Optional[int]:
        """The executable's live HBM footprint: arguments + outputs +
        temporaries, net of donated-input aliasing (aliased outputs reuse
        argument memory instead of allocating their own)."""
        if self.memory is None:
            return None
        total = sum(self.memory.get(f, 0)
                    for f in ("argument_size_in_bytes",
                              "output_size_in_bytes",
                              "temp_size_in_bytes"))
        return total - self.memory.get("alias_size_in_bytes", 0)

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["arithmetic_intensity"] = self.arithmetic_intensity
        d["hbm_bytes"] = self.hbm_bytes
        return d


_MEMORY_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes")


def executable_cost(compiled) -> Dict[str, Any]:
    """Extract flops/bytes/memory from a ``jax.stages.Compiled`` (or
    anything quacking like one), degrading field-by-field: an analysis that
    raises or returns nothing leaves its fields None and flips
    ``degraded`` — never an exception (the satellite contract: cost
    analysis must not break the dispatch path)."""
    out: Dict[str, Any] = {"flops": None, "bytes_accessed": None,
                           "transcendentals": None, "memory": None,
                           "degraded": False}
    try:
        cost = dict(compiled.cost_analysis() or {})
    except Exception:
        cost = {}
    if cost:
        for field, key in (("flops", "flops"),
                           ("bytes_accessed", "bytes accessed"),
                           ("transcendentals", "transcendentals")):
            v = cost.get(key)
            if v is not None:
                try:
                    out[field] = float(v)
                except (TypeError, ValueError):
                    pass
    try:
        mem = compiled.memory_analysis()
        memory = {f: int(getattr(mem, f)) for f in _MEMORY_FIELDS
                  if getattr(mem, f, None) is not None}
        out["memory"] = memory or None
    except Exception:
        out["memory"] = None
    out["degraded"] = out["flops"] is None or out["memory"] is None
    return out


# ----------------------------------------------------------------- registry
class CompileRegistry:
    """Instruments every AOT compile it is handed: per-executable cost
    records (bounded, oldest evicted), compile counters/histograms on an
    optional shared ``MetricsRegistry``, compile run-events on an optional
    ``EventLog``, and the runner compile-cache eviction telemetry
    (eval/runner.py reports into it).

    The registry is passive: callers opt in by wrapping their jitted
    callables with ``instrument`` (or calling ``aot_compile`` directly).
    No registry attached anywhere == the exact pre-existing jit dispatch.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 events=None,
                 device_peak_tflops: Optional[float] = None,
                 max_records: int = 256):
        if max_records < 1:
            raise ValueError(f"max_records={max_records} must be >= 1")
        self.events = events
        self.max_records = max_records
        self.peak_flops = peak_flops_for(override_tflops=device_peak_tflops)
        self._lock = threading.Lock()
        # key -> latest record for that compile point; insertion-ordered so
        # the bound evicts oldest-compiled first.
        self._records: "collections.OrderedDict[str, CompileRecord]" = (
            collections.OrderedDict())
        self._evictions = 0
        self._total_compile_s = 0.0
        self.metrics = registry
        if registry is not None:
            self.compiles = registry.counter(
                "compiles_total",
                "XLA executables built through the AOT cost registry")
            self.compile_seconds = registry.histogram(
                "compile_seconds", "per-executable compile wall time")
            self.executables = registry.gauge(
                "compile_executables", "cost records currently held")
            self.runner_evictions = registry.counter(
                "runner_compile_evictions_total",
                "InferenceRunner per-shape executables evicted "
                "(oldest-first past max_cached_shapes)")
            self.runner_cache_size = registry.gauge(
                "runner_compile_cache_size",
                "entries in the reporting runner's per-shape compile cache")
            if self.peak_flops:
                registry.gauge(
                    "device_peak_flops_per_s",
                    "peak FLOP/s used as the MFU denominator "
                    "(auto table or --device_peak_tflops)"
                ).set(self.peak_flops)
        else:
            self.compiles = self.compile_seconds = None
            self.executables = self.runner_evictions = None
            self.runner_cache_size = None

    # ------------------------------------------------------------ recording
    def record(self, key: str, site: str, compile_s: float,
               compiled=None, device: str = "",
               model: Optional[str] = None) -> CompileRecord:
        """Record one compiled executable (``compiled`` may be None — e.g.
        a compile observed but not AOT-captured: compile-time-only
        record).  ``model`` is the registered-model coordinate
        (``name@version``) for multi-model serving sites; None
        everywhere else."""
        fields = (executable_cost(compiled) if compiled is not None
                  else {"degraded": True})
        rec = CompileRecord(
            key=key, site=site, compile_s=compile_s,
            created_unix=time.time(),
            device=device or _local_device_kind(),
            model=model,
            flops=fields.get("flops"),
            bytes_accessed=fields.get("bytes_accessed"),
            transcendentals=fields.get("transcendentals"),
            memory=fields.get("memory"),
            degraded=bool(fields.get("degraded", True)))
        with self._lock:
            self._records.pop(key, None)  # re-compile: latest record wins
            self._records[key] = rec
            while len(self._records) > self.max_records:
                self._records.popitem(last=False)
                self._evictions += 1
            n = len(self._records)
            self._total_compile_s += compile_s
        if self.compiles is not None:
            self.compiles.inc()
            self.compile_seconds.observe(compile_s)
            self.executables.set(n)
        if self.events is not None:
            self.events.emit(
                "compile", site=site, key=key,
                compile_s=round(compile_s, 4), flops=rec.flops,
                bytes_accessed=rec.bytes_accessed, memory=rec.memory,
                degraded=rec.degraded, device=rec.device,
                **({"model": model} if model is not None else {}))
        return rec

    def aot_compile(self, jitted, *args, key: str, site: str,
                    model: Optional[str] = None, **kwargs):
        """``jitted.lower(*args).compile()`` with the compile recorded.
        Returns the compiled executable, or ``jitted`` itself (and a
        degraded record) when the AOT path is unavailable — the caller can
        always just call the return value."""
        t0 = time.perf_counter()
        try:
            compiled = jitted.lower(*args, **kwargs).compile()
        except Exception:
            log.warning("AOT compile of %s failed; falling back to plain "
                        "jit dispatch (compile-time-only record)", key,
                        exc_info=True)
            self.record(key, site, time.perf_counter() - t0, compiled=None,
                        model=model)
            return jitted
        self.record(key, site, time.perf_counter() - t0, compiled=compiled,
                    model=model)
        return compiled

    def instrument(self, jitted, key: str, site: str,
                   model: Optional[str] = None) -> "_InstrumentedFn":
        """Wrap a jitted callable so its compiles run through the AOT path
        and land in this registry.  Same call signature, same results."""
        return _InstrumentedFn(self, jitted, key, site, model=model)

    # -------------------------------------------------------------- queries
    def get(self, key: str) -> Optional[CompileRecord]:
        with self._lock:
            return self._records.get(key)

    def records(self) -> List[CompileRecord]:
        with self._lock:
            return list(self._records.values())

    def to_json(self) -> Dict[str, Any]:
        """The ``GET /debug/compiles`` payload: executable inventory plus
        the registry's own counters."""
        with self._lock:
            records = [r.to_dict() for r in self._records.values()]
            evictions = self._evictions
            total_s = self._total_compile_s
        return {
            "executables": records,
            "count": len(records),
            "record_evictions": evictions,
            "total_compile_s": round(total_s, 4),
            "peak_flops_per_s": self.peak_flops,
        }

    # ------------------------------------------- runner cache telemetry
    def note_runner_eviction(self, evicted_key: str, cache_size: int) -> None:
        """eval/runner.py reports each compile-cache eviction here (the
        record for the evicted executable stays in ``records()`` — the
        inventory is history, the runner cache is workingset)."""
        if self.runner_evictions is not None:
            self.runner_evictions.inc()
            self.runner_cache_size.set(cache_size)

    def note_runner_cache_size(self, cache_size: int) -> None:
        if self.runner_cache_size is not None:
            self.runner_cache_size.set(cache_size)


def _signature(args, kwargs) -> Tuple:
    """Shape/dtype signature of a call's pytree leaves (the executable
    compatibility key for re-lowering on input change)."""
    import jax
    return tuple(
        (getattr(x, "shape", None), str(getattr(x, "dtype", type(x))))
        for x in jax.tree_util.tree_leaves((args, kwargs)))


# Executable variants kept per instrumented callable; real callers see one
# signature per compile point (the runner keys by padded shape already,
# the train step by construction), so this only guards pathological
# alternating-dtype clients from unbounded growth.
_MAX_VARIANTS = 8


class _InstrumentedFn:
    """AOT-compiled stand-in for a jitted callable.

    First call lowers + compiles through the registry; later calls hit the
    cached executable directly.  A shape/dtype change re-lowers (and
    records — which is exactly the recompile you want on the books); any
    failure of the AOT machinery falls back to the plain jitted callable,
    so instrumentation can slow a call down but never fail it.
    """

    def __init__(self, registry: CompileRegistry, jitted, key: str,
                 site: str, model: Optional[str] = None):
        self._registry = registry
        self._jitted = jitted
        self.key = key
        self.site = site
        self.model = model
        self._lock = threading.Lock()
        self._last = None
        self._by_sig: "collections.OrderedDict[Tuple, Any]" = (
            collections.OrderedDict())

    def __call__(self, *args, **kwargs):
        exe = self._last
        if exe is not None:
            try:
                return exe(*args, **kwargs)
            except TypeError:
                # signature drift (new shapes/dtypes): re-resolve below.
                # jax validates avals BEFORE executing (and before any
                # donation), so falling through here is safe.
                pass
        sig = _signature(args, kwargs)
        with self._lock:
            exe = self._by_sig.get(sig)
        if exe is None:
            exe = self._registry.aot_compile(self._jitted, *args,
                                             key=self.key, site=self.site,
                                             model=self.model, **kwargs)
            with self._lock:
                self._by_sig[sig] = exe
                while len(self._by_sig) > _MAX_VARIANTS:
                    self._by_sig.popitem(last=False)
        self._last = exe
        return exe(*args, **kwargs)


# ---------------------------------------------------------------------- MFU
class MfuMeter:
    """Rolling-window achieved-FLOP/s meter feeding an MFU gauge.

    ``note(flops)`` records each dispatch's model flops; the gauge becomes
    ``flops-in-window / elapsed / peak``.  With no known peak the gauge
    stays 0 — an unknown denominator must not masquerade as utilization.
    An optional second gauge receives the raw achieved FLOP/s (useful even
    without a peak).
    """

    def __init__(self, gauge: Gauge, peak_flops: Optional[float],
                 achieved_gauge: Optional[Gauge] = None,
                 window_s: float = 60.0):
        self.gauge = gauge
        self.achieved_gauge = achieved_gauge
        self.peak_flops = peak_flops
        self.window_s = window_s
        self._lock = threading.Lock()
        self._samples: "collections.deque[Tuple[float, float]]" = (
            collections.deque())
        self._t0: Optional[float] = None

    def note(self, flops: float, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        with self._lock:
            if self._t0 is None:
                self._t0 = now
            self._samples.append((now, float(flops)))
            horizon = now - self.window_s
            while self._samples and self._samples[0][0] < horizon:
                self._samples.popleft()
            total = sum(f for _, f in self._samples)
            elapsed = min(self.window_s, now - self._t0)
        achieved = total / elapsed if elapsed > 0 else 0.0
        if self.achieved_gauge is not None:
            self.achieved_gauge.set(achieved)
        if self.peak_flops:
            self.gauge.set(achieved / self.peak_flops)
