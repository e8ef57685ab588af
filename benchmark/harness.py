"""What every cell's run shares: finding the cell's files by name, the chip
check, the table of peaks, the compile-cache rule, percentiles, and the one
result line.

Nothing here imports jax at module level: the serving cells' parent is the
load generator and must never hold the chip.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
import time
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
WORK_ROOT = os.path.join(ROOT, ".bench_work")
T_PROCESS_START = time.monotonic()


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, bad files): exit non-zero,
    print no result line."""


def load_json(*parts: str) -> dict:
    path = os.path.join(BENCH_DIR, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise BenchError(f"{path}: not found") from e


def load_cell(workload_name: str) -> dict:
    """The cell as ``BENCHMARK.json`` names it, with its configuration and
    traffic files found by name and its metrics split by kind."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload_name not in cells:
        raise BenchError(f"no workload {workload_name!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[workload_name]
    config = load_json("configs", cell["config"] + ".json")
    workload = load_json("workloads", workload_name + ".json")

    def reported_here(metric: dict) -> bool:
        return workload_name in metric.get("workloads", [workload_name])

    return {
        "name": workload_name, "chips": cell["chips"], "config": config,
        "workload": workload,
        "end_to_end": [m for m in bench["end_to_end"] if reported_here(m)],
        "per_layer": [m for m in bench["per_layer"] if reported_here(m)],
    }


@dataclasses.dataclass(frozen=True)
class TestRig:
    """What only the benchmark's own tests, rehearsal and control may change
    about a run.  It reaches an entry as an argument of its own: ``run.py``'s
    command line builds none, and no key of a configuration's or a workload's
    file is read in its place, so no cell can carry one.

    ``sizes`` replaces keys of the workload's file (a tiny size).
    ``require_accelerator`` false lets the run past the look for a chip, and
    the trace's reduction past a trace with no device plane; ``device_kind``
    then names the table row whose peaks the readers are rehearsed with.
    ``child_patch`` (``module:function``) is called in the serving child to
    break the timed path; ``program_overrides`` and ``env`` switch the
    program's own lower-precision path on (the control)."""
    __test__ = False            # not a test class, whatever pytest thinks

    sizes: dict = dataclasses.field(default_factory=dict)
    require_accelerator: bool = True
    device_kind: Optional[str] = None
    child_patch: Optional[str] = None
    program_overrides: dict = dataclasses.field(default_factory=dict)
    env: dict = dataclasses.field(default_factory=dict)

    def resized(self, cell: dict) -> dict:
        if not self.sizes:
            return cell
        return dict(cell, workload=_merged(cell["workload"], self.sizes))


NO_RIG = TestRig()


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = (_merged(base[k], v)
                  if isinstance(v, dict) and isinstance(base.get(k), dict)
                  else v)
    return out


# ------------------------------------------------------------------ device
def peaks_for(device_kind: str) -> dict:
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"benchmark/peaks.json ({sorted(table)}): add its "
                         f"published peaks with their source")
    return table[device_kind]


def require_chips(want: int, require_accelerator: bool = True) -> dict:
    """The device as jax reports it, or ``BenchError`` where there is no
    accelerator or not the chips the cell asks for.  Imports jax: call it
    only in the process that is to hold the chip."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if require_accelerator:
        if d0.platform == "cpu":
            raise BenchError(f"jax found no accelerator (platform "
                             f"{d0.platform!r}); the benchmark measures on "
                             f"the chip only")
        if len(devices) < want:
            raise BenchError(f"the cell asks for {want} chip(s), jax found "
                             f"{len(devices)}")
        peaks_for(d0.device_kind)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def cache_env(env: Optional[dict] = None) -> dict:
    """The environment a child that compiles gets: jax's persistent cache at
    the place the machine names, else ``<checkout>/.jax_cache`` (a fixed
    path: it is part of the cache's key), and every program cached however
    short its compile, so a second run compiles nothing."""
    env = dict(os.environ if env is None else env)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.setdefault("TPU_LOG_DIR", "disabled")
    return env


def use_cache_in_process() -> str:
    """The same rule for a process that compiles itself."""
    env = cache_env()
    for k in ("JAX_COMPILATION_CACHE_DIR",
              "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
              "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "TPU_LOG_DIR"):
        os.environ.setdefault(k, env[k])
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def work_dir(cell_name: str) -> str:
    """A fixed scratch directory of this cell inside the checkout, emptied
    at the start of each run (so a run leaves at most one run's files)."""
    import shutil

    path = os.path.join(WORK_ROOT, cell_name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


# -------------------------------------------------------------- arithmetic
def percentile(samples: List[float], q: float, attempted: int) -> float:
    """The q-th percentile (0..100) over ALL ``attempted`` requests: one
    that failed, was shed or never answered counts as slower than any
    answer, so a percentile that reaches into the missing ones is inf."""
    if attempted <= 0:
        raise ValueError("no request attempted")
    if len(samples) > attempted:
        raise ValueError("more samples than requests attempted")
    ordered = sorted(samples)
    rank = max(0, math.ceil(q / 100.0 * attempted) - 1)
    return ordered[rank] if rank < len(ordered) else math.inf


def metric_entry(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


# ----------------------------------------------------------------- readers
def read_per_layer(cell: dict, observed: dict) -> Dict[str, dict]:
    """Each per-layer metric's own reader (``metrics/<name>.py``) takes its
    number from what the traced run observed; one that finds nothing to
    read returns None and the metric is left out of the line."""
    import importlib.util

    out = {}
    for m in cell["per_layer"]:
        path = os.path.join(BENCH_DIR, "metrics", m["name"] + ".py")
        if not os.path.exists(path):
            raise BenchError(f"{path}: the reader of {m['name']} is missing")
        spec = importlib.util.spec_from_file_location(
            "benchmark_metric_" + "".join(c if c.isalnum() else "_"
                                          for c in m["name"]), path)
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        value = reader.read(observed)
        if value is not None:
            out[m["name"]] = metric_entry(value, m["unit"])
    return out


# ------------------------------------------------------------------ result
def emit(correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, compared: List[dict], extra: Optional[dict] = None,
         breakdown: Optional[dict] = None) -> None:
    """The compared numbers beside their limits as the last lines of
    stderr, then the one result line as the last line of stdout."""
    for c in compared:
        sys.stderr.write(f"compared {c['name']}: {c['value']!r} "
                         f"limit {c['limit']!r} "
                         f"{'ok' if c['ok'] else 'FAILED'}\n")
    sys.stderr.flush()
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line.update(extra or {})
    line["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in compared}
    print(json.dumps(line), flush=True)
