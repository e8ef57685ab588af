"""Structured run events: a versioned JSONL log + shared bench headers.

Before this module every long-running artifact wrote its own shape: the
bench scripts hand-rolled ``json.dump`` blocks with no common header, and a
training run left nothing machine-readable at all — its lifecycle lived in
log lines.  This module is the one schema they consolidate onto:

* ``EventLog`` — an append-only JSONL file; every line carries
  ``schema_version``, a wall-clock ``ts``, a monotonically increasing
  ``seq``, and an ``event`` kind.  The training loop emits run-start
  (config snapshot + device topology), periodic step-stat flushes,
  validation results, checkpoint/preemption/resume events, and XLA compile
  events (telemetry/train_metrics.py); ``replay()`` reads the file back
  into the run timeline (tests/test_telemetry.py replays one end to end).
* ``bench_record()`` — wraps a bench result dict with the same
  ``schema_version`` + run-metadata header, so every smoke or tool JSON
  line/file is attributable to a device topology and a timestamp without
  each bench re-inventing the header.

Writes are line-buffered and flushed per event: a SIGKILL mid-run loses at
most the event being written, and every earlier line stays valid JSON.
"""

from __future__ import annotations

import json
import logging
import os
import socket
import threading
import time
from typing import Callable, Dict, Iterator, Optional

log = logging.getLogger(__name__)

SCHEMA_VERSION = 1


def device_topology() -> Dict[str, object]:
    """Backend/device summary for run headers; {} before jax initializes
    cleanly (the caller may be a CPU-only test environment)."""
    try:
        import jax
        devices = jax.devices()
        return {
            "platform": devices[0].platform,
            "device_kind": getattr(devices[0], "device_kind", ""),
            "n_devices": len(devices),
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
        }
    except Exception:  # pragma: no cover - backend init failure
        return {}


def run_metadata() -> Dict[str, object]:
    """The shared header: who/where/when/what-backend."""
    meta: Dict[str, object] = {
        "unix_time": time.time(),
        "hostname": socket.gethostname(),
        "pid": os.getpid(),
    }
    try:
        import jax
        meta["jax_version"] = jax.__version__
    except Exception:  # pragma: no cover - jax import failure
        pass
    meta.update(device_topology())
    return meta


def bench_record(rec: Dict[str, object], **extra) -> Dict[str, object]:
    """Wrap a bench result with the shared versioned header.  The record's
    own keys stay top-level (the ``{"metric", "value", ...}`` contract all
    the bench parsers read); the header rides alongside."""
    out: Dict[str, object] = {"schema_version": SCHEMA_VERSION,
                              "run": run_metadata()}
    out.update(rec)
    out.update(extra)
    return out


def write_record(path: str, rec: Dict[str, object], indent: Optional[int] = None
                 ) -> Dict[str, object]:
    """Write one header-wrapped bench record to ``path``; returns the
    wrapped record (callers usually also print it)."""
    wrapped = rec if "schema_version" in rec else bench_record(rec)
    with open(path, "w") as f:
        f.write(json.dumps(wrapped, indent=indent) + "\n")
    return wrapped


class EventLog:
    """Append-only JSONL run-event log (thread-safe)."""

    def __init__(self, path: str):
        self.path = path
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        self._lock = threading.Lock()
        self._f = open(path, "a")
        self._seq = 0
        self._sinks: list = []

    def add_sink(self, sink: Callable[[Dict[str, object]], None]) -> None:
        """Mirror every emitted record into ``sink(rec)`` as well as the
        file — how the flight recorder keeps its bounded in-memory ring of
        recent events (telemetry/flight_recorder.py) without a second
        emission path that could drift from the log."""
        with self._lock:
            self._sinks.append(sink)

    def emit(self, event: str, **fields) -> Dict[str, object]:
        """Write one event line; returns the full record written."""
        with self._lock:
            if self._f is None:
                return {}
            rec = {"schema_version": SCHEMA_VERSION, "seq": self._seq,
                   "ts": time.time(), "event": event, **fields}
            self._seq += 1
            self._f.write(json.dumps(rec, default=_jsonable) + "\n")
            self._f.flush()
            for sink in self._sinks:
                try:
                    sink(rec)
                except Exception:  # pragma: no cover - sink must not kill
                    log.exception("event sink failed")      # the emitter
            return rec

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None

    def __enter__(self) -> "EventLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _jsonable(v):
    """np scalars/arrays and other strays degrade to plain types instead of
    killing the training run with a serialization error."""
    for attr in ("item", "tolist"):
        f = getattr(v, attr, None)
        if f is not None:
            try:
                return f()
            except Exception:  # pragma: no cover - exotic array type
                pass
    return str(v)


def replay(path: str) -> Iterator[Dict[str, object]]:
    """Read an event log back in order, yielding complete records.

    A torn FINAL line (the process was killed mid-write — the at-most-one-
    line loss ``EventLog.emit`` guarantees) is tolerated with a warning
    instead of raising.  A malformed line anywhere EARLIER is not part of
    that guarantee — it means real corruption — so it is also skipped with
    a (louder) warning rather than silently, and the complete records
    around it still come back; a replay must never lose the readable
    majority of a run's timeline to one bad line."""
    with open(path) as f:
        lines = f.readlines()
    last = len(lines) - 1
    for i, line in enumerate(lines):
        stripped = line.strip()
        if not stripped:
            continue
        try:
            yield json.loads(stripped)
        except ValueError:
            if i == last and not line.endswith("\n"):
                log.warning(
                    "event log %s: torn final line (%d bytes) skipped — "
                    "the process was likely killed mid-write", path,
                    len(line))
            else:
                log.warning(
                    "event log %s: malformed record at line %d skipped — "
                    "this is mid-file corruption, not a torn tail", path,
                    i + 1)
