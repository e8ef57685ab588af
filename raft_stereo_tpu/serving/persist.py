"""Shared content-addressed executable artifact store: restart-to-ready
(and fleet scale-out) in seconds, not compile-minutes.

A forward at published widths costs the v5e compiler 30-40 s *per shape
bucket* (PERF.md section 5, set-up) — and rounds 11/12/14/15 multiplied
the executable surface to (bucket x batch x tier x family).  A crashed,
rescheduled, or newly scaled-out serving replica repays that entire
product on boot, which at production scale means tens of seconds of dead
pod per autoscale event — times N replicas.  This module makes prewarm
fetch-bound instead of compile-bound:

* ``ExecutableDiskCache`` — a content-addressed store of serialized
  compiled executables (``jax.experimental.serialize_executable``).  The
  key is a SHA-256 over everything that invalidates an executable: jax
  version, backend platform + version, device kind, the model config
  JSON, padded shape, batch size, tier knobs, GRU depth, fetch dtype,
  donation, quant mode, and the executable FAMILY / flow_init arity —
  a new jax wheel or a config change misses cleanly and recompiles
  (stale entries are dead files, never wrong programs).

  **Layout** (the fleet contract, docs/architecture.md §Fleet): entries
  live at ``<store>/<key[:2]>/<key>.jaxexe`` with an optional
  ``<key>.json`` manifest sidecar recording the human-readable compile
  coordinates — a flat SHA-256-addressed tree any shared medium can
  carry (NFS mount, object-store sync, an image layer baked by
  tools/compile_farm.py).  Round-13 flat-layout entries
  (``<store>/<key>.jaxexe``) still load.  Because keys are pure content
  hashes, concurrent writers (N replicas, a compile farm) can share one
  directory with no coordination: identical coordinates produce
  identical keys, and the atomic rename makes the last writer win with
  an equivalent artifact.

  **Shared-store roles**: a compile farm populates the store
  (read-write); replicas may mount it ``read_only`` — they fetch warm
  artifacts but never write, so a misconfigured replica cannot pollute
  the fleet's shared cache.

  **Garbage collection**: ``max_bytes`` bounds the store.  Entries are
  evicted least-recently-USED first (atime, which ``load`` refreshes
  explicitly via ``os.utime`` so noatime mounts still track use);
  config / jax-fingerprint churn therefore ages out instead of growing
  without bound.  The ``bytes_gauge`` hook keeps the
  ``serve_persist_cache_bytes`` gauge live.

* ``enable_persistent_compilation_cache`` — turns on jax's own
  persistent compilation cache in the same directory, which also covers
  compiles that do not route through the AOT path.

* ``SessionHandoffStore`` — the store's ``sessions/`` namespace (round
  18): serialized SessionStore blobs a draining replica publishes so
  its live streams survive a planned restart (docs/architecture.md
  §Fleet, "Session handoff").  Content-hash keys, atomic writes,
  TTL-bounded, and the same can-only-cost-warmth degradation contract.

Degradation contract (same as telemetry/costs.py): serialization that
fails for any reason — backend without serialization support, pickle
drift across versions, a corrupt/truncated cache file — logs once and
falls back to a fresh compile.  The store can make boot faster; it can
never make serving wrong or down.  Writes are atomic (tmp +
``os.replace``) so a crash mid-write cannot leave a torn entry for the
next boot (or another replica) to trip over.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

log = logging.getLogger(__name__)

# Bump to invalidate every existing cache entry on a format change.
CACHE_FORMAT_VERSION = 1

ENTRY_SUFFIX = ".jaxexe"
MANIFEST_SUFFIX = ".json"


def backend_fingerprint() -> Dict[str, str]:
    """The jax/backend identity an executable is only valid under."""
    import jax

    fp = {"jax": jax.__version__,
          "cache_format": str(CACHE_FORMAT_VERSION)}
    try:
        backend = jax.extend.backend.get_backend()
        fp["platform"] = str(backend.platform)
        fp["platform_version"] = str(
            getattr(backend, "platform_version", ""))
    except Exception:  # pragma: no cover - exotic backend init
        fp["platform"] = str(jax.default_backend())
    try:
        fp["device_kind"] = str(
            getattr(jax.devices()[0], "device_kind", ""))
    except Exception:  # pragma: no cover
        fp["device_kind"] = ""
    return fp


def executable_cache_key(**coords: Any) -> str:
    """Stable content key of one compile point: the caller passes every
    coordinate that selects a distinct program (config JSON, padded
    shape, batch, tier, iters, fetch dtype, donation, device index) and
    the backend fingerprint is mixed in here."""
    payload = dict(coords)
    payload["backend"] = backend_fingerprint()
    blob = json.dumps(payload, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()


class ExecutableDiskCache:
    """Content-addressed store of serialized compiled executables, keyed
    by ``executable_cache_key``.

    ``load`` returns a ready-to-call loaded executable or None (miss /
    unreadable / wrong format — misses never raise).  ``store`` is
    best-effort and atomic, a no-op in ``read_only`` mode.  A
    ``disabled`` cache (serialization proved unavailable on this
    backend) stops trying after the first failure so a hot dispatch path
    does not repeatedly pay a doomed serialize.  ``max_bytes`` bounds
    the store with LRU-by-atime eviction; ``bytes_gauge`` (any object
    with ``set``) tracks the post-GC total.
    """

    def __init__(self, cache_dir: str, max_bytes: Optional[int] = None,
                 read_only: bool = False, bytes_gauge=None):
        if max_bytes is not None and max_bytes < 0:
            raise ValueError(f"max_bytes={max_bytes} must be >= 0")
        self.cache_dir = os.path.abspath(os.path.expanduser(cache_dir))
        if not read_only:
            os.makedirs(self.cache_dir, exist_ok=True)
        self.max_bytes = max_bytes
        self.read_only = read_only
        self.bytes_gauge = bytes_gauge
        self._lock = threading.Lock()
        self.disabled = False
        self.loads = 0       # warm hits served from disk
        self.stores = 0
        self.misses = 0
        self.evictions = 0
        if bytes_gauge is not None:
            bytes_gauge.set(self.total_bytes())

    # ------------------------------------------------------------- layout
    def _path(self, key: str) -> str:
        """Sharded canonical path: ``<store>/<key[:2]>/<key>.jaxexe``."""
        return os.path.join(self.cache_dir, key[:2],
                            f"{key}{ENTRY_SUFFIX}")

    def _legacy_path(self, key: str) -> str:
        """Round-13 flat layout, still honored on load."""
        return os.path.join(self.cache_dir, f"{key}{ENTRY_SUFFIX}")

    def _entries(self) -> List[Tuple[str, int, float]]:
        """Every entry file as ``(path, size, atime)`` — flat and
        sharded layouts alike; never raises (a racing eviction or an
        unshared store mid-write just drops out of the listing)."""
        out: List[Tuple[str, int, float]] = []
        try:
            roots = [self.cache_dir] + [
                os.path.join(self.cache_dir, d)
                for d in os.listdir(self.cache_dir)
                if len(d) == 2
                and os.path.isdir(os.path.join(self.cache_dir, d))]
        except OSError:
            return out
        for root in roots:
            try:
                names = os.listdir(root)
            except OSError:
                continue
            for name in names:
                if not name.endswith(ENTRY_SUFFIX):
                    continue
                path = os.path.join(root, name)
                try:
                    st = os.stat(path)
                except OSError:
                    continue
                out.append((path, st.st_size, st.st_atime))
        return out

    def total_bytes(self) -> int:
        """Bytes of executable entries on disk (manifest sidecars are
        noise-level and not counted)."""
        return sum(size for _, size, _ in self._entries())

    # ----------------------------------------------------------------- load
    def load(self, key: str, devices: Optional[Sequence] = None):
        """The stored executable loaded onto ``devices`` — the devices it
        was compiled for (None = the default device, what a plain
        ``jax.jit(f).lower(...).compile()`` targets) — or None on a
        miss."""
        if self.disabled:
            return None
        path = self._path(key)
        if not os.path.exists(path):
            legacy = self._legacy_path(key)
            path = legacy if os.path.exists(legacy) else path
        try:
            with open(path, "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
        except FileNotFoundError:
            with self._lock:
                self.misses += 1
            return None
        except Exception:
            log.warning("unreadable executable cache entry %s; "
                        "recompiling (entry will be rewritten)", path,
                        exc_info=True)
            with self._lock:
                self.misses += 1
            return None
        try:
            import jax
            from jax.experimental import serialize_executable
            # deserialize_and_load defaults to EVERY device of the
            # backend; an executable compiled for one device (or one xl
            # group) must be loaded onto exactly those.
            exe = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=(list(devices) if devices
                                   else jax.devices()[:1]))
        except Exception:
            log.warning("could not deserialize cached executable %s "
                        "(backend/jax drift past the fingerprint?); "
                        "recompiling", path, exc_info=True)
            with self._lock:
                self.misses += 1
            return None
        # Mark use explicitly: LRU eviction orders by atime, and noatime
        # mounts would otherwise never see reads.  Best-effort (a
        # read-only mount cannot utime — fine, its GC runs elsewhere).
        try:
            os.utime(path)
        except OSError:
            pass
        with self._lock:
            self.loads += 1
        return exe

    # ---------------------------------------------------------------- store
    def store(self, key: str, compiled,
              meta: Optional[Dict[str, Any]] = None) -> bool:
        """Serialize ``compiled`` under ``key``; ``meta`` (optional)
        lands in a ``<key>.json`` manifest sidecar so a human (or an
        audit job) can read WHAT each content hash is without
        deserializing it."""
        if self.disabled or self.read_only:
            return False
        try:
            from jax.experimental import serialize_executable
            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            blob = pickle.dumps((payload, in_tree, out_tree))
        except Exception:
            log.warning("executable serialization unavailable on this "
                        "backend; persistent cache disabled for this "
                        "process", exc_info=True)
            self.disabled = True
            return False
        path = self._path(key)
        tmp = f"{path}.tmp-{os.getpid()}"
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError:
            log.warning("could not write executable cache entry %s",
                        path, exc_info=True)
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        if meta is not None:
            self._write_manifest(key, meta, len(blob))
        with self._lock:
            self.stores += 1
        self.gc()
        return True

    def _write_manifest(self, key: str, meta: Dict[str, Any],
                        size: int) -> None:
        mpath = os.path.join(os.path.dirname(self._path(key)),
                             f"{key}{MANIFEST_SUFFIX}")
        tmp = f"{mpath}.tmp-{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump({"key": key, "bytes": size,
                           "backend": backend_fingerprint(), **meta},
                          f, indent=1, sort_keys=True, default=str)
            os.replace(tmp, mpath)
        except OSError:   # the manifest is advisory — never fail a store
            try:
                os.unlink(tmp)
            except OSError:
                pass

    # ------------------------------------------------------------------- gc
    def gc(self) -> int:
        """Evict least-recently-used entries until the store fits
        ``max_bytes``; returns the number evicted.  Also refreshes the
        bytes gauge.  No-op without a bound (the gauge still updates)."""
        entries = self._entries()
        total = sum(size for _, size, _ in entries)
        evicted = 0
        if (self.max_bytes is not None and not self.read_only
                and total > self.max_bytes):
            for path, size, _ in sorted(entries, key=lambda e: e[2]):
                if total <= self.max_bytes:
                    break
                try:
                    os.unlink(path)
                except OSError:
                    continue
                try:   # the manifest dies with its entry
                    os.unlink(path[:-len(ENTRY_SUFFIX)]
                              + MANIFEST_SUFFIX)
                except OSError:
                    pass
                total -= size
                evicted += 1
            if evicted:
                with self._lock:
                    self.evictions += evicted
                log.info("executable cache GC: evicted %d LRU entr%s "
                         "(max_bytes=%d, now %d bytes)", evicted,
                         "y" if evicted == 1 else "ies",
                         self.max_bytes, total)
        if self.bytes_gauge is not None:
            self.bytes_gauge.set(total)
        return evicted

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"loads": self.loads, "stores": self.stores,
                    "misses": self.misses, "evictions": self.evictions,
                    "disabled": int(self.disabled),
                    "read_only": int(self.read_only)}


class SessionHandoffStore:
    """The artifact store's ``sessions/`` namespace (round 18): a
    gracefully draining replica publishes its serialized session blob
    here (serving/sessions.py ``SessionStore.export``), the router hands
    the content key to whichever survivors inherit those ids
    (``X-Handoff-Artifact``), and the receiving replica fetches the blob
    lazily at the session's next frame.

    Same degradation contract as the executable store above: a handoff
    that cannot be written, read, or parsed costs warmth (those sessions
    cold-start), never correctness or uptime.  Keys are SHA-256 content
    hashes, writes are atomic, and ``gc`` ages published blobs out after
    ``ttl_s`` — a handoff is only useful for about one session TTL, so
    the namespace is self-bounding under rolling restarts.
    """

    SUFFIX = ".sessions"

    def __init__(self, store_dir: str, ttl_s: float = 600.0,
                 read_only: bool = False):
        self.dir = os.path.join(
            os.path.abspath(os.path.expanduser(store_dir)), "sessions")
        self.ttl_s = ttl_s
        self.read_only = read_only
        if not read_only:
            try:
                os.makedirs(self.dir, exist_ok=True)
            except OSError:
                log.warning("cannot create session handoff namespace %s",
                            self.dir, exc_info=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.dir, f"{key}{self.SUFFIX}")

    def publish(self, blob: bytes) -> Optional[str]:
        """Write one handoff blob; returns its content key, or None when
        the write failed (the drain proceeds — its sessions fail over to
        the r16 typed-loss path instead)."""
        if self.read_only:
            return None
        key = hashlib.sha256(blob).hexdigest()
        path = self._path(key)
        tmp = f"{path}.tmp-{os.getpid()}"
        try:
            os.makedirs(self.dir, exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError:
            log.warning("could not publish session handoff %s", path,
                        exc_info=True)
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
        self.gc()
        return key

    def fetch(self, key: str) -> Optional[bytes]:
        """The blob for ``key``, or None (missing / unreadable / key
        fails the content-hash check — a torn or tampered file must not
        reach the parser as trusted state)."""
        try:
            with open(self._path(key), "rb") as f:
                blob = f.read()
        except OSError:
            return None
        if hashlib.sha256(blob).hexdigest() != key:
            log.warning("session handoff %s fails its content hash; "
                        "ignoring", key)
            return None
        return blob

    def gc(self) -> int:
        """Drop handoff blobs older than ``ttl_s`` (mtime); returns the
        count removed."""
        if self.read_only:
            return 0
        removed = 0
        try:
            names = os.listdir(self.dir)
        except OSError:
            return 0
        cutoff = time.time() - self.ttl_s
        for name in names:
            if not name.endswith(self.SUFFIX):
                continue
            path = os.path.join(self.dir, name)
            try:
                if os.stat(path).st_mtime < cutoff:
                    os.unlink(path)
                    removed += 1
            except OSError:
                continue
        return removed


def enable_persistent_compilation_cache(cache_dir: str) -> None:
    """Point jax's own persistent compilation cache at ``cache_dir`` —
    covers compiles outside the engine's AOT path.  Yields to
    ``JAX_COMPILATION_CACHE_DIR`` like every other site
    (profiling.setup_compilation_cache): with the variable set, the
    directory is the environment's."""
    import jax

    from raft_stereo_tpu.profiling import COMPILE_CACHE_ENV

    if not os.environ.get(COMPILE_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.abspath(os.path.expanduser(cache_dir)))
    # Cache every compile, not just the slow ones: serving prewarm is
    # many medium-size compiles, each below the default 1s floor.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
