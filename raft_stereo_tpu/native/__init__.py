"""ctypes bindings for the native host-side decoders (native/stereo_native.cpp).

The compute hot path is Pallas/XLA on device; this is the *host* native
layer — the TPU-framework counterpart of the reference's C++ extension
scaffolding (reference: sampler/setup.py builds at install time).  The
shared library is built from source on first import (one ``g++`` invocation,
cached next to the source); if a toolchain or libpng is missing everything
falls back to the pure-Python readers in ``data/frame_utils.py``.

ctypes releases the GIL for the duration of each foreign call, so decodes
scale across the ``StereoLoader`` worker threads.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

log = logging.getLogger(__name__)

# Source ships as package data so pip installs keep the native path; the
# library is built (and cached) next to it.
_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_SRC_DIR, "stereo_native.cpp")
_SO = os.path.join(_SRC_DIR, "libstereo_native.so")

_lib: Optional[ctypes.CDLL] = None
_lib_lock = threading.Lock()
_build_failed = False

_i64 = ctypes.c_int64
_i64p = ctypes.POINTER(ctypes.c_int64)


def _build() -> bool:
    # Compile to a per-process temp file and atomically rename: concurrent
    # builders never expose a half-written .so (a loader that already
    # dlopen'ed the old file keeps its mapped inode).
    tmp = f"{_SO}.build-{os.getpid()}"
    cmd = ["g++", "-O3", "-fPIC", "-shared", "-o", tmp, _SRC, "-lpng", "-lz"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, cwd=_SRC_DIR,
                       timeout=120)
        os.replace(tmp, _SO)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        log.warning("native decoder build failed (%s); using the (slower) "
                    "Python readers", e)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        if not os.path.exists(_SO) or (
                os.path.exists(_SRC)
                and os.path.getmtime(_SRC) > os.path.getmtime(_SO)):
            if not os.path.exists(_SRC):
                log.warning("native decoder source missing at %s; using "
                            "the (slower) Python readers", _SRC)
                _build_failed = True
                return None
            if not _build():
                _build_failed = True
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            log.warning("native decoder load failed (%s); using the "
                        "(slower) Python readers", e)
            _build_failed = True
            return None
        lib.pfm_dims.argtypes = [ctypes.c_char_p, _i64, _i64p, _i64p, _i64p]
        lib.pfm_decode.argtypes = [ctypes.c_char_p, _i64, ctypes.c_void_p]
        lib.png_dims.argtypes = [ctypes.c_char_p, _i64,
                                 _i64p, _i64p, _i64p, _i64p]
        lib.png_decode_rgb8.argtypes = [ctypes.c_char_p, _i64, ctypes.c_void_p]
        lib.png_decode_gray16.argtypes = [ctypes.c_char_p, _i64,
                                          ctypes.c_void_p]
        for f in (lib.pfm_dims, lib.pfm_decode, lib.png_dims,
                  lib.png_decode_rgb8, lib.png_decode_gray16):
            f.restype = ctypes.c_int
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def read_pfm(path: str) -> np.ndarray:
    """Decode a PFM file: (H, W) float32 for 'Pf', (H, W, 3) for 'PF',
    rows top-down (same contract as data.frame_utils.read_pfm)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native decoders unavailable")
    with open(path, "rb") as f:
        buf = f.read()
    w, h, c = _i64(), _i64(), _i64()
    rc = lib.pfm_dims(buf, len(buf), ctypes.byref(w), ctypes.byref(h),
                      ctypes.byref(c))
    if rc:
        raise ValueError(f"{path}: PFM parse error {rc}")
    # Sanity-bound the header-declared dims against the payload actually
    # present before allocating: a corrupt/truncated header could otherwise
    # declare huge dims and trigger a multi-GB np.empty (MemoryError) instead
    # of the ValueError that routes callers to the Python fallback.
    if w.value * h.value * c.value * 4 > len(buf):
        raise ValueError(
            f"{path}: PFM header declares {w.value}x{h.value}x{c.value} "
            f"floats but file holds only {len(buf)} bytes")
    out = np.empty((h.value, w.value, c.value), np.float32)
    rc = lib.pfm_decode(buf, len(buf),
                        out.ctypes.data_as(ctypes.c_void_p))
    if rc:
        raise ValueError(f"{path}: PFM decode error {rc}")
    return out[..., 0] if c.value == 1 else out


def png_info(buf: bytes) -> Tuple[int, int, int, int]:
    """(width, height, bit_depth, channels) of an in-memory PNG."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native decoders unavailable")
    w, h, d, c = _i64(), _i64(), _i64(), _i64()
    rc = lib.png_dims(buf, len(buf), ctypes.byref(w), ctypes.byref(h),
                      ctypes.byref(d), ctypes.byref(c))
    if rc:
        raise ValueError(f"PNG parse error {rc}")
    return w.value, h.value, d.value, c.value


def read_png_rgb8(path: str) -> np.ndarray:
    """Decode any 8/16-bit PNG to (H, W, 3) uint8 (gray replicated, alpha
    dropped) — the native path for data.frame_utils.read_image."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native decoders unavailable")
    with open(path, "rb") as f:
        buf = f.read()
    w, h, _, _ = png_info(buf)
    out = np.empty((h, w, 3), np.uint8)
    rc = lib.png_decode_rgb8(buf, len(buf),
                             out.ctypes.data_as(ctypes.c_void_p))
    if rc:
        raise ValueError(f"{path}: PNG decode error {rc}")
    return out


def read_png_gray16(path: str) -> np.ndarray:
    """Decode a 16-bit grayscale PNG to (H, W) uint16 — KITTI disparity
    maps (value/256 = px; reference core/utils/frame_utils.py:124)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native decoders unavailable")
    with open(path, "rb") as f:
        buf = f.read()
    out_w, out_h, depth, channels = png_info(buf)
    if depth != 16 or channels != 1:
        raise ValueError(f"{path}: expected 16-bit gray, got "
                         f"{depth}-bit {channels}ch")
    out = np.empty((out_h, out_w), np.uint16)
    rc = lib.png_decode_gray16(buf, len(buf),
                               out.ctypes.data_as(ctypes.c_void_p))
    if rc:
        raise ValueError(f"{path}: PNG decode error {rc}")
    return out
