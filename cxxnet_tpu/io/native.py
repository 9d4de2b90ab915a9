"""ctypes bridge to the native data-plane library (cxxnet_tpu/native/).

``libcxxnet_native.so`` (JPEG decode) is git-ignored, so it is built
from ``decode.cc`` on the machine that uses it: the first decode builds
it when it is missing or older than its source (``native/build.sh
native``), and a library that will not load — copied from a machine
with another toolchain — is rebuilt once. Only where that fails (no
compiler, no libjpeg) does the pipeline fall back to cv2/PIL, and
:func:`decoder_name` says which decoder a run got. ctypes releases the
GIL during calls, so a ThreadPoolExecutor over these decoders gets real
multi-core parallelism — the same design as the reference's OpenMP
decode loop (iter_image_recordio-inl.hpp:206-250).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_SO = os.path.join(_NATIVE_DIR, "libcxxnet_native.so")
_SRC = os.path.join(_NATIVE_DIR, "decode.cc")

_lib = None
_lib_lock = threading.Lock()
_tried = False
#: why the native decoder is not in use ('' while it is, or untried)
_why_not = ""


def _build() -> str:
    """Compile decode.cc on this machine; returns '' or what went wrong."""
    try:
        r = subprocess.run(
            ["sh", os.path.join(_NATIVE_DIR, "build.sh"), "native"],
            capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{type(e).__name__}: {e}"
    return "" if r.returncode == 0 else \
        (r.stderr.strip().splitlines() or ["build.sh failed"])[-1]


def _open() -> ctypes.CDLL:
    lib = ctypes.CDLL(_SO)
    lib.cxn_jpeg_dims.restype = ctypes.c_int
    lib.cxn_jpeg_dims.argtypes = [
        ctypes.c_char_p, ctypes.c_long,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    lib.cxn_jpeg_decode.restype = ctypes.c_int
    lib.cxn_jpeg_decode.argtypes = [
        ctypes.c_char_p, ctypes.c_long, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
    return lib


def _open_or_build():
    """(library, '') — or (None, why) when it can neither be loaded
    nor built here."""
    if os.path.exists(_SO) \
            and os.path.getmtime(_SO) >= os.path.getmtime(_SRC):
        try:
            return _open(), ""
        except OSError:
            pass        # built on another machine: rebuild it here
    err = _build()
    if err:
        return None, err
    try:
        return _open(), ""
    except OSError as e:
        return None, str(e)


def _load():
    global _lib, _tried, _why_not
    if _tried:
        return _lib
    with _lib_lock:
        if not _tried:
            _lib, _why_not = _open_or_build()
            _tried = True
    return _lib


def decoder_name() -> str:
    """Which JPEG decoder this process uses: ``native``, or the
    fallback with the reason the native build/load failed."""
    if _load() is not None:
        return "native (libjpeg, cxxnet_tpu/native/libcxxnet_native.so)"
    try:
        import cv2  # noqa: F401
        fallback = "cv2"
    except ImportError:
        fallback = "PIL"
    return f"{fallback} (native decoder unavailable: {_why_not})"


def available() -> bool:
    return _load() is not None


def try_decode(data: bytes, want_channels: int = 3) -> Optional[np.ndarray]:
    """Decode JPEG bytes to HWC uint8, or None if the native lib is absent
    or the payload is not a JPEG it can handle."""
    lib = _load()
    if lib is None:
        return None
    h = ctypes.c_int()
    w = ctypes.c_int()
    c = ctypes.c_int()
    if lib.cxn_jpeg_dims(data, len(data), ctypes.byref(h), ctypes.byref(w),
                         ctypes.byref(c)) != 0:
        return None
    out = np.empty((h.value, w.value, want_channels), np.uint8)
    rc = lib.cxn_jpeg_decode(data, len(data), want_channels,
                             out.ctypes.data_as(ctypes.c_void_p),
                             h.value, w.value)
    if rc != 0:
        return None
    return out
