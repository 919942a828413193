"""ctypes loader for the native columnar bridge (``batchpack.cpp``).

Role (SURVEY.md §2 "Native components"): the TensorFrames analog — a C++
library that packs DataFrame image rows into contiguous device-ready
batches (decode + channel-normalize + BGR flip + jax-compatible bilinear
resize, threaded across rows), replacing the per-row Python loop in the
transformer/UDF hot path.

The library is built on demand with ``g++`` (no pybind11 in this
environment; plain C ABI + ctypes) from the committed source, at first use.
Where there is no C++ compiler (or ``SPARKDL_NO_NATIVE=1``), callers use
the pure-Python path — ``is_available()`` gates every use.  Where there IS
a compiler and the build or the load fails, that is an error
(:class:`NativeBuildError`), not a quiet switch of paths: a run must not
pack its batches through a slower path than the one its host supports
without saying so.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO_PATH = os.path.join(_HERE, "_batchpack.so")
_SRC_PATH = os.path.join(_HERE, "batchpack.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
#: set while one thread runs the build/dlopen; later callers wait on it
_inflight: Optional[threading.Event] = None


class NativeBuildError(RuntimeError):
    """The host has a C++ compiler, and the bridge still could not be
    built or loaded."""


def _build() -> bool:
    """Compile the shared library next to the source (one-time); False
    where the host has no compiler, :class:`NativeBuildError` where it
    has one and the build fails.

    Builds to a process-unique temp name and renames into place, so
    concurrent executor processes never dlopen a half-written .so.
    """
    tmp = f"{_SO_PATH}.{os.getpid()}.tmp"
    cmd = [
        os.environ.get("CXX", "g++"),
        "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread",
        "-o", tmp, _SRC_PATH,
    ]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=300
        )
    except FileNotFoundError as e:  # no toolchain on this host
        logger.info("native bridge build unavailable: %s", e)
        return False
    except subprocess.TimeoutExpired as e:
        raise NativeBuildError(f"native bridge build timed out: {e}") from e
    if proc.returncode != 0:
        raise NativeBuildError(
            f"native bridge build failed ({' '.join(cmd)}):\n"
            f"{proc.stderr[-2000:]}"
        )
    os.replace(tmp, _SO_PATH)  # atomic on POSIX
    return True


def _load() -> Optional[ctypes.CDLL]:
    """Resolve the library handle, building at most once (single-flight).

    The slow work — the g++ subprocess and the dlopen — runs OUTSIDE
    ``_lock``: the first caller claims the build by planting an Event
    under the lock, every later caller waits on that Event (not on the
    lock, which stays free), and the result is admitted under the lock
    once ready.  Same shape as ``serving/cache.py``'s ProgramCache —
    holding a lock across a multi-second subprocess stalls every thread
    that so much as *checks* availability (the lock-blocking rule).
    """
    global _lib, _tried, _inflight
    while True:
        with _lock:
            if _tried:
                return _lib
            if _inflight is None:
                _inflight = claim = threading.Event()
                break
            waiter = _inflight
        waiter.wait()
    lib = None
    resolved = False
    try:
        lib = _resolve()
        resolved = True
    finally:
        with _lock:
            _lib = lib
            # a failed build stays an error for the next caller too,
            # instead of turning into "unavailable" after the first raise
            _tried = resolved
            _inflight = None
        claim.set()
    return lib


def _resolve() -> Optional[ctypes.CDLL]:
    """Build (if needed) + dlopen + bind signatures.  Runs with no lock
    held, in exactly one thread per process (see :func:`_load`)."""
    if os.environ.get("SPARKDL_NO_NATIVE") == "1":
        return None
    try:
        src_mtime = os.path.getmtime(_SRC_PATH)
    except OSError:
        src_mtime = None  # source not shipped (wheel install)
    so_exists = os.path.exists(_SO_PATH)
    stale = (
        src_mtime is not None
        and so_exists
        and os.path.getmtime(_SO_PATH) < src_mtime
    )
    if not so_exists or stale:
        if src_mtime is None or not _build():
            return None
    try:
        lib = ctypes.CDLL(_SO_PATH)
    except OSError as e:
        raise NativeBuildError(f"native bridge load failed: {e}") from e
    if lib.sdl_abi_version() != 1:
        raise NativeBuildError(
            f"native bridge ABI mismatch in {_SO_PATH}; delete it to rebuild"
        )
    lib.sdl_pack_resize_batch.restype = ctypes.c_int64
    lib.sdl_pack_resize_batch.argtypes = [
        ctypes.POINTER(ctypes.c_void_p),  # datas
        ctypes.POINTER(ctypes.c_int32),   # heights
        ctypes.POINTER(ctypes.c_int32),   # widths
        ctypes.POINTER(ctypes.c_int32),   # channels
        ctypes.POINTER(ctypes.c_int32),   # modes
        ctypes.c_int64,                   # n
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,  # out h/w/c
        ctypes.c_int32,                   # bgr_to_rgb
        ctypes.POINTER(ctypes.c_float),   # out
        ctypes.c_int32,                   # n_threads
    ]
    lib.sdl_pack_batch_u8.restype = ctypes.c_int64
    lib.sdl_pack_batch_u8.argtypes = [
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_int32,
    ]
    lib.sdl_resize_batch_f32.restype = ctypes.c_int64
    lib.sdl_resize_batch_f32.argtypes = [
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int32,
    ]
    logger.info("native columnar bridge loaded (%s)", _SO_PATH)
    return lib


def is_available() -> bool:
    return _load() is not None


def pack_image_rows(
    rows: Sequence,
    out_hw: Tuple[int, int],
    out_c: int,
    bgr_to_rgb: bool = False,
    n_threads: int = 0,
) -> Optional[np.ndarray]:
    """Decode+normalize+resize+pack image-struct Rows into a float32 NHWC
    batch in one native call.  Returns None if the native path is
    unavailable (caller falls back to Python); raises on bad row data."""
    lib = _load()
    if lib is None:
        return None
    # unknown mode ordinals (and short/corrupt data buffers) fall back to
    # the Python codec, which raises the canonical error instead of the C++
    # code reading out of bounds
    _known_modes = {0, 16, 24, 5, 21, 29}
    _f32_modes = {5, 21, 29}
    if any(int(r["mode"]) not in _known_modes for r in rows):
        return None
    n = len(rows)
    out_h, out_w = int(out_hw[0]), int(out_hw[1])
    out = np.empty((n, out_h, out_w, int(out_c)), dtype=np.float32)

    datas = (ctypes.c_void_p * n)()
    heights = (ctypes.c_int32 * n)()
    widths = (ctypes.c_int32 * n)()
    channels = (ctypes.c_int32 * n)()
    modes = (ctypes.c_int32 * n)()
    # bytes are immutable and the C side only reads, so pass them zero-copy;
    # this list pins them for the duration of the call
    keepalive = []
    for i, r in enumerate(rows):
        raw = r["data"]
        if not isinstance(raw, bytes):
            raw = bytes(raw)  # ctypes.c_char_p accepts only bytes
        itemsize = 4 if int(r["mode"]) in _f32_modes else 1
        expected = int(r["height"]) * int(r["width"]) * int(r["nChannels"])
        if len(raw) < expected * itemsize:
            return None  # Python path raises the canonical ValueError
        keepalive.append(raw)
        datas[i] = ctypes.cast(ctypes.c_char_p(raw), ctypes.c_void_p)
        heights[i] = int(r["height"])
        widths[i] = int(r["width"])
        channels[i] = int(r["nChannels"])
        modes[i] = int(r["mode"])

    rc = lib.sdl_pack_resize_batch(
        datas, heights, widths, channels, modes,
        ctypes.c_int64(n), out_h, out_w, int(out_c),
        1 if bgr_to_rgb else 0,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        int(n_threads),
    )
    if rc != 0:
        raise ValueError(
            f"native pack failed on row {int(rc) - 1} "
            f"(unsupported mode/channel combination)"
        )
    return out


def pack_image_rows_u8(
    rows: Sequence,
    out_hw: Tuple[int, int],
    out_c: int,
    bgr_to_rgb: bool = False,
    n_threads: int = 0,
) -> Optional[np.ndarray]:
    """Pack same-sized *uint8* structs into a uint8 NHWC batch (no resize,
    no float cast — the device program casts, quartering link bytes).
    Returns None when the native path is unavailable or any row is float /
    wrong-sized / needs luminance conversion."""
    lib = _load()
    if lib is None:
        return None
    u8_modes = {0, 16, 24}
    out_h, out_w = int(out_hw[0]), int(out_hw[1])
    for r in rows:
        if (
            int(r["mode"]) not in u8_modes
            or int(r["height"]) != out_h
            or int(r["width"]) != out_w
            or (int(out_c) == 1 and int(r["nChannels"]) != 1)
        ):
            return None
    n = len(rows)
    out = np.empty((n, out_h, out_w, int(out_c)), dtype=np.uint8)
    datas = (ctypes.c_void_p * n)()
    heights = (ctypes.c_int32 * n)()
    widths = (ctypes.c_int32 * n)()
    channels = (ctypes.c_int32 * n)()
    modes = (ctypes.c_int32 * n)()
    keepalive = []
    for i, r in enumerate(rows):
        raw = r["data"]
        if not isinstance(raw, bytes):
            raw = bytes(raw)  # ctypes.c_char_p accepts only bytes
        if len(raw) < out_h * out_w * int(r["nChannels"]):
            return None  # short buffer: Python path raises cleanly
        keepalive.append(raw)
        datas[i] = ctypes.cast(ctypes.c_char_p(raw), ctypes.c_void_p)
        heights[i] = int(r["height"])
        widths[i] = int(r["width"])
        channels[i] = int(r["nChannels"])
        modes[i] = int(r["mode"])
    rc = lib.sdl_pack_batch_u8(
        datas, heights, widths, channels, modes,
        ctypes.c_int64(n), out_h, out_w, int(out_c),
        1 if bgr_to_rgb else 0,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        int(n_threads),
    )
    if rc != 0:
        return None  # unsupported combo: caller falls back
    return out


def resize_batch(
    batch: np.ndarray, out_hw: Tuple[int, int], n_threads: int = 0
) -> Optional[np.ndarray]:
    """Bilinear-resize a same-shaped float32 NHWC batch natively (matches
    jax.image.resize linear/antialias semantics).  None if unavailable."""
    lib = _load()
    if lib is None:
        return None
    batch = np.ascontiguousarray(batch, dtype=np.float32)
    n, h, w, c = batch.shape
    out_h, out_w = int(out_hw[0]), int(out_hw[1])
    out = np.empty((n, out_h, out_w, c), dtype=np.float32)
    rc = lib.sdl_resize_batch_f32(
        batch.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_int64(n), h, w, c, out_h, out_w,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        int(n_threads),
    )
    if rc != 0:  # pragma: no cover - resize has no failure modes today
        raise RuntimeError("native resize failed")
    return out
