"""Native (C++) input-pipeline kernel: build + ctypes bindings.

The reference reaches native decode through torchvision/PIL and parallelizes
it with the DataLoader worker pool (ref: /root/reference/distribuuuu/
utils.py:127,147). Here the equivalent is first-party C++ (decode.cc):
libjpeg/libpng decode, a PIL-compatible resampler, normalization, and an
internal std::thread pool — one GIL-free call per batch.

The library is built lazily with g++ on first use and cached next to the
source; everything degrades gracefully to the pure-PIL path when a toolchain
or libjpeg headers are missing (``available()`` → False).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "decode.cc")
_LIB = os.path.join(os.path.dirname(__file__), "_libdtpu_decode.so")
_ABI_VERSION = 4

_lock = threading.Lock()
_lib = None
_build_error: str | None = None


class Geom(ctypes.Structure):
    """Mirror of decode.cc's Geom: one resample geometry per image."""

    _fields_ = [
        ("box_x", ctypes.c_double),
        ("box_y", ctypes.c_double),
        ("scale_x", ctypes.c_double),
        ("scale_y", ctypes.c_double),
        ("out_x0", ctypes.c_int32),
        ("out_y0", ctypes.c_int32),
        ("flip", ctypes.c_int32),
        ("_pad", ctypes.c_int32),
    ]


def _build() -> str | None:
    """Compile decode.cc → shared lib. Returns error string or None."""
    if os.path.exists(_LIB) and os.path.getmtime(_LIB) >= os.path.getmtime(_SRC):
        return None
    # Per-pid temp target: concurrent first-use builds (multi-process JAX on
    # one host, shared package dir) must not interleave writes; os.replace of
    # a fully-written file is atomic either way.
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
        _SRC, "-o", tmp, "-ljpeg", "-lpng",
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as exc:  # no g++ etc.
        return f"native build failed to launch: {exc}"
    if proc.returncode != 0:
        return f"native build failed:\n{proc.stderr[-2000:]}"
    os.replace(tmp, _LIB)
    return None


def _unavailable(why: str) -> None:
    """Record why the kernel is out, and say once that decoding runs on
    the pure-PIL path (``DATA.BACKEND auto`` falls back without asking)."""
    global _build_error
    _build_error = why
    from distribuuuu_tpu.utils.logger import get_logger

    get_logger().warning(
        "native decode kernel unavailable — image decode falls back to "
        "the pure-PIL path (DATA.BACKEND native would refuse): %s", why,
    )


def _load():
    global _lib
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        err = _build()
        if err is not None:
            return _unavailable(err)
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError as exc:
            return _unavailable(f"native lib load failed: {exc}")
        if lib.dtpu_abi_version() != _ABI_VERSION:
            return _unavailable(
                "native ABI mismatch (stale _libdtpu_decode.so?)")
        lib.dtpu_file_dims.restype = ctypes.c_int
        lib.dtpu_file_dims.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.dtpu_load_batch.restype = None
        lib.dtpu_load_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.c_void_p,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.dtpu_load_batch_u8.restype = None
        lib.dtpu_load_batch_u8.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.c_void_p,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int32),
        ]
        # memory-buffer entry points (shard records) — ABI 4
        lib.dtpu_mem_dims.restype = ctypes.c_int
        lib.dtpu_mem_dims.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.dtpu_load_batch_mem.restype = None
        lib.dtpu_load_batch_mem.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_void_p,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.dtpu_load_batch_u8_mem.restype = None
        lib.dtpu_load_batch_u8_mem.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_void_p,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int32),
        ]
        _lib = lib
        return _lib


def available() -> bool:
    """True when the native kernel built/loaded (builds on first call)."""
    return _load() is not None


def build_error() -> str | None:
    _load()
    return _build_error


def file_dims(path: str) -> tuple[int, int] | None:
    """(width, height) from the image header, or None if unsupported."""
    lib = _load()
    if lib is None:
        return None
    w, h = ctypes.c_int32(), ctypes.c_int32()
    if lib.dtpu_file_dims(path.encode(), ctypes.byref(w), ctypes.byref(h)):
        return None
    return w.value, h.value


def load_batch(
    paths: list[str],
    geoms: np.ndarray,  # structured array matching Geom, len n
    out_size: tuple[int, int],  # (h, w)
    mean: np.ndarray,
    std: np.ndarray,
    n_threads: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Decode+transform a batch. Returns (images [n,h,w,3] f32, statuses [n]).

    Nonzero status marks an image the native path could not handle (exotic
    format/CMYK/corrupt); the caller re-does those via PIL.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native decode unavailable: {_build_error}")
    n = len(paths)
    out_h, out_w = out_size
    images = np.empty((n, out_h, out_w, 3), np.float32)
    statuses = np.empty((n,), np.int32)
    c_paths = (ctypes.c_char_p * n)(*[p.encode() for p in paths])
    mean32 = np.ascontiguousarray(mean, np.float32)
    std32 = np.ascontiguousarray(std, np.float32)
    geoms = np.ascontiguousarray(geoms)
    assert geoms.nbytes == n * ctypes.sizeof(Geom), "geom layout mismatch"
    lib.dtpu_load_batch(
        c_paths,
        geoms.ctypes.data_as(ctypes.c_void_p),
        n,
        out_w,
        out_h,
        mean32.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        std32.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n_threads,
        images.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        statuses.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return images, statuses


def load_batch_u8(
    paths: list[str],
    geoms: np.ndarray,  # structured array matching Geom, len n
    out_size: tuple[int, int],  # (h, w)
    n_threads: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Raw-u8 batch (``DATA.DEVICE_NORMALIZE``): decode+resample+flip, no
    normalize. Returns (images [n,h,w,3] uint8, statuses [n])."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native decode unavailable: {_build_error}")
    n = len(paths)
    out_h, out_w = out_size
    images = np.empty((n, out_h, out_w, 3), np.uint8)
    statuses = np.empty((n,), np.int32)
    c_paths = (ctypes.c_char_p * n)(*[p.encode() for p in paths])
    geoms = np.ascontiguousarray(geoms)
    assert geoms.nbytes == n * ctypes.sizeof(Geom), "geom layout mismatch"
    lib.dtpu_load_batch_u8(
        c_paths,
        geoms.ctypes.data_as(ctypes.c_void_p),
        n,
        out_w,
        out_h,
        n_threads,
        images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        statuses.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return images, statuses


def has_mem_api() -> bool:
    """True when the loaded kernel speaks the memory-buffer entry points
    (ABI ≥ 4 — the version gate in ``_load`` already enforces it, so this
    is equivalent to ``available()``; kept separate for call-site intent)."""
    return available()


def mem_dims(data: bytes) -> tuple[int, int] | None:
    """(width, height) from an in-memory encoded image, or None."""
    lib = _load()
    if lib is None or not data:
        return None
    w, h = ctypes.c_int32(), ctypes.c_int32()
    if lib.dtpu_mem_dims(data, len(data), ctypes.byref(w), ctypes.byref(h)):
        return None
    return w.value, h.value


def _mem_args(bufs: list[bytes]):
    n = len(bufs)
    c_bufs = (ctypes.c_char_p * n)(*bufs)
    c_lens = (ctypes.c_int64 * n)(*[len(b) for b in bufs])
    return c_bufs, c_lens


def load_batch_mem(
    bufs: list[bytes],
    geoms: np.ndarray,  # structured array matching Geom, len n
    out_size: tuple[int, int],  # (h, w)
    mean: np.ndarray,
    std: np.ndarray,
    n_threads: int,
) -> tuple[np.ndarray, np.ndarray]:
    """``load_batch`` over in-memory encoded buffers (shard records): one
    GIL-free call, internal thread pool. An empty buffer is the caller's
    fallback sentinel — it fails instantly with nonzero status."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native decode unavailable: {_build_error}")
    n = len(bufs)
    out_h, out_w = out_size
    images = np.empty((n, out_h, out_w, 3), np.float32)
    statuses = np.empty((n,), np.int32)
    c_bufs, c_lens = _mem_args(bufs)
    mean32 = np.ascontiguousarray(mean, np.float32)
    std32 = np.ascontiguousarray(std, np.float32)
    geoms = np.ascontiguousarray(geoms)
    assert geoms.nbytes == n * ctypes.sizeof(Geom), "geom layout mismatch"
    lib.dtpu_load_batch_mem(
        c_bufs,
        c_lens,
        geoms.ctypes.data_as(ctypes.c_void_p),
        n,
        out_w,
        out_h,
        mean32.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        std32.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n_threads,
        images.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        statuses.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return images, statuses


def load_batch_u8_mem(
    bufs: list[bytes],
    geoms: np.ndarray,  # structured array matching Geom, len n
    out_size: tuple[int, int],  # (h, w)
    n_threads: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Raw-u8 variant of ``load_batch_mem`` (``DATA.DEVICE_NORMALIZE``)."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native decode unavailable: {_build_error}")
    n = len(bufs)
    out_h, out_w = out_size
    images = np.empty((n, out_h, out_w, 3), np.uint8)
    statuses = np.empty((n,), np.int32)
    c_bufs, c_lens = _mem_args(bufs)
    geoms = np.ascontiguousarray(geoms)
    assert geoms.nbytes == n * ctypes.sizeof(Geom), "geom layout mismatch"
    lib.dtpu_load_batch_u8_mem(
        c_bufs,
        c_lens,
        geoms.ctypes.data_as(ctypes.c_void_p),
        n,
        out_w,
        out_h,
        n_threads,
        images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        statuses.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return images, statuses


GEOM_DTYPE = np.dtype(
    [
        ("box_x", np.float64),
        ("box_y", np.float64),
        ("scale_x", np.float64),
        ("scale_y", np.float64),
        ("out_x0", np.int32),
        ("out_y0", np.int32),
        ("flip", np.int32),
        ("_pad", np.int32),
    ]
)
