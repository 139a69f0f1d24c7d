"""Loader for the C++ host runtime (native_src/dl4jtpu_native.cpp).

Build-on-first-use with g++ (cached in the package's build dir under a
name that carries a hash of the source, so a stale binary can never be
loaded and a fresh copy of the tree builds exactly once), loaded via
ctypes — the JavaCPP/JNI bridge analog of the reference's nd4j-native
backend loader, with the same silent-fallback contract: if no toolchain is
available the pure-NumPy implementations take over and everything still
runs (reference backend discovery falls back the same way).
"""
from __future__ import annotations

import ctypes
import hashlib
import subprocess
import threading
from pathlib import Path
from typing import Optional

import numpy as np

_SRC = Path(__file__).resolve().parent.parent.parent / "native_src" \
    / "dl4jtpu_native.cpp"
_BUILD_DIR = Path(__file__).resolve().parent / "_build"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _so_path() -> Path:
    """The cached build for the source AS IT IS NOW: file times do not
    survive a copy of the tree, content does."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _BUILD_DIR / f"libdl4jtpu_native-{digest}.so"


def _build() -> Optional[Path]:
    import os
    import uuid
    so = _so_path()
    if so.exists():
        return so
    _BUILD_DIR.mkdir(parents=True, exist_ok=True)
    # compile to a unique temp path and rename atomically: concurrent
    # builders (multi-process tests) and killed builds must never leave a
    # half-written .so at the canonical path
    tmp = _BUILD_DIR / f".build-{uuid.uuid4().hex}.so"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           str(_SRC), "-o", str(tmp)]
    try:
        proc = subprocess.run(cmd, capture_output=True, timeout=120)
        if proc.returncode != 0:
            return None
        os.replace(tmp, so)
        return so
    except (OSError, subprocess.TimeoutExpired):
        return None
    finally:
        tmp.unlink(missing_ok=True)


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, or None (fallback mode)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not _SRC.exists():
            return None
        so = _build()
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(str(so))
        except OSError:
            so.unlink(missing_ok=True)  # corrupt artifact: force rebuild next run
            return None
        c_i64, c_f32p, c_u8p, c_charp = (ctypes.c_int64,
                                         ctypes.POINTER(ctypes.c_float),
                                         ctypes.POINTER(ctypes.c_ubyte),
                                         ctypes.c_char_p)
        lib.idx_header.restype = ctypes.c_int
        lib.idx_header.argtypes = [c_u8p, c_i64, ctypes.POINTER(c_i64),
                                   ctypes.POINTER(ctypes.c_int)]
        lib.idx_decode_f32.restype = c_i64
        lib.idx_decode_f32.argtypes = [c_u8p, c_i64, c_f32p, c_i64,
                                       ctypes.c_float]
        lib.csv_decode_f32.restype = c_i64
        lib.csv_decode_f32.argtypes = [c_charp, c_i64, ctypes.c_char,
                                       c_f32p, c_i64]
        lib.csv_shape.restype = None
        lib.csv_shape.argtypes = [c_charp, c_i64, ctypes.c_char,
                                  ctypes.POINTER(c_i64),
                                  ctypes.POINTER(c_i64)]
        lib.staging_alloc.restype = ctypes.c_void_p
        lib.staging_alloc.argtypes = [c_i64]
        lib.staging_release.restype = None
        lib.staging_release.argtypes = [ctypes.c_void_p, c_i64]
        lib.staging_stats.restype = None
        lib.staging_stats.argtypes = [ctypes.POINTER(c_i64)] * 4
        _lib = lib
        return _lib


def native_available() -> bool:
    return get_lib() is not None


# -- high-level wrappers (NumPy fallback built in) -----------------------------

def decode_idx(data: bytes, scale: float = 1.0) -> np.ndarray:
    """Decode an IDX u8 container to a float32 ndarray (scaled). The MNIST
    fetcher path (reference datasets/mnist/MnistImageFile)."""
    lib = get_lib()
    if lib is None:
        return _decode_idx_numpy(data, scale)
    buf = (ctypes.c_ubyte * len(data)).from_buffer_copy(data)
    dims = (ctypes.c_int64 * 8)()
    dtype = ctypes.c_int()
    ndim = lib.idx_header(buf, len(data), dims, ctypes.byref(dtype))
    if ndim < 0 or dtype.value != 0x08:
        return _decode_idx_numpy(data, scale)
    shape = tuple(dims[i] for i in range(ndim))
    out = np.empty(int(np.prod(shape)), np.float32)
    n = lib.idx_decode_f32(buf, len(data),
                           out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                           out.size, scale)
    if n != out.size:
        return _decode_idx_numpy(data, scale)
    return out.reshape(shape)


def _decode_idx_numpy(data: bytes, scale: float) -> np.ndarray:
    ndim = data[3]
    shape = tuple(int.from_bytes(data[4 + 4 * i:8 + 4 * i], "big")
                  for i in range(ndim))
    arr = np.frombuffer(data, np.uint8, offset=4 + 4 * ndim,
                        count=int(np.prod(shape)))
    return (arr.astype(np.float32) * scale).reshape(shape)


def decode_csv(text: bytes, delimiter: str = ",") -> np.ndarray:
    """One-pass CSV -> [rows, cols] float32 (Canova CSVRecordReader hot
    path). Rows must be rectangular."""
    lib = get_lib()
    if lib is None:
        return _decode_csv_numpy(text, delimiter)
    n_rows = ctypes.c_int64()
    n_vals = ctypes.c_int64()
    lib.csv_shape(text, len(text), delimiter.encode()[0:1],
                  ctypes.byref(n_rows), ctypes.byref(n_vals))
    rows, vals = n_rows.value, n_vals.value
    if rows <= 0 or vals <= 0 or vals % rows != 0:
        return _decode_csv_numpy(text, delimiter)
    out = np.empty(vals, np.float32)
    n = lib.csv_decode_f32(text, len(text), delimiter.encode()[0:1],
                           out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                           vals)
    if n != vals:
        return _decode_csv_numpy(text, delimiter)
    return out.reshape(rows, vals // rows)


def _decode_csv_numpy(text: bytes, delimiter: str) -> np.ndarray:
    lines = [l for l in text.decode().splitlines() if l.strip()]
    return np.asarray([[float(v) for v in l.split(delimiter)]
                       for l in lines], np.float32)


class StagingBuffer:
    """A pooled page-aligned host buffer exposed as a NumPy array — the
    recycling staging allocation the async prefetch path fills before
    host->HBM transfer (JITA/AffinityManager analog)."""

    def __init__(self, nbytes: int):
        self._lib = get_lib()
        self.nbytes = nbytes
        if self._lib is not None:
            self._ptr = self._lib.staging_alloc(nbytes)
            if not self._ptr:
                raise MemoryError(f"staging_alloc({nbytes}) failed")
            self.array = np.ctypeslib.as_array(
                ctypes.cast(self._ptr, ctypes.POINTER(ctypes.c_ubyte)),
                (nbytes,))
        else:
            self._ptr = None
            self.array = np.empty(nbytes, np.uint8)

    def as_float32(self, shape) -> np.ndarray:
        n = int(np.prod(shape))
        return self.array[:n * 4].view(np.float32).reshape(shape)

    def release(self) -> None:
        if self._ptr is not None and self._lib is not None:
            self._lib.staging_release(self._ptr, self.nbytes)
            self._ptr = None
            self.array = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()


def staging_stats() -> dict:
    lib = get_lib()
    if lib is None:
        return {"native": False}
    vals = [ctypes.c_int64() for _ in range(4)]
    lib.staging_stats(*[ctypes.byref(v) for v in vals])
    return {"native": True, "live": vals[0].value, "reused": vals[1].value,
            "allocated": vals[2].value, "pooled": vals[3].value}
