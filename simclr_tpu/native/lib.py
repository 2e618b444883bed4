"""ctypes bindings for the native gather library, with lazy self-build.

Build strategy: compile ``gather.cpp`` with g++ into ``_build/`` on first
use and record the source's sha256 next to the library. The library is
rebuilt whenever that record does not match ``gather.cpp`` as it is on disk,
so only tracked source decides what runs — a ``.so`` left over from another
checkout or copied in from another machine is never loaded as it is. A failed
build (no compiler, read-only FS) logs one warning and selects the NumPy path
for the rest of the process. ctypes rather than pybind11 because this
environment ships no pybind11 and the ABI here is two flat C functions over
raw pointers.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "gather.cpp")
_BUILD_DIR = os.path.join(_DIR, "_build")
_SO = os.path.join(_BUILD_DIR, "libsimclr_gather.so")
_SO_SOURCE_HASH = _SO + ".source-sha256"

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False

DEFAULT_THREADS = min(8, os.cpu_count() or 1)


def _source_hash() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _built_from(source_hash: str) -> bool:
    """True when ``_SO`` exists and was built from exactly this source."""
    try:
        with open(_SO_SOURCE_HASH) as f:
            return os.path.exists(_SO) and f.read().strip() == source_hash
    except OSError:
        return False


def _build(source_hash: str) -> None:
    """Compile ``gather.cpp`` and record the hash it was built from. Raises
    ``OSError`` / ``subprocess.SubprocessError`` on failure."""
    from simclr_tpu.utils.ioutil import atomic_write

    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{_SO}.tmp.{os.getpid()}"
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
        _SRC, "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    atomic_write(_SO_SOURCE_HASH, lambda f: f.write(source_hash + "\n"))


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            source_hash = _source_hash()
            if not _built_from(source_hash):
                _build(source_hash)
            lib = ctypes.CDLL(_SO)
        except (OSError, subprocess.SubprocessError) as e:
            from simclr_tpu.utils.logging import get_logger

            detail = getattr(e, "stderr", b"") or b""
            get_logger().warning(
                "native gather library unavailable (%r %s); host batch "
                "gathers use the NumPy path for this process",
                e, detail.decode(errors="replace").strip()[-500:],
            )
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.gather_rows.argtypes = [
            u8p, i64p, u8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32
        ]
        lib.gather_rows.restype = None
        lib.gather_rows2.argtypes = [
            u8p, ctypes.c_int64, u8p,
            u8p, ctypes.c_int64, u8p,
            i64p, ctypes.c_int64, ctypes.c_int32,
        ]
        lib.gather_rows2.restype = None
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


def _as_u8(view: np.ndarray):
    return view.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _check_bounds(idx64: np.ndarray, n_rows: int) -> None:
    # the C path memcpy's blindly; reject anything numpy would reject (and
    # negative indices, which numpy would wrap but a raw pointer would not)
    if len(idx64) and (idx64.min() < 0 or idx64.max() >= n_rows):
        raise IndexError(
            f"gather indices out of bounds for {n_rows} rows "
            f"(min {idx64.min()}, max {idx64.max()})"
        )


def gather_rows(
    src: np.ndarray, idx: np.ndarray, n_threads: int = DEFAULT_THREADS
) -> np.ndarray:
    """``src[idx]`` for a C-contiguous array of non-negative in-range
    indices, multithreaded when native; rows are whatever trails the first
    axis. Out-of-range or negative indices raise ``IndexError`` on both the
    native and fallback paths.
    """
    lib = _load()
    src = np.ascontiguousarray(src)
    idx64 = np.ascontiguousarray(idx, dtype=np.int64)
    _check_bounds(idx64, len(src))
    if lib is None:
        return src[idx64]
    out = np.empty((len(idx64), *src.shape[1:]), dtype=src.dtype)
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    lib.gather_rows(
        _as_u8(src.view(np.uint8).reshape(-1)),
        idx64.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        _as_u8(out.view(np.uint8).reshape(-1)),
        len(idx64),
        row_bytes,
        int(n_threads),
    )
    return out


def gather_rows2(
    src_a: np.ndarray,
    src_b: np.ndarray,
    idx: np.ndarray,
    n_threads: int = DEFAULT_THREADS,
) -> tuple[np.ndarray, np.ndarray]:
    """(src_a[idx], src_b[idx]) in one native pass (images + labels).

    Same bounds contract as :func:`gather_rows`.
    """
    lib = _load()
    src_a = np.ascontiguousarray(src_a)
    src_b = np.ascontiguousarray(src_b)
    idx64 = np.ascontiguousarray(idx, dtype=np.int64)
    _check_bounds(idx64, min(len(src_a), len(src_b)))
    if lib is None:
        return src_a[idx64], src_b[idx64]
    out_a = np.empty((len(idx64), *src_a.shape[1:]), dtype=src_a.dtype)
    out_b = np.empty((len(idx64), *src_b.shape[1:]), dtype=src_b.dtype)
    rb_a = src_a.dtype.itemsize * int(np.prod(src_a.shape[1:], dtype=np.int64))
    rb_b = src_b.dtype.itemsize * int(np.prod(src_b.shape[1:], dtype=np.int64))
    lib.gather_rows2(
        _as_u8(src_a.view(np.uint8).reshape(-1)), rb_a,
        _as_u8(out_a.view(np.uint8).reshape(-1)),
        _as_u8(src_b.view(np.uint8).reshape(-1)), rb_b,
        _as_u8(out_b.view(np.uint8).reshape(-1)),
        idx64.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(idx64),
        int(n_threads),
    )
    return out_a, out_b
