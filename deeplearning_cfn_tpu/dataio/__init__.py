"""Native data-loading bindings (ctypes over dataio.cpp).

Builds the shared library on first use (g++ -O3, cached beside the source
under a name that carries a hash of the source) and exposes the batch
gather/augment entry points. Without a compiler, or when the build fails,
``get_lib`` returns None and pipeline.py takes the Python path — mirroring
how the reference degraded when its native input pipelines were
unavailable. The degradation is visible: :func:`status` says which loader
is active and why, and a failed build prints the compiler's error once.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import shutil
import subprocess
import sys
import threading
from typing import Optional

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "dataio.cpp")
_ABI_VERSION = 2
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_status = "not loaded yet"


def _lib_path() -> str:
    """Where the library built from the CURRENT source lives. Keyed on the
    source's hash, not its mtime: a copied tree keeps no mtimes, and a
    library git never saw must not be trusted for being newer."""
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    return os.path.join(os.path.dirname(_SRC), f"_dataio.{digest}.so")


def _build(lib_path: str) -> str:
    """Compile ``_SRC`` to ``lib_path``; returns "" or why it failed."""
    if shutil.which("g++") is None:
        return "no g++ on PATH"
    # Compile to a private temp path, then rename: concurrent processes
    # (multi-host launch, parallel pytest) must never dlopen a half-written
    # library.
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           "-o", tmp, _SRC]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
        if proc.returncode != 0 or not os.path.exists(tmp):
            print(f"[dataio] native loader build failed "
                  f"(rc={proc.returncode}); using the Python loader:\n"
                  f"{proc.stderr.strip()}", file=sys.stderr, flush=True)
            return f"build failed (rc={proc.returncode}, error on stderr)"
        os.replace(tmp, lib_path)
    except (OSError, subprocess.TimeoutExpired) as e:
        print(f"[dataio] native loader build failed: {e!r}; using the "
              f"Python loader", file=sys.stderr, flush=True)
        return f"build failed ({e!r})"
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass
    # Libraries built from an older source are dead weight now.
    for old in glob.glob(os.path.join(os.path.dirname(lib_path),
                                      "_dataio*.so")):
        if old != lib_path:
            try:
                os.unlink(old)
            except OSError:
                pass
    return ""


def _load(lib_path: str) -> Optional[ctypes.CDLL]:
    """dlopen + bind; None (with ``_status`` set) when it cannot."""
    global _status
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError as e:
        _status = f"python loader ({os.path.basename(lib_path)}: {e})"
        return None
    u64, i32, i64, f32p, i32p = (ctypes.c_uint64, ctypes.c_int,
                                 ctypes.c_int64,
                                 ctypes.POINTER(ctypes.c_float),
                                 ctypes.POINTER(ctypes.c_int32))
    lib.dlcfn_version.restype = ctypes.c_int
    if lib.dlcfn_version() != _ABI_VERSION:
        _status = (f"python loader (dataio.cpp is ABI "
                   f"{lib.dlcfn_version()}, bindings expect {_ABI_VERSION})")
        return None
    for fn, argtypes in (
            (lib.dlcfn_gather_augment,
             [f32p, i32p, f32p, i32, i32, i32, i32, i32, u64, i32, i32]),
            (lib.dlcfn_gather_rows_f32, [f32p, i32p, f32p, i32, i64, i32]),
            (lib.dlcfn_gather_rows_i32, [i32p, i32p, i32p, i32, i64, i32]),
            (lib.dlcfn_crop_resize_norm,
             [ctypes.POINTER(u64), i32, i32, f32p, i32, i32, u64, i32,
              f32p, f32p, i32])):
        fn.argtypes = argtypes
        fn.restype = None
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, building it if needed; None if unavailable."""
    global _lib, _tried, _status
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        lib_path = _lib_path()
        how = "cached"
        if not os.path.exists(lib_path):
            failure = _build(lib_path)
            if failure:
                _status = f"python loader ({failure})"
                return None
            how = "built now"
        _lib = _load(lib_path)
        if _lib is not None:
            _status = f"native ({os.path.basename(lib_path)}, {how})"
        return _lib


def status() -> str:
    """One line: which loader is active and why (``doctor``,
    ``chip_smoke.py``)."""
    get_lib()
    return _status


def available() -> bool:
    return get_lib() is not None


def _f32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _i32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def gather_augment(src: np.ndarray, idx: np.ndarray, pad: int, seed: int,
                   augment: bool, nthreads: int = 4) -> np.ndarray:
    """Batched image gather with optional crop/flip augmentation.

    src [N,H,W,C] f32 contiguous; idx [B] i32 → out [B,H,W,C].
    """
    lib = get_lib()
    assert lib is not None, "native dataio unavailable"
    src = np.ascontiguousarray(src, np.float32)
    idx = np.ascontiguousarray(idx, np.int32)
    b = len(idx)
    _, h, w, c = src.shape
    out = np.empty((b, h, w, c), np.float32)
    lib.dlcfn_gather_augment(_f32(src), _i32(idx), _f32(out), b, h, w, c,
                             pad, seed & (2**64 - 1), int(augment), nthreads)
    return out


def crop_resize_norm(src_ptrs: np.ndarray, src_hw, out_size: int,
                     seed: int, augment: bool, mean: np.ndarray,
                     std: np.ndarray, nthreads: int = 4) -> np.ndarray:
    """Batched u8 record → cropped/resized/normalized f32 [B,S,S,3].

    ``src_ptrs``: uint64 array of B addresses, each pointing at a contiguous
    u8 HWC image payload of shape ``src_hw + (3,)`` (e.g. records inside
    mmap'd ImageNet shards). Augmentation (random-resized-crop + flip) is
    deterministic per (seed, batch position); see dataio.cpp for the RNG
    contract shared with the Python fallback.
    """
    lib = get_lib()
    assert lib is not None, "native dataio unavailable"
    src_ptrs = np.ascontiguousarray(src_ptrs, np.uint64)
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    b = len(src_ptrs)
    out = np.empty((b, out_size, out_size, 3), np.float32)
    lib.dlcfn_crop_resize_norm(
        src_ptrs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        int(src_hw[0]), int(src_hw[1]), _f32(out), b, out_size,
        seed & (2**64 - 1), int(augment), _f32(mean), _f32(std), nthreads)
    return out


def gather_rows(src: np.ndarray, idx: np.ndarray, nthreads: int = 4
                ) -> np.ndarray:
    """out[b] = src[idx[b]] for f32/i32 arrays of any trailing shape."""
    lib = get_lib()
    assert lib is not None, "native dataio unavailable"
    idx = np.ascontiguousarray(idx, np.int32)
    row = int(np.prod(src.shape[1:], dtype=np.int64)) if src.ndim > 1 else 1
    out = np.empty((len(idx),) + src.shape[1:], src.dtype)
    if src.dtype == np.float32:
        src = np.ascontiguousarray(src)
        lib.dlcfn_gather_rows_f32(_f32(src), _i32(idx), _f32(out),
                                  len(idx), row, nthreads)
    elif src.dtype == np.int32:
        src = np.ascontiguousarray(src)
        lib.dlcfn_gather_rows_i32(_i32(src), _i32(idx), _i32(out),
                                  len(idx), row, nthreads)
    else:
        return src[idx]
    return out
