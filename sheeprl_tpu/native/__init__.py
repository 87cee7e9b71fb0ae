"""Native (C++) host-side kernels, built on demand and bound via ctypes.

The toolchain ships g++ but no pybind11, so the binding is a plain C ABI +
ctypes (see replay_gather.cpp for the kernels and why they exist). The
shared object is compiled on first use from replay_gather.cpp as it stands,
into ``<checkout>/.native_build/`` (git-ignored) under a name keyed by the
source's content, so an edited source or a copied tree never loads a stale
build. Every consumer must handle `load_native() is None` and keep a
pure-numpy path: it gives the same answer, slower. `native_status()` says
which of the two a process got, and why.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import Any, Dict, Optional

import numpy as np

_SRC = Path(__file__).resolve().parent / "replay_gather.cpp"
_BUILD_DIR = Path(__file__).resolve().parents[2] / ".native_build"
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
_STATUS: Dict[str, Any] = {"loaded": False, "path": None, "error": "not tried yet"}

_N_THREADS = int(os.environ.get("SHEEPRL_TPU_NATIVE_THREADS", "4"))


def _so_path() -> Path:
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _BUILD_DIR / f"replay_gather_{digest}.so"


def _build(so_path: Path) -> None:
    """Compile replay_gather.cpp to ``so_path``. Builds under a name of this
    process's own and renames, so concurrent builders (pytest-xdist workers)
    never load a half-written file. Raises on failure."""
    so_path.parent.mkdir(parents=True, exist_ok=True)
    tmp = so_path.with_suffix(f".{os.getpid()}.tmp")
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread", str(_SRC), "-o", str(tmp)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so_path)
    finally:
        tmp.unlink(missing_ok=True)


def load_native() -> Optional[ctypes.CDLL]:
    """Compile (once) and load the native library; None if unavailable
    (`native_status()` then carries the reason)."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("SHEEPRL_TPU_DISABLE_NATIVE"):
            _STATUS["error"] = "disabled by SHEEPRL_TPU_DISABLE_NATIVE"
            return None
        try:
            so_path = _so_path()
            if not so_path.is_file():
                _build(so_path)
            lib = ctypes.CDLL(str(so_path))
        except (OSError, subprocess.SubprocessError) as err:
            detail = getattr(err, "stderr", b"") or b""
            _STATUS["error"] = f"{type(err).__name__}: {err} {detail.decode(errors='replace')[-400:]}".strip()
            return None
        lib.gather_rows.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_int32,
        ]
        lib.gather_rows.restype = None
        lib.circular_add.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int64,
        ]
        lib.circular_add.restype = None
        _LIB = lib
        _STATUS.update(loaded=True, path=str(so_path), error=None)
        return _LIB


def native_status() -> Dict[str, Any]:
    """``{loaded, path, error}`` after attempting the load: whether this
    process gathers through the native library or through numpy."""
    load_native()
    return dict(_STATUS)


def gather_rows(src: np.ndarray, row_idx: np.ndarray, out_shape) -> Optional[np.ndarray]:
    """Gather rows of a C-contiguous array by flat leading-axis index.

    `src` is treated as [R, F] with R = src.shape[0] (callers pre-flatten);
    `row_idx` (any shape, int64) selects rows in destination order. Returns
    the gathered array reshaped to `out_shape`, or None if the native path
    cannot handle the input (caller falls back to numpy)."""
    lib = load_native()
    if lib is None:
        return None
    src = np.asarray(src)
    if not src.flags["C_CONTIGUOUS"] or src.dtype.hasobject:
        return None
    idx = np.ascontiguousarray(row_idx, dtype=np.int64)
    n_out = idx.size
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    if row_bytes == 0:
        return np.empty(out_shape, dtype=src.dtype)
    out = np.empty((n_out,) + src.shape[1:], dtype=src.dtype)
    lib.gather_rows(
        src.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(row_bytes),
        idx.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int64(n_out),
        out.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_int32(_N_THREADS),
    )
    return out.reshape(out_shape)
