"""Native (C++) data-plane kernels, loaded via ctypes.

The reference delegates its native compute to torch/cuDNN/NCCL binaries
(SURVEY.md §2.8); the TPU rebuild's device compute is XLA, and this package
holds the *host-side* native pieces — currently the fused replay-buffer
gather (`gather.cpp`) that feeds the host→HBM pipeline.

Build model: no pybind11/pip in this image, so the shared object is compiled
lazily with g++ from the committed ``gather.cpp`` the first time it's needed
and cached under a content hash (rebuilds only when the source changes) in
``SHEEPRL_TPU_NATIVE_CACHE`` when that is set, else in one fixed git-ignored
directory inside the checkout. If there is no compiler or the build or load
fails, callers fall back to numpy — and :func:`status` (folded into every run
record as ``native_gather``) says which of the two ran and why.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "gather.cpp")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
_WHY_NUMPY = ""  # set when a load attempt ended on the numpy path

#: the .so directory when SHEEPRL_TPU_NATIVE_CACHE does not place it
REPO_NATIVE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".native_cache"
)

DEFAULT_THREADS = min(8, os.cpu_count() or 1)


def _build_dir() -> str:
    d = os.environ.get("SHEEPRL_TPU_NATIVE_CACHE") or REPO_NATIVE_CACHE_DIR
    os.makedirs(d, exist_ok=True)
    return d


def _compile() -> Optional[str]:
    global _WHY_NUMPY
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so_path = os.path.join(_build_dir(), f"gather_{digest}.so")
    if os.path.exists(so_path):
        return so_path
    # unique temp output per process: concurrent first-use builds (the
    # multi-process launcher tests, two runs on one host) must not interleave
    # writes before the atomic publish
    tmp_path = f"{so_path}.{os.getpid()}.tmp"
    cmd = [
        "g++",
        "-O3",
        "-shared",
        "-fPIC",
        "-std=c++17",
        "-pthread",
        _SRC,
        "-o",
        tmp_path,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp_path, so_path)
    except (OSError, subprocess.SubprocessError) as err:
        detail = getattr(err, "stderr", b"") or b""
        _WHY_NUMPY = f"g++ build failed: {err!r} {detail[-300:].decode(errors='replace')}".strip()
        return None
    finally:
        if os.path.exists(tmp_path):
            try:
                os.remove(tmp_path)
            except OSError:
                pass
    return so_path


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED, _WHY_NUMPY
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("SHEEPRL_TPU_DISABLE_NATIVE"):
            _WHY_NUMPY = "SHEEPRL_TPU_DISABLE_NATIVE is set"
            return None
        so_path = _compile()
        if so_path is None:
            return None
        try:
            lib = ctypes.CDLL(so_path)
        except OSError as err:
            _WHY_NUMPY = f"loading {so_path} failed: {err!r}"
            return None
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.gather_sequences.restype = ctypes.c_int
        lib.gather_sequences.argtypes = [
            ctypes.c_void_p,  # src
            ctypes.c_int64,  # buffer_size
            ctypes.c_int64,  # n_envs
            ctypes.c_int64,  # item_bytes
            i64p,  # starts
            i64p,  # envs
            ctypes.c_int64,  # batch_dim
            ctypes.c_int64,  # seq_len
            ctypes.c_int64,  # n_samples
            ctypes.c_int64,  # batch
            ctypes.c_int64,  # shift
            ctypes.c_void_p,  # dst
            ctypes.c_int,  # n_threads
        ]
        lib.gather_rows.restype = ctypes.c_int
        lib.gather_rows.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_int64,
            i64p,
            i64p,
            ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_int,
        ]
        _LIB = lib
        return _LIB


def available() -> bool:
    """True when the native gather library is (or can be) loaded."""
    return _load() is not None


def status() -> str:
    """Which gather path this process runs: ``native`` (the g++ build is
    loaded), ``numpy (<why>)`` (a load was attempted and failed — callers are
    on the numpy fallback), or ``not loaded`` (nothing asked for a host
    gather yet, e.g. the replay ring lives on the device)."""
    if _LIB is not None:
        return "native"
    return f"numpy ({_WHY_NUMPY})" if _TRIED else "not loaded"


def gather_sequences(
    src: np.ndarray,
    starts: np.ndarray,
    envs: np.ndarray,
    seq_len: int,
    n_samples: int,
    batch: int,
    shift: int = 0,
) -> Optional[np.ndarray]:
    """Fused gather+layout: ring buffer ``src [size, n_envs, ...]`` →
    contiguous ``[n_samples, seq_len, batch, ...]`` with sequence ``s=(n,b)``
    reading rows ``(starts[s]+shift+t) % size`` of env ``envs[s]``.

    Returns None when the native library is unavailable or the input layout
    isn't supported (caller falls back to numpy).
    """
    lib = _load()
    if lib is None:
        return None
    if src.ndim < 2 or not src.flags.c_contiguous or src.dtype.hasobject:
        return None
    size, n_envs = src.shape[0], src.shape[1]
    item_shape = src.shape[2:]
    item_bytes = int(np.prod(item_shape, dtype=np.int64)) * src.itemsize
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    envs = np.ascontiguousarray(envs, dtype=np.int64)
    batch_dim = int(starts.shape[0])
    if batch_dim != n_samples * batch or envs.shape[0] != batch_dim:
        return None
    dst = np.empty((n_samples, seq_len, batch) + item_shape, dtype=src.dtype)
    rc = lib.gather_sequences(
        src.ctypes.data_as(ctypes.c_void_p),
        size,
        n_envs,
        item_bytes,
        starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        envs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        batch_dim,
        seq_len,
        n_samples,
        batch,
        shift,
        dst.ctypes.data_as(ctypes.c_void_p),
        DEFAULT_THREADS,
    )
    return dst if rc == 0 else None


def gather_rows(src: np.ndarray, rows: np.ndarray, envs: np.ndarray) -> Optional[np.ndarray]:
    """Row gather: ``src [size, n_envs, ...]`` → ``[count, ...]`` where row i
    is ``src[rows[i] % size, envs[i]]``. None → caller falls back to numpy."""
    lib = _load()
    if lib is None:
        return None
    if src.ndim < 2 or not src.flags.c_contiguous or src.dtype.hasobject:
        return None
    size, n_envs = src.shape[0], src.shape[1]
    item_shape = src.shape[2:]
    item_bytes = int(np.prod(item_shape, dtype=np.int64)) * src.itemsize
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    envs = np.ascontiguousarray(envs, dtype=np.int64)
    count = int(rows.shape[0])
    if envs.shape[0] != count:
        return None
    dst = np.empty((count,) + item_shape, dtype=src.dtype)
    rc = lib.gather_rows(
        src.ctypes.data_as(ctypes.c_void_p),
        size,
        n_envs,
        item_bytes,
        rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        envs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        count,
        dst.ctypes.data_as(ctypes.c_void_p),
        DEFAULT_THREADS,
    )
    return dst if rc == 0 else None
