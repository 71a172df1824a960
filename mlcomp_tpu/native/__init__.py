"""ctypes bindings for the native data-ops library (C++, GIL-free).

Builds ``libmlcdata-<hash>.so`` from ``dataops.cpp`` + ``schedcore.cpp``
on first use (g++ is in the image).  The file name is keyed by a hash
of the sources and the compile command, so a binary left in the tree by
an older checkout — or copied in from somewhere else — is never loaded
for these sources: a different hash is a different path, and the build
runs.  No ``-march=native``: the tree gets copied between hosts, and a
binary tuned to the host that built it may not run on the next one.

Every entry point degrades gracefully: if the toolchain or the build is
unavailable, ``lib()`` returns None and callers (data/loader.py) fall
back to the numpy path — same results, fewer cores.  ``status()`` says
which of the two happened, so a caller that must not run on the
fallback unknowingly (chip_smoke.py) can tell a deliberate
``MLCOMP_TPU_NO_NATIVE`` from a build that was attempted and failed.
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

_DIR = Path(__file__).resolve().parent
_SRCS = [_DIR / "dataops.cpp", _DIR / "schedcore.cpp"]
_FLAGS = ["-O3", "-shared", "-fPIC", "-pthread"]

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_build_error: Optional[str] = None


def _so_path() -> Path:
    h = hashlib.sha256(" ".join(_FLAGS).encode())
    for s in _SRCS:
        h.update(s.read_bytes())
    return _DIR / f"libmlcdata-{h.hexdigest()[:16]}.so"


def _build(so: Path) -> Optional[str]:
    """Compile to ``so`` (atomically: a concurrent first use in another
    process never loads a half-written file); returns the error text on
    failure."""
    tmp = so.with_name(f"{so.name}.{os.getpid()}.tmp")
    cmd = ["g++", *_FLAGS, *[str(s) for s in _SRCS], "-o", str(tmp)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return None
    except subprocess.CalledProcessError as e:
        return (e.stderr or b"").decode("utf-8", "replace")[-2000:] or str(e)
    except (OSError, subprocess.SubprocessError) as e:
        return f"{type(e).__name__}: {e}"
    finally:
        tmp.unlink(missing_ok=True)


def status() -> Dict[str, Any]:
    """Whether the native core is in use, after a ``lib()`` attempt:
    ``loaded``; ``disabled`` (MLCOMP_TPU_NO_NATIVE); ``build_error``
    (text of a build or load that was attempted and failed)."""
    return {
        "loaded": lib() is not None,
        "disabled": bool(os.environ.get("MLCOMP_TPU_NO_NATIVE")),
        "build_error": _build_error,
    }


def lib() -> Optional[ctypes.CDLL]:
    """The loaded library, building it on first use; None if unavailable."""
    global _lib, _tried, _build_error
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("MLCOMP_TPU_NO_NATIVE"):
            return None
        so = _so_path()
        if not so.exists():
            _build_error = _build(so)
            if _build_error is not None:
                return None
        try:
            l = ctypes.CDLL(str(so))
        except OSError as e:
            _build_error = f"load failed: {e}"
            return None
        l.mlc_gather.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int,
        ]
        l.mlc_shuffle.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64]
        l.mlc_iota.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        l.mlc_dag_analyze.argtypes = [
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        l.mlc_dag_analyze.restype = ctypes.c_int64
        _lib = l
        return _lib


def gather_rows(
    src: np.ndarray, idx: np.ndarray, n_threads: Optional[int] = None
) -> Optional[np.ndarray]:
    """dst[i] = src[idx[i]] via the native thread pool; None → caller
    falls back to numpy. src must be C-contiguous."""
    l = lib()
    if l is None or not src.flags.c_contiguous or src.ndim < 1:
        return None
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    dst = np.empty((len(idx),) + src.shape[1:], dtype=src.dtype)
    if n_threads is None:
        n_threads = min(os.cpu_count() or 1, 16)
    l.mlc_gather(
        src.ctypes.data, row_bytes, idx.ctypes.data, len(idx),
        dst.ctypes.data, n_threads,
    )
    return dst


def shuffled_indices(n: int, seed: int) -> Optional[np.ndarray]:
    """Deterministic native Fisher–Yates permutation of arange(n)."""
    l = lib()
    if l is None:
        return None
    idx = np.empty(n, dtype=np.int64)
    l.mlc_iota(idx.ctypes.data, n)
    l.mlc_shuffle(idx.ctypes.data, n, np.uint64(seed & (2**64 - 1)))
    return idx


def dag_analyze(dep_offsets, deps, status, priority):
    """One-pass ready-set + doom propagation over a dependency CSR.

    Returns ``(ready_indices, doomed_indices)`` (numpy int64 arrays) or
    None when the native library is unavailable or the graph is cyclic —
    callers fall back to the Python graph walk (dag/graph.py).
    """
    l = lib()
    if l is None:
        return None
    dep_offsets = np.ascontiguousarray(dep_offsets, dtype=np.int64)
    deps = np.ascontiguousarray(deps, dtype=np.int64)
    status = np.ascontiguousarray(status, dtype=np.int8)
    priority = np.ascontiguousarray(priority, dtype=np.int64)
    n = len(status)
    ready = np.empty(n, dtype=np.int64)
    doomed = np.empty(n, dtype=np.int64)
    n_doomed = np.zeros(1, dtype=np.int64)
    n_ready = l.mlc_dag_analyze(
        n, dep_offsets.ctypes.data, deps.ctypes.data, status.ctypes.data,
        priority.ctypes.data, ready.ctypes.data, doomed.ctypes.data,
        n_doomed.ctypes.data,
    )
    if n_ready < 0:
        return None
    return ready[:n_ready].copy(), doomed[: n_doomed[0]].copy()
