"""Native (C++) runtime components.

The compute path is XLA/Pallas; this package holds the host-side native pieces —
currently the replay-sequence gather that feeds the device (``gather.cpp``).  The
shared library is compiled on first use with the image's g++ into
``_gather_<sha256 of gather.cpp>.so`` next to the source: the name is derived
from the committed source, so a library left on disk by another checkout or an
older ``gather.cpp`` can never be loaded in its place — a changed source simply
has no library yet and is built.  Every consumer falls back to the numpy path if
the toolchain is unavailable (logged once with the reason), so the framework
never hard-depends on it.  Disable explicitly with ``SHEEPRL_TPU_NATIVE=0``.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

import numpy as np

_HERE = Path(__file__).parent
_SRC = _HERE / "gather.cpp"
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
#: How :func:`load` got its answer: "built" (compiled from ``gather.cpp`` in this
#: process), "loaded" (the library for this exact source was already on disk),
#: "disabled" (``SHEEPRL_TPU_NATIVE=0``), "unavailable" (build or load failed —
#: the numpy path serves), or None before the first call.
status: Optional[str] = None
#: Gather calls served by the library vs handed back to the caller's numpy path
#: (library unavailable, or an array that is not C-contiguous / is empty).
calls = {"native": 0, "numpy": 0}

_log = logging.getLogger(__name__)
_I64P = np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")


def library_path() -> Path:
    """``_gather_<source hash>.so`` for the ``gather.cpp`` on disk right now."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _HERE / f"_gather_{digest}.so"


def _build(lib_path: Path) -> None:
    # Per-process tmp name: concurrent first-use builds (e.g. a multi-host launch on a
    # fresh checkout) must not write into each other's output; os.replace is atomic.
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", str(_SRC), "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load() -> Optional[ctypes.CDLL]:
    """The gather library, building it on first call; None when unavailable."""
    global _lib, _tried, status
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("SHEEPRL_TPU_NATIVE", "1") == "0":
            status = "disabled"
            return None
        lib_path = library_path()
        try:
            if lib_path.is_file():
                status = "loaded"
            else:
                _build(lib_path)
                status = "built"
            lib = ctypes.CDLL(str(lib_path))
        except (OSError, subprocess.SubprocessError) as e:
            status = "unavailable"
            detail = getattr(e, "stderr", b"") or b""
            _log.warning(
                "native gather unavailable (%s: %s %s); host sampling uses the numpy path",
                type(e).__name__, e, detail.decode(errors="replace")[-500:],
            )
            return None
        lib.gather_seq.restype = None
        lib.gather_seq.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, _I64P, _I64P,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ]
        lib.gather_rows.restype = None
        lib.gather_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, _I64P, _I64P,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ]
        _lib = lib
        return _lib


def gather_seq(
    src: np.ndarray,
    starts: np.ndarray,
    env_idx: np.ndarray,
    n_samples: int,
    seq_len: int,
    batch: int,
    start_offset: int = 0,
) -> Optional[np.ndarray]:
    """Gather ``[n_samples, T, B, *feat]`` sequences from a ``[size, n_envs, *feat]``
    C-contiguous buffer in one pass (time-major output, no transpose copy).
    ``starts``/``env_idx`` are ``[n_samples*B]`` int64, sample-major.  Returns None
    when the native path can't serve this array (not contiguous / lib missing)."""
    lib = load()
    if lib is None or not src.flags["C_CONTIGUOUS"] or src.size == 0:
        calls["numpy"] += 1
        return None
    calls["native"] += 1
    feat_bytes = int(src.itemsize * np.prod(src.shape[2:], dtype=np.int64))
    out = np.empty((n_samples, seq_len, batch) + src.shape[2:], dtype=src.dtype)
    lib.gather_seq(
        src.ctypes.data, out.ctypes.data,
        np.ascontiguousarray(starts, dtype=np.int64),
        np.ascontiguousarray(env_idx, dtype=np.int64),
        n_samples, seq_len, batch, src.shape[0], src.shape[1], feat_bytes,
        start_offset,
    )
    return out


def gather_rows(src: np.ndarray, rows: np.ndarray, envs: np.ndarray) -> Optional[np.ndarray]:
    """dst[i] = src[rows[i], envs[i]] for a ``[size, n_envs, *feat]`` buffer."""
    lib = load()
    if lib is None or not src.flags["C_CONTIGUOUS"] or src.size == 0:
        calls["numpy"] += 1
        return None
    calls["native"] += 1
    n = int(rows.shape[0])
    feat_bytes = int(src.itemsize * np.prod(src.shape[2:], dtype=np.int64))
    out = np.empty((n,) + src.shape[2:], dtype=src.dtype)
    lib.gather_rows(
        src.ctypes.data, out.ctypes.data,
        np.ascontiguousarray(rows, dtype=np.int64),
        np.ascontiguousarray(envs, dtype=np.int64),
        n, src.shape[1], feat_bytes,
    )
    return out
