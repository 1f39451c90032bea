"""ctypes binding for native/sortutil.cpp: GIL-released argsort and
unique+inverse over int64 arrays, used by the automaton assembler so
background rebuilds stop freezing the insert/publish thread (numpy's
sorts hold the GIL)."""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_SRC = os.path.join(_REPO, "native", "sortutil.cpp")
_SO = os.path.join(_REPO, "native", "build", "libsortutil.so")

_lock = threading.Lock()
_lib = None
_lib_failed = False

_I64P = ctypes.POINTER(ctypes.c_int64)


def load():
    global _lib, _lib_failed
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        if os.environ.get("EMQX_TPU_NO_NATIVE_SORT") == "1":
            _lib_failed = True
            return None
        try:
            if not os.path.exists(_SO) or os.path.getmtime(
                _SO
            ) < os.path.getmtime(_SRC):
                os.makedirs(os.path.dirname(_SO), exist_ok=True)
                # built from the committed source on first load in a fresh
                # checkout (native/build/ is not committed) and again when
                # the source is newer — never on the steady-state path, so
                # the loop stall is accepted
                # brokerlint: ignore[ASYNC101]
                subprocess.run(
                    ["g++", "-O3", "-fPIC", "-shared", "-std=c++17",
                     "-Wall", "-o", _SO, _SRC],
                    check=True,
                    capture_output=True,
                )
            lib = ctypes.CDLL(_SO)
            lib.su_argsort_i64.argtypes = [_I64P, ctypes.c_int64, _I64P]
            lib.su_unique_inverse_i64.restype = ctypes.c_int64
            lib.su_unique_inverse_i64.argtypes = [
                _I64P, ctypes.c_int64, _I64P, _I64P, _I64P,
            ]
            _lib = lib
        except Exception:
            logging.getLogger("emqx_tpu.ops").exception(
                "native sortutil build failed; using numpy sorts"
            )
            _lib_failed = True
        return _lib


def _p(a: np.ndarray) -> "ctypes.POINTER":
    return a.ctypes.data_as(_I64P)


def argsort_i64(arr: np.ndarray) -> np.ndarray:
    """Stable argsort (int64), GIL released; numpy fallback."""
    lib = load()
    a = np.ascontiguousarray(arr, np.int64)
    if lib is None or len(a) < 4096:
        return np.argsort(a, kind="stable")
    out = np.empty(len(a), np.int64)
    lib.su_argsort_i64(_p(a), len(a), _p(out))
    return out


def unique_inverse_i64(
    arr: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray]:
    """``np.unique(arr, return_inverse=True)`` (int64), GIL released;
    numpy fallback below the native-worthwhile size."""
    lib = load()
    a = np.ascontiguousarray(arr, np.int64)
    if lib is None or len(a) < 4096:
        return np.unique(a, return_inverse=True)
    n = len(a)
    uniq = np.empty(n, np.int64)
    inv = np.empty(n, np.int64)
    scratch = np.empty(n, np.int64)
    m = lib.su_unique_inverse_i64(_p(a), n, _p(uniq), _p(inv), _p(scratch))
    return uniq[:m].copy(), inv
