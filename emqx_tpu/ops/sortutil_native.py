"""ctypes binding for native/sortutil.cpp: GIL-released argsort and
unique+inverse over int64 arrays, used by the automaton assembler so
background rebuilds stop freezing the insert/publish thread (numpy's
sorts hold the GIL)."""

from __future__ import annotations

import ctypes
from typing import Tuple

import numpy as np

from . import nativelib

_I64P = ctypes.POINTER(ctypes.c_int64)


def _bind(lib) -> None:
    lib.su_argsort_i64.argtypes = [_I64P, ctypes.c_int64, _I64P]
    lib.su_unique_inverse_i64.restype = ctypes.c_int64
    lib.su_unique_inverse_i64.argtypes = [
        _I64P, ctypes.c_int64, _I64P, _I64P, _I64P,
    ]


def load():
    return nativelib.load("sortutil", _bind)


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
