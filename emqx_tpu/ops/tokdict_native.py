"""ctypes binding for the native token dictionary (native/tokdict.cpp).

One batch call encodes a whole filter delta — split, word->id map,
'+'/'#' handling — with the GIL RELEASED, so fold/rebuild encode
bursts no longer steal the insert/publish thread's cycles (profiled:
the Python per-word loop halved sustained insert throughput under
churn).  Id semantics are bit-identical to `dictionary.TokenDict`;
new words are mirrored back into the Python dict after each call so
both maps always agree (the Python dict stays the nanosecond-scale
lookup path for per-topic encodes)."""

from __future__ import annotations

import ctypes

import numpy as np

from . import nativelib


def _bind(lib) -> None:
    lib.td_new.restype = ctypes.c_void_p
    lib.td_free.argtypes = [ctypes.c_void_p]
    lib.td_len.restype = ctypes.c_int64
    lib.td_len.argtypes = [ctypes.c_void_p]
    lib.td_add.restype = ctypes.c_int32
    lib.td_add.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
    ]
    lib.td_get.restype = ctypes.c_int32
    lib.td_get.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
    ]
    lib.td_seed.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
    ]
    lib.td_encode_topics_into.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8),
    ]
    lib.td_encode_filters.restype = ctypes.c_int64
    lib.td_encode_filters.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
    ]


def load():
    return nativelib.load("tokdict", _bind)


def _ptr(arr, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


class NativeEncoder:
    """Per-TokenDict native mirror + batch filter encode."""

    def __init__(self, ids: dict) -> None:
        lib = load()
        if lib is None:
            raise RuntimeError("native tokdict unavailable")
        self._lib = lib
        self._h = lib.td_new()
        if ids:
            # seed the mirror with the words the Python dict already
            # holds — one bulk call (insertion order == id order for a
            # Python dict, so position IS the id)
            parts = [w.encode() for w in ids]
            blob = b"".join(parts)
            n = len(parts)
            lens = np.fromiter((len(p) for p in parts), np.int64,
                               count=n)
            starts = np.empty(n, np.int64)
            starts[0] = 0
            np.cumsum(lens[:-1], out=starts[1:])
            lib.td_seed(self._h, blob, _ptr(starts, ctypes.c_int64),
                        _ptr(lens, ctypes.c_int64), n)

    def __del__(self) -> None:
        lib, h = getattr(self, "_lib", None), getattr(self, "_h", None)
        if lib is not None and h:
            lib.td_free(h)
            self._h = None

    def add(self, word: str) -> int:
        w = word.encode()
        return self._lib.td_add(self._h, w, len(w))

    def encode_filters_into(
        self, ids: dict, items, max_levels: int,
        mat: np.ndarray, blen: np.ndarray, ish: np.ndarray,
    ) -> None:
        """Encode ``items`` (``(fid, words)`` pairs) into the given
        array slices (row i = item i) in ONE GIL-released call, then
        mirror the new words back into the Python ``ids`` dict."""
        n = len(items)
        parts = [("/".join(ws)).encode() for _, ws in items]
        blob = b"".join(parts)
        # spans are length-delimited, abutting (never split on
        # content — topic words may legally contain any byte but NUL)
        lens = np.fromiter((len(p) for p in parts), np.int64, count=n)
        starts = np.empty(n, np.int64)
        if n:
            starts[0] = 0
            np.cumsum(lens[:-1], out=starts[1:])
        cap = int(lens.sum()) + 1  # new words <= total chars bound
        new_ids = np.empty(max(cap, 1), np.int32)
        new_spans = np.empty(max(2 * cap, 2), np.int64)
        assert mat.flags["C_CONTIGUOUS"]
        err_i = ctypes.c_int64(-1)
        rc = self._lib.td_encode_filters(
            self._h, blob, _ptr(starts, ctypes.c_int64),
            _ptr(lens, ctypes.c_int64), n,
            max_levels, _ptr(mat, ctypes.c_int32),
            _ptr(blen, ctypes.c_int32),
            _ptr(ish.view(np.uint8), ctypes.c_uint8),
            _ptr(new_ids, ctypes.c_int32),
            _ptr(new_spans, ctypes.c_int64), cap,
            ctypes.byref(err_i),
        )
        # mirror new words BEFORE any failure handling: the native map
        # already holds words inserted ahead of a too-deep filter, and
        # skipping the mirror would desynchronize the two dictionaries
        # permanently (topic encodes would see UNKNOWN_TOK for words
        # arena rows reference)
        for k in range(int(rc)):
            o, ln = new_spans[2 * k], new_spans[2 * k + 1]
            ids[blob[o:o + ln].decode()] = int(new_ids[k])
        if err_i.value >= 0:
            fid, ws = items[int(err_i.value)]
            raise ValueError(
                f"filter deeper than max_levels={max_levels}: {ws}"
            )

    def encode_topics_into(
        self, topics, levels: int,
        mat: np.ndarray, out_lens: np.ndarray, dollar: np.ndarray,
    ) -> None:
        """Encode topic STRINGS (the publish-path miss batch) into the
        given row slices in one GIL-released call: get-only token
        lookups, truncation at `levels`, '$'-flag."""
        n = len(topics)
        parts = [t.encode() for t in topics]
        blob = b"".join(parts)
        lens = np.fromiter((len(p) for p in parts), np.int64, count=n)
        starts = np.empty(n, np.int64)
        if n:
            starts[0] = 0
            np.cumsum(lens[:-1], out=starts[1:])
        assert mat.flags["C_CONTIGUOUS"]
        self._lib.td_encode_topics_into(
            self._h, blob, _ptr(starts, ctypes.c_int64),
            _ptr(lens, ctypes.c_int64), n, levels,
            _ptr(mat, ctypes.c_int32), _ptr(out_lens, ctypes.c_int32),
            _ptr(dollar.view(np.uint8), ctypes.c_uint8),
        )
