"""ctypes binding for the native host trie (native/hosttrie.cpp).

Drop-in interface twin of `trie_host.HostTrie` — insert/delete_id/
match/match_words/filters/len/contains — with the mutation and match
hot paths in C++ (Python's ~20 us/insert caps churn at ~20k inserts/s;
the native path is ~1-2 us).  Arbitrary Python fid objects intern to
dense int64 handles at this boundary; the word-tuple mirror needed by
rebuild/fold snapshots stays on the Python side (no marshaling on the
snapshot path).

`make_trie()` returns a NativeTrie when the toolchain builds it, else
the pure-Python HostTrie — behavior is identical (equivalence-tested in
tests/test_trie_host.py).
"""

from __future__ import annotations

import ctypes
from typing import Dict, Hashable, Iterator, List, Tuple

import numpy as np

from .. import topic as T
from . import nativelib


def _bind(lib) -> None:
    lib.ht_new.restype = ctypes.c_void_p
    lib.ht_free.argtypes = [ctypes.c_void_p]
    lib.ht_len.restype = ctypes.c_int64
    lib.ht_len.argtypes = [ctypes.c_void_p]
    lib.ht_insert.restype = ctypes.c_int64
    lib.ht_insert.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_int64,
    ]
    lib.ht_seq.restype = ctypes.c_int64
    lib.ht_seq.argtypes = [ctypes.c_void_p]
    _i64p = ctypes.POINTER(ctypes.c_int64)
    lib.ht_insert_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        _i64p, _i64p, _i64p, ctypes.c_int64, _i64p,
    ]
    lib.ht_match_since.restype = ctypes.c_int64
    lib.ht_match_since.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
    ]
    lib.ht_delete.restype = ctypes.c_int32
    lib.ht_delete.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.ht_delete_batch.restype = ctypes.c_int64
    lib.ht_delete_batch.argtypes = [ctypes.c_void_p, _i64p, ctypes.c_int64]
    lib.ht_match.restype = ctypes.c_int64
    lib.ht_match.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
    ]


def load():
    return nativelib.load("hosttrie", _bind)


class NativeTrie:
    """C++-backed trie with the HostTrie interface."""

    def __init__(self) -> None:
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native hosttrie unavailable")
        self._h = self._lib.ht_new()
        # fid object <-> dense int64 handle interning
        self._ids: Dict[Hashable, int] = {}
        self._rev: List[Hashable] = []
        self._free: List[int] = []
        # fid -> words mirror (read by fold/rebuild snapshots)
        self._filters: Dict[Hashable, Tuple[str, ...]] = {}
        self._buf = np.empty(1024, np.int64)
        self._buf_p = self._buf.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
        # bound locals: CDLL attribute access is a per-call dict lookup
        self._ht_insert = self._lib.ht_insert

    def __del__(self) -> None:
        lib, h = getattr(self, "_lib", None), getattr(self, "_h", None)
        if lib is not None and h:
            lib.ht_free(h)
            self._h = None

    def __len__(self) -> int:
        return len(self._filters)

    def __contains__(self, fid: Hashable) -> bool:
        return fid in self._filters

    def filters(self) -> Iterator[Tuple[Hashable, Tuple[str, ...]]]:
        return iter(self._filters.items())

    def _intern(self, fid: Hashable) -> int:
        # non-negative ints pass through as even handles (no table);
        # everything else interns to odd handles — the two spaces can't
        # collide, so mixed int/str/tuple fid sets stay distinct
        if type(fid) is int and fid >= 0:
            return fid << 1
        iid = self._ids.get(fid)
        if iid is None:
            if self._free:
                iid = self._free.pop()
                self._rev[iid] = fid
            else:
                iid = len(self._rev)
                self._rev.append(fid)
            self._ids[fid] = iid
        return (iid << 1) | 1

    def _unintern(self, h: int) -> Hashable:
        return self._rev[h >> 1] if h & 1 else h >> 1

    def insert(self, flt: str, fid: Hashable, ws: Tuple[str, ...] = None) -> int:
        """Insert; returns the monotonically increasing sequence tag
        (0 when unchanged) — `match_since_words` filters on it."""
        if ws is None:
            ws = T.words(flt)
        if self._filters.get(fid) == ws:
            return 0
        seq = self._ht_insert(self._h, flt.encode(), self._intern(fid))
        self._filters[fid] = ws
        return seq

    def insert_batch(self, items) -> List[int]:
        """Insert ``(flt, fid, ws)`` triples in ONE GIL-released call
        (the emqx_router_syncer batching shape); returns per-item
        sequence tags.  Callers pre-filter unchanged entries."""
        n = len(items)
        parts = []
        fids = np.empty(n, np.int64)
        for i, (flt, fid, ws) in enumerate(items):
            parts.append(flt.encode())
            fids[i] = self._intern(fid)
        blob = b"".join(parts)
        lens = np.fromiter((len(p) for p in parts), np.int64, count=n)
        starts = np.empty(n, np.int64)
        if n:
            starts[0] = 0
            np.cumsum(lens[:-1], out=starts[1:])
        seqs = np.empty(n, np.int64)
        p64 = ctypes.POINTER(ctypes.c_int64)
        self._lib.ht_insert_batch(
            self._h, blob,
            starts.ctypes.data_as(p64), lens.ctypes.data_as(p64),
            fids.ctypes.data_as(p64), n, seqs.ctypes.data_as(p64),
        )
        flt_map = self._filters
        for flt, fid, ws in items:
            flt_map[fid] = ws
        return seqs.tolist()

    def delete_id(self, fid: Hashable) -> bool:
        if type(fid) is int and fid >= 0:
            if fid not in self._filters:
                return False
            self._lib.ht_delete(self._h, fid << 1)
            self._filters.pop(fid, None)
            return True
        iid = self._ids.pop(fid, None)
        if iid is None:
            return False
        self._lib.ht_delete(self._h, (iid << 1) | 1)
        self._rev[iid] = None
        self._free.append(iid)
        self._filters.pop(fid, None)
        return True

    def delete_batch(self, fids) -> int:
        """`delete_id` of every fid in ONE GIL-released call (the
        twin of `insert_batch`); returns how many were present."""
        flt_map, ids = self._filters, self._ids
        handles = []
        for fid in fids:
            if flt_map.pop(fid, None) is None:
                continue
            if type(fid) is int and fid >= 0:
                handles.append(fid << 1)
                continue
            iid = ids.pop(fid)
            self._rev[iid] = None
            self._free.append(iid)
            handles.append((iid << 1) | 1)
        if handles:
            arr = np.asarray(handles, np.int64)
            self._lib.ht_delete_batch(
                self._h, arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                len(arr),
            )
        return len(handles)

    def match(self, name: str) -> set:
        raw = name.encode()
        n = self._lib.ht_match(self._h, raw, self._buf_p, len(self._buf))
        if n > len(self._buf):
            self._buf = np.empty(int(n) * 2, np.int64)
            self._buf_p = self._buf.ctypes.data_as(
                ctypes.POINTER(ctypes.c_int64)
            )
            n = self._lib.ht_match(self._h, raw, self._buf_p, len(self._buf))
        rev = self._rev
        return {
            rev[h >> 1] if h & 1 else h >> 1
            for h in self._buf[:n].tolist()
        }

    def match_words(self, name: Tuple[str, ...]) -> set:
        return self.match("/".join(name))

    def last_seq(self) -> int:
        return self._lib.ht_seq(self._h)

    def match_since_words(self, name: Tuple[str, ...], min_seq: int) -> set:
        """Matches restricted to filters inserted with seq >= min_seq
        (the residual-since-watermark view)."""
        raw = "/".join(name).encode()
        n = self._lib.ht_match_since(
            self._h, raw, min_seq, self._buf_p, len(self._buf)
        )
        if n > len(self._buf):
            self._buf = np.empty(int(n) * 2, np.int64)
            self._buf_p = self._buf.ctypes.data_as(
                ctypes.POINTER(ctypes.c_int64)
            )
            n = self._lib.ht_match_since(
                self._h, raw, min_seq, self._buf_p, len(self._buf)
            )
        rev = self._rev
        return {
            rev[h >> 1] if h & 1 else h >> 1
            for h in self._buf[:n].tolist()
        }

    def match_brute(self, name: str) -> set:
        nw = T.words(name)
        return {
            fid for fid, fw in self._filters.items() if T.match_words(nw, fw)
        }


def make_trie():
    """NativeTrie when buildable, else the Python HostTrie."""
    if load() is None:
        from .trie_host import HostTrie

        return HostTrie()
    return NativeTrie()
