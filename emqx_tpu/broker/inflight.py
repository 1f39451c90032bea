"""Bounded in-flight window keyed by packet id.

`emqx_inflight` (/root/reference/apps/emqx/src/emqx_inflight.erl) is a
gb_trees window; insertion order is what retransmit-on-reconnect needs,
so a plain insertion-ordered dict (Python guarantees order) suffices.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterator, List, Optional, Tuple


class Inflight:
    def __init__(self, max_size: int = 32) -> None:
        self.max_size = max_size
        self._d: Dict[int, Any] = {}

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key: int) -> bool:
        return key in self._d

    def is_full(self) -> bool:
        return self.max_size > 0 and len(self._d) >= self.max_size

    def room_for(self, n: int) -> bool:
        """Can the window absorb ``n`` more entries right now?  The
        native run paths use this as their all-or-nothing gate: a run
        that would overflow falls back to the per-delivery loop, which
        queues the overflow one delivery at a time."""
        return self.max_size <= 0 or len(self._d) + n <= self.max_size

    def insert(self, key: int, value: Any) -> None:
        if key in self._d:
            raise KeyError(f"packet id {key} already in flight")
        self._d[key] = value

    def insert_run(self, keys, values) -> None:
        """Bulk insert for one delivery run: the same duplicate check
        as `insert`, but the clean case (no key already in flight) is
        ONE C-speed disjointness probe plus one dict.update — the
        caller builds all values with ONE clock read, so a
        64-delivery run costs two C calls instead of 64 insert calls
        (and 64 ``time.time()``s)."""
        d = self._d
        kl = keys if isinstance(keys, list) else list(keys)
        # batch-internal duplicates must raise as loudly as in-flight
        # ones (two PUBLISHes sharing one pid would ack as one)
        if len(set(kl)) == len(kl) and d.keys().isdisjoint(kl):
            d.update(zip(kl, values))
            return
        # a colliding run keeps insert-by-insert semantics: entries
        # before the duplicate land, the duplicate raises (a batch-
        # internal dup's first occurrence is in `d` by the time the
        # second is checked)
        for key, value in zip(kl, values):
            if key in d:
                raise KeyError(f"packet id {key} already in flight")
            d[key] = value

    def insert_seq(self, lo: int, values) -> None:
        """Insert ``values`` under consecutive keys ``lo..lo+n-1``
        the caller has already proven free (`free_range`) — one
        dict.update, no per-key Python."""
        self._d.update(zip(range(lo, lo + len(values)), values))

    def free_range(self, lo: int, hi: int) -> bool:
        """True when no key lies in [lo, hi] — one C-speed scan, the
        block allocator's consecutive-ids fast path."""
        return self._d.keys().isdisjoint(range(lo, hi + 1))

    def update(self, key: int, value: Any) -> None:
        if key not in self._d:
            raise KeyError(key)
        self._d[key] = value  # preserves original insertion order

    def delete(self, key: int) -> Optional[Any]:
        return self._d.pop(key, None)

    def delete_run(self, keys, qos: int) -> List[int]:
        """Delete each of ``keys`` whose entry is of this ``qos`` and
        return those, in order: what `get` + `delete` key by key give
        (a key unknown, repeated or of another qos is passed over),
        with the dict's methods bound once for the run."""
        d = self._d
        get = d.get
        gone: List[int] = []
        for key in keys:
            entry = get(key)
            if entry is not None and entry.qos == qos:
                del d[key]
                gone.append(key)
        return gone

    def get(self, key: int) -> Optional[Any]:
        return self._d.get(key)

    def items(self) -> List[Tuple[int, Any]]:
        return list(self._d.items())

    def values(self) -> Iterator[Any]:
        return iter(self._d.values())

    def clear(self) -> None:
        self._d.clear()
