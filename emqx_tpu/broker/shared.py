"""Shared-subscription ($share/Group/Topic) group dispatch.

Re-creates `emqx_shared_sub` (/root/reference/apps/emqx/src/
emqx_shared_sub.erl): group membership per (group, real-filter), the
seven pick strategies (:79-86), per-message pick (`dispatch/4`
:144-166) and redispatch-on-failure.  Single-node for now: the mria
membership table collapses to an in-process registry; `local` strategy
degenerates to `random` until the cluster layer adds node placement.

A window's picks run as one operation (`pick_window`): each
(group, filter) pair is a numbered *key* whose members are kept as
arrays of client rows and opts-table slots, and the router hands a
window's shared part over as two int64 columns (message, key).  The
per-message `pick` stays as the redispatch path and its referee.
"""

from __future__ import annotations

import random
import zlib
from typing import Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from ..message import Message

STRATEGIES = (
    "random",
    "round_robin",
    "round_robin_per_group",
    "sticky",
    "local",
    "hash_clientid",
    "hash_topic",
)

_EMPTY_I64 = np.empty(0, dtype=np.int64)


def _hash(s: str) -> int:
    return zlib.crc32(s.encode("utf-8"))


class _Key:
    """One (group, filter) pair: its members in join order, each with
    the client row and opts-table slot its deliveries ride."""

    __slots__ = ("kid", "group", "flt", "members", "_ids", "_arr")

    def __init__(self, kid: int, group: str, flt: str) -> None:
        self.kid = kid
        self.group = group
        self.flt = flt
        self.members: Dict[str, Tuple[int, int]] = {}
        self._ids: Optional[List[str]] = None
        self._arr: Optional[Tuple[np.ndarray, np.ndarray]] = None

    def changed(self) -> None:
        self._ids = None
        self._arr = None

    def ids(self) -> List[str]:
        ids = self._ids
        if ids is None:
            ids = self._ids = list(self.members)
        return ids

    def arrays(self) -> Tuple[np.ndarray, np.ndarray]:
        """(client rows, opts slots), in join order."""
        a = self._arr
        if a is None:
            rs = list(self.members.values())
            a = self._arr = (
                np.fromiter((r for r, _ in rs), np.int64, len(rs)),
                np.fromiter((s for _, s in rs), np.int64, len(rs)),
            )
        return a


class SharedSubManager:
    def __init__(self, strategy: str = "random", seed: Optional[int] = None):
        self._rng = random.Random(seed)
        # the window operation's draws (random / local)
        self._np_rng = np.random.default_rng(seed)
        self._keys: Dict[Tuple[str, str], _Key] = {}
        self._by_kid: List[Optional[_Key]] = []
        self._kid_free: List[int] = []
        # filter -> its keys' ids in creation order: what a window's
        # expansion reads for every matched filter (`Router.expand_window`)
        self.keys_by_filter: Dict[str, np.ndarray] = {}
        self.strategy = strategy
        # shared rows picked, by the window operation, by the scalar
        # redispatch path, and rows that found no eligible member
        self.picks = 0
        self.picks_vector = 0
        self.picks_fallback = 0
        self.picks_no_member = 0

    @property
    def strategy(self) -> str:
        return self._strategy

    @strategy.setter
    def strategy(self, name: str) -> None:
        """Switch strategy; the round-robin counters and sticky picks
        start over."""
        if name not in STRATEGIES:
            raise ValueError(f"unknown shared-sub strategy {name!r}")
        self._strategy = name
        self._rr: Dict[Tuple[str, str], int] = {}
        self._rr_group: Dict[str, int] = {}
        self._sticky: Dict[Tuple[str, str], str] = {}

    # ------------------------------------------------------ membership

    def join(self, group: str, flt: str, clientid: str,
             row: int = -1, slot: int = -1) -> bool:
        """Add a member (``row`` / ``slot``: the client row and the
        opts-table slot its deliveries ride); True if the (group,
        filter) pair is new (i.e. the underlying route must be
        added)."""
        key = self._keys.get((group, flt))
        if key is None:
            if self._kid_free:
                kid = self._kid_free.pop()
            else:
                kid = len(self._by_kid)
                self._by_kid.append(None)
            key = self._keys[(group, flt)] = self._by_kid[kid] = _Key(
                kid, group, flt
            )
            old = self.keys_by_filter.get(flt, _EMPTY_I64)
            self.keys_by_filter[flt] = np.append(old, np.int64(kid))
        fresh = not key.members
        key.members[clientid] = (row, slot)
        key.changed()
        return fresh

    def leave(self, group: str, flt: str, clientid: str) -> bool:
        """Remove a member; True if the pair became empty (route
        delete needed)."""
        key = self._keys.get((group, flt))
        if key is None:
            return False
        if key.members.pop(clientid, None) is not None:
            key.changed()
        if self._sticky.get((group, flt)) == clientid:
            del self._sticky[(group, flt)]
        if key.members:
            return False
        del self._keys[(group, flt)]
        self._by_kid[key.kid] = None
        self._kid_free.append(key.kid)
        self._rr.pop((group, flt), None)
        kids = self.keys_by_filter[flt]
        kids = kids[kids != key.kid]
        if len(kids):
            self.keys_by_filter[flt] = kids
        else:
            del self.keys_by_filter[flt]
        return True

    def leave_all(self, clientid: str) -> List[Tuple[str, str]]:
        """Drop a client from every group (channel death); returns the
        (group, filter) pairs that became empty."""
        emptied = []
        for (group, flt), key in list(self._keys.items()):
            if clientid in key.members:
                if self.leave(group, flt, clientid):
                    emptied.append((group, flt))
        return emptied

    def members(self, group: str, flt: str) -> List[str]:
        key = self._keys.get((group, flt))
        return [] if key is None else list(key.ids())

    def key_of(self, kid: int) -> Tuple[str, str]:
        """The (group, filter) pair a key id numbers."""
        key = self._by_kid[kid]
        return key.group, key.flt

    def stats(self) -> Dict[str, int]:
        return {
            "picks": self.picks,
            "picks_vector": self.picks_vector,
            "picks_fallback": self.picks_fallback,
            "picks_no_member": self.picks_no_member,
        }

    # ---------------------------------------------------------- pick

    def pick(
        self,
        group: str,
        flt: str,
        msg: Message,
        exclude: Optional[Set[str]] = None,
    ) -> Optional[str]:
        """Choose the receiving member for one message; ``exclude``
        carries previously-failed members during redispatch
        (emqx_shared_sub:redispatch)."""
        key = (group, flt)
        k = self._keys.get(key)
        if k is None:
            return None
        members = k.ids()
        if exclude:
            members = [m for m in members if m not in exclude]
            if not members:
                return None
        s = self._strategy
        if s == "sticky":
            cur = self._sticky.get(key)
            if cur is not None and cur in members:
                return cur
            picked = self._rng.choice(members)
            self._sticky[key] = picked
            return picked
        if s == "round_robin":
            i = self._rr.get(key, 0)
            self._rr[key] = i + 1
            return members[i % len(members)]
        if s == "round_robin_per_group":
            i = self._rr_group.get(group, 0)
            self._rr_group[group] = i + 1
            return members[i % len(members)]
        if s == "hash_clientid":
            return members[_hash(msg.from_client) % len(members)]
        if s == "hash_topic":
            return members[_hash(msg.topic) % len(members)]
        # random | local (no node placement yet)
        return self._rng.choice(members)

    def pick_window(
        self,
        s_msg: np.ndarray,
        s_key: np.ndarray,
        msgs,
        eligible: Optional[Callable[[str], bool]] = None,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Pick a member for every shared row of a window at once.

        ``s_msg`` / ``s_key`` are aligned: row ``r`` owes message
        ``msgs[s_msg[r]]`` to one member of key ``s_key[r]``, rows in
        the order the scalar path would pick them.  Returns ``(rows,
        slots, served)``: each row's member client row and opts slot,
        and whether this operation served it.  A key with a member
        ``eligible`` refuses is not served (its rows read -1): it
        takes the scalar `pick`'s redispatch, row by row (under
        `round_robin_per_group` its whole group does, since the
        group's one counter runs over all its keys).  Where every
        member of a key is eligible the picks are `pick`'s, row for
        row, and the counters end where `pick`'s would, for every
        strategy but `random` / `local`, which draw from their own
        generator."""
        n = len(s_key)
        rows = np.full(n, -1, dtype=np.int64)
        slots = np.full(n, -1, dtype=np.int64)
        served = np.zeros(n, dtype=bool)
        if not n:
            return rows, slots, served
        uk, first, inv, cnt = np.unique(
            s_key, return_index=True, return_inverse=True,
            return_counts=True,
        )
        by_kid = self._by_kid
        keys = [by_kid[k] for k in uk.tolist()]
        ok = np.ones(len(keys), dtype=bool)
        if eligible is not None:
            # read once a window for each key present: O(members)
            for j, key in enumerate(keys):
                ok[j] = all(map(eligible, key.ids()))
        s = self._strategy
        if s == "round_robin_per_group" and not ok.all():
            bad = {keys[j].group for j in np.flatnonzero(~ok).tolist()}
            ok &= np.fromiter((k.group not in bad for k in keys), bool,
                              len(keys))
        vi = np.flatnonzero(ok[inv])
        self.picks += n
        self.picks_vector += len(vi)
        self.picks_fallback += n - len(vi)
        if not len(vi):
            return rows, slots, served
        kinv = inv[vi]
        nmem = np.fromiter((len(k.members) for k in keys), np.int64,
                           len(keys))
        arrs = [k.arrays() for k in keys]
        flat_rows = np.concatenate([a[0] for a in arrs])
        flat_slots = np.concatenate([a[1] for a in arrs])
        off = np.cumsum(nmem) - nmem
        if s == "round_robin":
            base = np.fromiter(
                (self._rr.get((k.group, k.flt), 0) for k in keys),
                np.int64, len(keys),
            )
            j = (base[kinv] + _rank(kinv, len(keys))) % nmem[kinv]
            for jk in np.flatnonzero(ok).tolist():
                k = keys[jk]
                self._rr[(k.group, k.flt)] = int(base[jk] + cnt[jk])
        elif s == "round_robin_per_group":
            names: Dict[str, int] = {}
            gid = np.fromiter(
                (names.setdefault(k.group, len(names)) for k in keys),
                np.int64, len(keys),
            )
            groups = list(names)
            base = np.fromiter(
                (self._rr_group.get(g, 0) for g in groups), np.int64,
                len(groups),
            )
            grow = gid[kinv]
            j = (base[grow] + _rank(grow, len(groups))) % nmem[kinv]
            gcnt = np.bincount(grow, minlength=len(groups))
            for g in np.flatnonzero(gcnt).tolist():
                self._rr_group[groups[g]] = int(base[g] + gcnt[g])
        elif s == "sticky":
            at = np.zeros(len(keys), dtype=np.int64)
            # keys in the order their first row comes, as `pick`
            # would meet them: a fresh key draws from the same stream
            for jk in np.argsort(first, kind="stable").tolist():
                if not ok[jk]:
                    continue
                k = keys[jk]
                ids = k.ids()
                cur = self._sticky.get((k.group, k.flt))
                if cur is None or cur not in k.members:
                    cur = self._sticky[(k.group, k.flt)] = (
                        self._rng.choice(ids)
                    )
                at[jk] = ids.index(cur)
            j = at[kinv]
        elif s in ("hash_topic", "hash_clientid"):
            vm = s_msg[vi]
            um, minv = np.unique(vm, return_inverse=True)
            if s == "hash_topic":
                h = (_hash(msgs[i].topic) for i in um.tolist())
            else:
                h = (_hash(msgs[i].from_client) for i in um.tolist())
            hv = np.fromiter(h, np.int64, len(um))
            j = hv[minv] % nmem[kinv]
        else:  # random | local (no node placement yet)
            j = self._np_rng.integers(0, nmem[kinv])
        at = off[kinv] + j
        rows[vi] = flat_rows[at]
        slots[vi] = flat_slots[at]
        served[vi] = True
        return rows, slots, served


def _rank(grp: np.ndarray, n_groups: int) -> np.ndarray:
    """Each row's rank among the rows of its group, in row order."""
    order = np.argsort(grp, kind="stable")
    cnt = np.bincount(grp, minlength=n_groups)
    starts = np.cumsum(cnt) - cnt
    rank = np.empty(len(grp), dtype=np.int64)
    rank[order] = np.arange(len(grp)) - starts[grp[order]]
    return rank
