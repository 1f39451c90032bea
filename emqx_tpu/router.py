"""Router: subscription registry over the TPU match engine.

The layer the reference splits across `emqx_broker` subscriber tables +
`emqx_router` route table (/root/reference/apps/emqx/src/
emqx_broker.erl:119-132 ETS tables; emqx_router.erl:476-525 v2 route
schema).  Here one object owns both because a single host is one
"node": the `MatchEngine` indexes each distinct real filter once
(fid = the filter string), and per-filter subscriber maps carry
(clientid -> SubOpts) fan-out, CSR-expanded at dispatch time.

Fan-out expansion is vectorized: client ids intern to integer rows and
each SubOpts to a table slot, and each filter keeps an incrementally
maintained CSR column of (client_row, opts_row) pairs.  A window's
matched fid sets expand to flat ``(msg_idx, client_row, opts_row)``
arrays in one pass (`expand_window`) instead of per-filter dict churn —
rule fids and shared-group fids split off as distinct columns feeding
the rule sink and the shared-pick path.

Shared subscriptions route through the same engine entry for the real
filter; group membership and a window's picks live in
`SharedSubManager`.

Route changes come in batches, the `emqx_router_syncer` shape with the
event loop's turn as the batch (emqx_router_syncer.erl:58, :115-121:
callers push their route ops, then wait for the batch before the
SUBACK).  Inside a scope (`begin_routes` / `end_routes`: the turn's
reads) a subscribe or unsubscribe stages its engine route op and
changes its own state at once; when the scope closes the staged ops go
to the engine as one batch in their net order
(`MatchEngine.apply_routes`: one lock acquisition, one native call each
way), so the loop waits for the matcher's lock once a turn, not once a
filter.  What waits for the batch (`after_routes`: a SUBACK or UNSUBACK)
runs once it is applied, in order.  Outside a scope a route op applies
at once, as every caller off the read path expects.

Why the turn waits for the lock rather than carrying its batch to a
later turn where a match holds it: the holder is a window's `overlay`,
Python on an executor thread, and a loop that goes on running while it
waits takes the GIL from it.  On the chip (TPU v5e, the
`fleet-1m-consoles.churn-paced` cell) a carrying loop measured the median
delay 29-54 % above a loop that waits once a filter, and the one wait a
turn 19 % below it (`PERF.md`, section 5).
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from . import topic as T
from .broker.session import SubOpts
from .broker.shared import SharedSubManager
from .engine import MatchEngine, _validate_filter
from .tp import tp

_EMPTY_I64 = np.empty(0, dtype=np.int64)

# initial capacity of the parallel SubOpts attribute columns; grown by
# doubling so the device decide path sees few distinct table shapes
_OPTS_CAP0 = 64


class _CsrBucket:
    """One filter's subscriber column: parallel (client_row, opts_row)
    lists with O(1) append and swap-remove, plus lazily rebuilt numpy
    views so a window expansion is array concatenation, not dict
    iteration."""

    __slots__ = ("rows", "opts_rows", "pos", "_arr")

    def __init__(self) -> None:
        self.rows: List[int] = []
        self.opts_rows: List[int] = []
        self.pos: Dict[int, int] = {}  # client_row -> index
        self._arr: Optional[Tuple[np.ndarray, np.ndarray]] = None

    def add(self, client_row: int, opts_row: int) -> None:
        self.pos[client_row] = len(self.rows)
        self.rows.append(client_row)
        self.opts_rows.append(opts_row)
        self._arr = None

    def opts_row_of(self, client_row: int) -> Optional[int]:
        i = self.pos.get(client_row)
        return None if i is None else self.opts_rows[i]

    def remove(self, client_row: int) -> Optional[int]:
        """Swap-remove; returns the freed opts row (None if absent)."""
        i = self.pos.pop(client_row, None)
        if i is None:
            return None
        freed = self.opts_rows[i]
        last_row = self.rows[-1]
        last_opts = self.opts_rows[-1]
        self.rows.pop()
        self.opts_rows.pop()
        if i < len(self.rows):
            self.rows[i] = last_row
            self.opts_rows[i] = last_opts
            self.pos[last_row] = i
        self._arr = None
        return freed

    def arrays(self) -> Tuple[np.ndarray, np.ndarray]:
        a = self._arr
        if a is None:
            a = self._arr = (
                np.asarray(self.rows, dtype=np.int64),
                np.asarray(self.opts_rows, dtype=np.int64),
            )
        return a


class Router:
    def __init__(
        self,
        engine: Optional[MatchEngine] = None,
        shared: Optional[SharedSubManager] = None,
    ) -> None:
        # `engine or MatchEngine()` would DISCARD a configured empty
        # engine: MatchEngine defines __len__, so a fresh one is falsy
        self.engine = engine if engine is not None else MatchEngine()
        self.shared = shared if shared is not None else SharedSubManager()
        # cluster hooks: fired when a real filter gains its first local
        # subscriber / loses its last one (the sync_route add/delete
        # points, emqx_broker.erl:691-721) — ClusterNode broadcasts them
        self.on_route_added = None
        self.on_route_removed = None
        # real filter -> {clientid -> SubOpts} (direct, non-shared).
        # Stays the source of truth (mgmt dumps, counts, the legacy
        # walk the CSR property test checks against).
        self._subs: Dict[str, Dict[str, SubOpts]] = {}
        # real filter -> {(group, clientid) -> SubOpts} (shared)
        self._shared_opts: Dict[str, Dict[Tuple[str, str], SubOpts]] = {}
        # (real, group, clientid) -> opts table slot: shared-sub opts
        # intern into the SAME table as direct ones, so a window's
        # shared picks ride the decision columns like any delivery
        self._shared_slot: Dict[Tuple[str, str, str], int] = {}
        # clientid -> set of full filter strings (incl. $share prefix)
        self._by_client: Dict[str, Set[str]] = {}
        # --- interning tables + CSR fan-out index -------------------
        self._client_rows: Dict[str, int] = {}   # clientid -> row
        self._row_clients: List[str] = []        # row -> clientid
        self._row_free: List[int] = []
        self._opts_table: List[Optional[SubOpts]] = []
        self._opts_free: List[int] = []
        self._csr: Dict[str, _CsrBucket] = {}
        # --- parallel SubOpts attribute columns ---------------------
        # numpy twins of `_opts_table`, maintained on every alloc/free/
        # refresh, so a window's per-delivery decisions (effective QoS,
        # no-local drop, RAP retain, subid presence) come from ONE
        # vectorized gather instead of a Python attribute read per
        # delivery.  `opts_rev` bumps on every write so the engine's
        # device decide path can cache its uploaded copies.
        self._oa_qos = np.zeros(_OPTS_CAP0, dtype=np.int8)
        self._oa_nl = np.zeros(_OPTS_CAP0, dtype=bool)
        self._oa_rap = np.zeros(_OPTS_CAP0, dtype=bool)
        self._oa_subid = np.zeros(_OPTS_CAP0, dtype=bool)
        self.opts_rev = 0
        # (client, filter) subscription pairs, kept by subscribe and
        # unsubscribe: the 'subscriptions.count' stat
        self._n_subs = 0
        # --- route batches -------------------------------------------
        # the filters whose route the engine holds or is staged to
        # hold: a delete is staged only for one of them
        self._routed: Set[str] = set()
        self._scope = 0  # open `begin_routes` scopes
        # staged (is_add, filter) ops and what waits for them: (fn,
        # perf_counter at the packet, filters), both in order
        self._ops: List[Tuple[bool, str]] = []
        self._acks: List[Tuple[Callable[[], None], float, int]] = []

    # ---------------------------------------------------- interning

    def _intern(self, clientid: str) -> int:
        row = self._client_rows.get(clientid)
        if row is None:
            if self._row_free:
                row = self._row_free.pop()
                self._row_clients[row] = clientid
            else:
                row = len(self._row_clients)
                self._row_clients.append(clientid)
            self._client_rows[clientid] = row
        return row

    def _release_client(self, clientid: str) -> None:
        row = self._client_rows.pop(clientid, None)
        if row is not None:
            self._row_clients[row] = ""
            self._row_free.append(row)

    def _alloc_opts(self, opts: SubOpts) -> int:
        if self._opts_free:
            slot = self._opts_free.pop()
            self._opts_table[slot] = opts
        else:
            slot = len(self._opts_table)
            self._opts_table.append(opts)
            if slot >= len(self._oa_qos):
                # double the attribute columns: few distinct shapes
                # keep the device decide path's recompiles bounded
                cap = 2 * len(self._oa_qos)
                for name in ("_oa_qos", "_oa_nl", "_oa_rap",
                             "_oa_subid"):
                    old = getattr(self, name)
                    new = np.zeros(cap, dtype=old.dtype)
                    new[: len(old)] = old
                    setattr(self, name, new)
        self._set_opts_attrs(slot, opts)
        return slot

    def _set_opts_attrs(self, slot: int, opts: SubOpts) -> None:
        """Mirror one SubOpts into the attribute columns (alloc AND
        options-refresh paths — the columns must never go stale, they
        are what the window decisions read)."""
        self._oa_qos[slot] = opts.qos
        self._oa_nl[slot] = opts.no_local
        self._oa_rap[slot] = opts.retain_as_published
        self._oa_subid[slot] = opts.subid is not None
        self.opts_rev += 1

    def _free_opts(self, slot: int) -> None:
        self._opts_table[slot] = None
        self._oa_qos[slot] = 0
        self._oa_nl[slot] = False
        self._oa_rap[slot] = False
        self._oa_subid[slot] = False
        self.opts_rev += 1
        self._opts_free.append(slot)

    def opts_columns(self) -> Tuple[np.ndarray, np.ndarray,
                                    np.ndarray, np.ndarray]:
        """(qos, no_local, retain_as_published, has_subid) attribute
        columns, indexed by opts row — the vectorized read side of the
        table `_set_opts_attrs` maintains."""
        return self._oa_qos, self._oa_nl, self._oa_rap, self._oa_subid

    def client_of_row(self, row: int) -> str:
        return self._row_clients[row]

    def row_of_client(self, clientid: str) -> Optional[int]:
        return self._client_rows.get(clientid)

    def opts_at(self, slot: int) -> SubOpts:
        return self._opts_table[slot]  # type: ignore[return-value]

    # ------------------------------------------------------- mutation

    def subscribe(self, clientid: str, flt: str, opts: SubOpts) -> None:
        """Register `clientid`'s subscription to `flt` (which may be a
        `$share/...` filter).  Mirrors emqx_broker:subscribe/3 +
        route-add (emqx_broker.erl:151-190, 691-721)."""
        shared = T.parse_share(flt)
        # (a filter the engine would refuse is refused before any state
        # changes, as the engine's own insert does)
        _validate_filter(shared.topic if shared is not None else flt)
        if shared is not None:
            real = shared.topic
            opts.share_group = shared.group
            row = self._intern(clientid)  # picks resolve to rows
            members = self._shared_opts.setdefault(real, {})
            self._n_subs += (shared.group, clientid) not in members
            members[(shared.group, clientid)] = opts
            skey = (real, shared.group, clientid)
            sslot = self._shared_slot.get(skey)
            if sslot is None:
                sslot = self._shared_slot[skey] = self._alloc_opts(opts)
            else:  # options refresh of an existing shared subscription
                self._opts_table[sslot] = opts
                self._set_opts_attrs(sslot, opts)
            need_route = self.shared.join(
                shared.group, real, clientid, row, sslot
            )
            if need_route and real not in self._subs:
                self._route(True, real)
        else:
            real = flt
            subs = self._subs.get(real)
            if subs is None:
                subs = self._subs[real] = {}
                if real not in self._shared_opts or not self._shared_opts[real]:
                    self._route(True, real)
            self._n_subs += clientid not in subs
            subs[clientid] = opts
            row = self._intern(clientid)
            bucket = self._csr.get(real)
            if bucket is None:
                bucket = self._csr[real] = _CsrBucket()
            slot = bucket.opts_row_of(row)
            if slot is None:
                bucket.add(row, self._alloc_opts(opts))
            else:  # options refresh of an existing subscription
                self._opts_table[slot] = opts
                self._set_opts_attrs(slot, opts)
        self._by_client.setdefault(clientid, set()).add(flt)

    def unsubscribe(self, clientid: str, flt: str) -> bool:
        shared = T.parse_share(flt)
        if shared is not None:
            real = shared.topic
            emptied = self.shared.leave(shared.group, real, clientid)
            opts_map = self._shared_opts.get(real)
            if opts_map is not None:
                if opts_map.pop((shared.group, clientid), None) is not None:
                    self._n_subs -= 1
                if not opts_map:
                    del self._shared_opts[real]
            sslot = self._shared_slot.pop(
                (real, shared.group, clientid), None
            )
            if sslot is not None:
                self._free_opts(sslot)
            removed = True
        else:
            real = flt
            subs = self._subs.get(real)
            if subs is None or clientid not in subs:
                removed = False
            else:
                del subs[clientid]
                self._n_subs -= 1
                if not subs:
                    del self._subs[real]
                bucket = self._csr.get(real)
                row = self._client_rows.get(clientid)
                if bucket is not None and row is not None:
                    freed = bucket.remove(row)
                    if freed is not None:
                        self._free_opts(freed)
                    if not bucket.rows:
                        del self._csr[real]
                removed = True
        self._maybe_drop_route(real)
        filters = self._by_client.get(clientid)
        if filters is not None:
            filters.discard(flt)
            if not filters:
                del self._by_client[clientid]
                self._release_client(clientid)
        return removed

    def _maybe_drop_route(self, real: str) -> None:
        if real not in self._subs and real not in self._shared_opts:
            self._route(False, real)

    # ------------------------------------------------ route batches

    def _route(self, add: bool, real: str) -> None:
        """Stage one route op, and apply it at once outside a scope.
        An op is staged only where it changes `_routed` (a second
        group's join, or a delete of a filter never routed, moves
        nothing), so a filter's first op in a batch says whether the
        engine held it before."""
        if add == (real in self._routed):
            return
        if add:
            self._routed.add(real)
        else:
            self._routed.discard(real)
        self._ops.append((add, real))
        if not self._scope:
            self._flush()

    def begin_routes(self) -> None:
        """Open a route scope (they nest): its ops wait for the end of
        the outermost one."""
        self._scope += 1

    def end_routes(self) -> None:
        """Close a scope; the outermost applies what is staged."""
        self._scope -= 1
        if not self._scope and (self._ops or self._acks):
            self._flush()

    def after_routes(self, fn: Callable[[], None], t0: float,
                     filters: int) -> None:
        """Run ``fn`` (a SUBACK or UNSUBACK and what follows it) once
        every route op staged so far is applied: at once where none
        waits, else in order after the batch.  ``t0`` (perf_counter)
        is when its packet came, ``filters`` how many it names: the
        engine counts the wait (`MatchEngine.note_route_acks`)."""
        if self._ops or self._acks:
            self._acks.append((fn, t0, filters))
            return
        fn()
        self.engine.note_route_acks(time.perf_counter() - t0, filters)

    def _flush(self) -> None:
        ops, self._ops = self._ops, []
        if ops:
            # a filter's first op says what the engine held before the
            # batch (a delete: the route), its last what it holds after;
            # where the two agree (a delete then an insert) nothing moves
            first: Dict[str, bool] = {}
            last: Dict[str, bool] = {}
            for add, real in ops:
                first.setdefault(real, add)
                last[real] = add
            pairs = [(r, r) for r, add in last.items()
                     if add and first[r]]
            dels = [r for r, add in last.items()
                    if not add and not first[r]]
            if pairs or dels:
                self.engine.apply_routes(pairs, dels)
            tp("routes_applied", ops=len(ops))
            added, removed = self.on_route_added, self.on_route_removed
            for add, real in ops:
                hook = added if add else removed
                if hook is not None:
                    hook(real)
        acks, self._acks = self._acks, []
        note = self.engine.note_route_acks
        for fn, t0, filters in acks:
            fn()
            note(time.perf_counter() - t0, filters)

    def cleanup_client(self, clientid: str) -> None:
        """Drop every subscription of a dead client (the
        `subscriber_down` path, emqx_broker.erl:448-462)."""
        for flt in list(self._by_client.get(clientid, ())):
            self.unsubscribe(clientid, flt)

    def subscriptions_of(self, clientid: str) -> Set[str]:
        return set(self._by_client.get(clientid, ()))

    def topics(self) -> List[str]:
        """All indexed real filters (the route-table dump used by the
        mgmt API's /topics)."""
        return list(self._subs.keys() | self._shared_opts.keys())

    def subscription_count(self) -> int:
        """Total (client, filter) subscription pairs — the
        'subscriptions.count' stat (rule fids excluded)."""
        return self._n_subs

    # --------------------------------------------------------- match

    def match_batch(
        self, topics: Sequence[str], congested: bool = False
    ) -> List[Set[str]]:
        """Real filters matching each topic (batched on device).  The
        ``congested`` hint flips the engine's auto policy into
        throughput mode (compare host CPU, not wall time)."""
        return self.engine.match_batch(topics, congested=congested)

    def subscribers(
        self, real: str
    ) -> List[Tuple[str, SubOpts]]:
        """Direct (non-shared) subscribers of a matched filter (the
        legacy per-filter walk; `expand_window` is the batched path)."""
        return list(self._subs.get(real, {}).items())

    def shared_opts(
        self, real: str, group: str, clientid: str
    ) -> Optional[SubOpts]:
        m = self._shared_opts.get(real)
        return None if m is None else m.get((group, clientid))

    def shared_slot_of(
        self, real: str, group: str, clientid: str
    ) -> Optional[int]:
        """Opts-table slot of one shared subscription (the row a
        window's shared pick contributes to the decision columns)."""
        return self._shared_slot.get((real, group, clientid))

    def opts_slot_of(self, clientid: str, flt: str) -> Optional[int]:
        """Opts-table slot of one client's subscription to ``flt``
        (``$share`` filters included) — how the durable-replay window
        builder resolves each (client, filter) backlog entry to the
        decision-column row its live deliveries already ride."""
        share = T.parse_share(flt)
        if share is not None:
            return self._shared_slot.get(
                (share.topic, share.group, clientid)
            )
        bucket = self._csr.get(flt)
        if bucket is None:
            return None
        row = self._client_rows.get(clientid)
        if row is None:
            return None
        return bucket.opts_row_of(row)

    # ----------------------------------------------- window expansion

    def expand_window(
        self, matched: Sequence[Set]
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
               List[Tuple[int, List[str]]], np.ndarray, np.ndarray]:
        """CSR-expand one window's matched fid sets to flat delivery
        columns.

        Returns ``(msg_idx, client_rows, opts_rows, rules, s_msg,
        s_key)``: the three aligned int64 arrays cover every DIRECT
        (non-shared) delivery in the window — one vectorized
        concatenation over the per-filter CSR columns — while rule
        fids come back grouped per message as ``(msg_idx, [rule_id,
        ...])`` (RAW: unsorted, a multi-filter rule may repeat; the
        rule engine's flatten cache dedups vectorized) and the shared
        part as two aligned int64 columns, a row per (message, matched
        filter, group): the message and the (group, filter) key id
        (`SharedSubManager.pick_window`), concatenated from each
        filter's key ids as the direct part is from its bucket.  Fids
        with no local state (e.g. raw engine fids preloaded by
        benchmarks) cost two dict misses each."""
        seg_rows: List[np.ndarray] = []
        seg_opts: List[np.ndarray] = []
        seg_msg: List[int] = []
        seg_len: List[int] = []
        rules: List[Tuple[int, List[str]]] = []
        sh_keys: List[np.ndarray] = []
        sh_msg: List[int] = []
        sh_len: List[int] = []
        csr = self._csr
        keys_of = self.shared.keys_by_filter
        rule_i = -1
        rule_ids: List[str] = []
        for i, fids in enumerate(matched):
            for fid in fids:
                if type(fid) is tuple:  # ("rule", rule_id, i)
                    if rule_i != i:
                        rule_i = i
                        rule_ids = []
                        rules.append((i, rule_ids))
                    rule_ids.append(fid[1])
                    continue
                bucket = csr.get(fid)
                if bucket is not None and bucket.rows:
                    r, o = bucket.arrays()
                    seg_rows.append(r)
                    seg_opts.append(o)
                    seg_msg.append(i)
                    seg_len.append(len(r))
                kids = keys_of.get(fid)
                if kids is not None:
                    sh_keys.append(kids)
                    sh_msg.append(i)
                    sh_len.append(len(kids))
        if sh_keys:
            s_msg = np.repeat(np.asarray(sh_msg, dtype=np.int64), sh_len)
            s_key = np.concatenate(sh_keys)
        else:
            s_msg = s_key = _EMPTY_I64
        if not seg_rows:
            return _EMPTY_I64, _EMPTY_I64, _EMPTY_I64, rules, s_msg, s_key
        if len(seg_rows) == 1:
            client_rows, opts_rows = seg_rows[0], seg_opts[0]
            msg_idx = np.full(seg_len[0], seg_msg[0], dtype=np.int64)
        else:
            client_rows = np.concatenate(seg_rows)
            opts_rows = np.concatenate(seg_opts)
            msg_idx = np.repeat(
                np.asarray(seg_msg, dtype=np.int64), seg_len
            )
        return msg_idx, client_rows, opts_rows, rules, s_msg, s_key
