"""In-memory MQTT session: subscriptions, mqueue, inflight, awaiting_rel.

Re-creates `emqx_session_mem` (/root/reference/apps/emqx/src/
emqx_session_mem.erl) + the session facade contract (emqx_session.erl
callbacks :185-195): a channel-owned state machine holding QoS 1/2
delivery windows.  Like the reference, an incoming QoS 2 PUBLISH is
routed immediately and ``awaiting_rel`` only deduplicates until PUBREL
(emqx_session_mem publish path).

The session is detachable: on takeover the channel dies but the session
object moves to the new channel with its pending queue and inflight
window intact (emqx_session_mem:takeover/resume).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..codec import mqtt as C
from ..message import Message
from ..ops import dispatchasm
from .inflight import Inflight
from .mqueue import MQueue

# inflight entry phases (server→client delivery)
_PUBLISHING = "publish"  # sent PUBLISH, awaiting PUBACK (q1) / PUBREC (q2)
_PUBREL = "pubrel"  # sent PUBREL, awaiting PUBCOMP

# shared all--1 pid column for pure-QoS0 runs (the native assembler
# reads pid[i] per delivery; -1 = no packet-id splice), grown on
# demand; the ctypes pointer is cached so QoS0 runs pay zero per-run
# conversion cost
_NEG1 = np.full(256, -1, dtype=np.int64)
_NEG1_PTR = _NEG1.ctypes.data_as(dispatchasm._I64P)


def _neg1_ptr(n: int):
    global _NEG1, _NEG1_PTR
    if n > len(_NEG1):
        _NEG1 = np.full(max(n, 2 * len(_NEG1)), -1, dtype=np.int64)
        _NEG1_PTR = _NEG1.ctypes.data_as(dispatchasm._I64P)
    return _NEG1_PTR


def window_entries(msgs, keys, table, now: float) -> list:
    """The in-flight entries of a window's pending (QoS>0) deliveries,
    in ``keys``' order: ``keys`` is an int64 column of
    ``msg_idx * 2 + effective_qos - 1`` and ``table`` the window's
    object array of ``2 * len(msgs)`` entries (``None`` until built),
    shared by both effective-QoS variants.  ONE entry a distinct
    (message, QoS) of the window, stamped with the window's one clock
    read and shared by every subscriber that is owed it (entries are
    replace-not-mutate, see `_InflightEntry`): a run takes its slice
    of the returned list into `Session.bookkeep_entries`."""
    seen = np.zeros(len(table), dtype=bool)
    seen[keys] = True
    for key in np.flatnonzero(seen).tolist():
        if table[key] is None:
            table[key] = _InflightEntry(
                _PUBLISHING, msgs[key >> 1], (key & 1) + 1, now
            )
    return table[keys].tolist()


@dataclass
class SubOpts:
    """Per-subscription options (the reference's subopts map)."""

    qos: int = 0
    no_local: bool = False
    retain_as_published: bool = False
    retain_handling: int = 0
    subid: Optional[int] = None
    share_group: Optional[str] = None

    @classmethod
    def from_subscription(
        cls, sub: C.Subscription, share_group: Optional[str] = None
    ) -> "SubOpts":
        return cls(
            qos=sub.qos,
            no_local=sub.no_local,
            retain_as_published=sub.retain_as_published,
            retain_handling=sub.retain_handling,
            share_group=share_group,
        )

    def to_dict(self) -> dict:
        from dataclasses import asdict

        return asdict(self)

    @classmethod
    def from_dict(cls, data: dict) -> "SubOpts":
        return cls(**data)


class _InflightEntry:
    """One inflight-window entry.  A plain __slots__ class (not a
    dataclass): fanout windows construct tens of thousands of these
    per second, and the generated dataclass __init__ was a measurable
    share of the deliver stage.  Entries are immutable by convention —
    every transition REPLACES the entry (`Inflight.update`), never
    mutates one — which is what lets the window dispatch share one
    entry across every subscriber of the same (msg, qos) delivery."""

    __slots__ = ("phase", "msg", "qos", "ts")

    def __init__(self, phase: str, msg: Optional[Message], qos: int,
                 ts: float) -> None:
        self.phase = phase
        self.msg = msg
        self.qos = qos
        self.ts = ts


class Session:
    """One client's session state.  Pure data + transitions: no IO; the
    channel turns returned ``Publish``/``Pubrel`` packets into bytes."""

    def __init__(
        self,
        clientid: str,
        clean_start: bool = True,
        max_inflight: int = 32,
        max_mqueue_len: int = 1000,
        max_awaiting_rel: int = 100,
        await_rel_timeout: float = 300.0,
        retry_interval: float = 30.0,
        expiry_interval: float = 0.0,
        upgrade_qos: bool = False,
        mqueue_priorities: Optional[Dict[str, int]] = None,
        mqueue_default_priority: str = "lowest",
        mqueue_store_qos0: bool = True,
    ) -> None:
        self.clientid = clientid
        self.clean_start = clean_start
        self.created_at = time.time()
        self.subscriptions: Dict[str, SubOpts] = {}
        # persistence-gate refs this session holds (maintained by the
        # broker; released exactly once on discard/termination)
        self.gate_filters: set = set()
        self.mqueue = MQueue(
            max_len=max_mqueue_len,
            priorities=mqueue_priorities,
            default_priority=mqueue_default_priority,
            store_qos0=mqueue_store_qos0,
        )
        self.inflight = Inflight(max_inflight)
        self.awaiting_rel: Dict[int, float] = {}
        self.max_awaiting_rel = max_awaiting_rel
        self.await_rel_timeout = await_rel_timeout
        self.retry_interval = retry_interval
        self.expiry_interval = expiry_interval
        self.upgrade_qos = upgrade_qos
        self._next_pid = 0
        # outbound-watermark parking: True while this CONNECTED
        # session holds mqueue entries parked by the out-buffer
        # watermark.  While set, dispatch keeps routing new QoS>0
        # deliveries through the mqueue (same-topic order must not
        # invert past the parked backlog), and the channel's retry
        # timer drains the queue once the buffer recovers — the
        # ack-driven `_dequeue` alone may never fire (the stall can
        # begin with an empty inflight window).  Cleared by
        # `_dequeue` when the queue empties.
        self.out_parked = False
        # wired by the broker: called with (dropped_msg, reason) when a
        # delivery is lost to queue overflow or expiry
        self.on_dropped: Optional[Callable[[Message, str], None]] = None

    # ------------------------------------------------------- packet ids

    def _alloc_packet_id(self) -> int:
        for _ in range(65535):
            self._next_pid = self._next_pid % 65535 + 1
            if self._next_pid not in self.inflight:
                return self._next_pid
        raise RuntimeError("no free packet id")

    def alloc_packet_ids(self, n: int) -> List[int]:
        """Block packet-id allocation for a delivery run: ``n`` ids
        with wraparound and in-use-skip semantics identical to ``n``
        sequential `_alloc_packet_id` calls — ids granted earlier in
        the block count as in use even though their inflight inserts
        land afterwards (`Inflight.insert_run`).

        Fast path: away from the 65535 wrap, the next ``n``
        consecutive ids are almost always all free (sessions that ack
        keep the window tiny), so one C-speed membership scan replaces
        the per-id skip loop; any collision falls back to the exact
        sequential semantics."""
        lo = self._consecutive_block(n)
        if lo is not None:
            return list(range(lo, lo + n))
        return self._alloc_exact(n)

    def _alloc_exact(self, n: int) -> List[int]:
        """The exact sequential-semantics allocator (wraparound +
        in-use skip), for blocks the consecutive probe rejected."""
        inflight = self.inflight
        pid = self._next_pid
        out: List[int] = []
        taken = set()
        for _ in range(n):
            for _ in range(65535):
                pid = pid % 65535 + 1
                if pid not in inflight and pid not in taken:
                    out.append(pid)
                    taken.add(pid)
                    break
            else:
                raise RuntimeError("no free packet id")
        self._next_pid = pid
        return out

    def _consecutive_block(self, n: int) -> Optional[int]:
        """Claim ``n`` consecutive free packet ids starting after
        ``_next_pid`` in one C-speed probe; returns the first id, or
        None when the block would wrap or collide (callers fall back
        to the exact sequential allocator).  The ONE home of the
        fast-path predicate, shared by `alloc_packet_ids` and
        `bookkeep_entries`."""
        pid = self._next_pid
        if pid + n <= 65535 and (
            len(self.inflight) == 0
            or self.inflight.free_range(pid + 1, pid + n)
        ):
            self._next_pid = pid + n
            return pid + 1
        return None

    # ------------------------------------------------------ subscribe

    def subscribe(self, flt: str, opts: SubOpts) -> bool:
        """Record the subscription; returns True if it is new (vs an
        option refresh of an existing one)."""
        is_new = flt not in self.subscriptions
        self.subscriptions[flt] = opts
        return is_new

    def unsubscribe(self, flt: str) -> Optional[SubOpts]:
        return self.subscriptions.pop(flt, None)

    # -------------------------------------------------- deliver (out)

    def deliver(
        self,
        deliveries: List[Tuple[Message, SubOpts]],
        encoder: Optional["C.DispatchEncoder"] = None,
        version: Optional[int] = None,
        shed_qos0: bool = False,
        shed_cell: Optional[List[int]] = None,
    ) -> List[C.Packet]:
        """Accept matched messages for this session; returns the wire
        packets that can go out now (window permitting) — the
        `emqx_session:deliver/3` path.

        With a window ``encoder`` (and the channel's negotiated
        ``version``), standard deliveries come back as pre-rendered
        single-encode packets: the PUBLISH body is serialized once per
        window and only the packet id is patched per subscriber.
        Deliveries carrying a subscription identifier (per-subscriber
        properties) fall back to the ordinary per-packet encode.

        ``shed_qos0`` (olp ladder level 2): effective-QoS0 deliveries
        are shed — skipped, counted into ``shed_cell`` by the caller's
        window accounting — except $SYS messages, whose operator
        signals must survive the ladder.  The referee semantics the
        columns path's folded shed mask is property-tested against."""
        out: List[C.Packet] = []
        enc = encoder if version is not None else None
        cid = self.clientid
        upgrade = self.upgrade_qos
        now = time.time()  # ONE clock read per run (PERF402)
        # PERF403 ignores below: this loop IS the scalar referee — the
        # per-delivery reads here define the semantics the window
        # decision columns are property-tested bit-identical against
        for msg, opts in deliveries:
            if opts.no_local and msg.from_client == cid:  # brokerlint: ignore[PERF403]
                continue  # [MQTT-3.8.3-3]
            # inline _effective_qos: this loop runs once per delivery
            # of every fan-out window
            mq, oq = msg.qos, opts.qos  # brokerlint: ignore[PERF403]
            qos = (mq if mq > oq else oq) if upgrade else (
                mq if mq < oq else oq
            )
            if qos == 0:
                if shed_qos0 and not msg.sys:
                    if shed_cell is not None:
                        shed_cell[0] += 1
                    continue
                if enc is not None and opts.subid is None:  # brokerlint: ignore[PERF403]
                    out.append(enc.publish_qos0(msg, opts, version))
                else:
                    out.append(self._publish_packet(msg, opts, 0, None))
                continue
            if self.inflight.is_full():
                evicted = self.mqueue.insert(self._queued(msg, opts, qos))
                if evicted is not None and self.on_dropped is not None:
                    self.on_dropped(evicted, "queue_full")
                continue
            pid = self._alloc_packet_id()
            self.inflight.insert(
                pid, _InflightEntry(_PUBLISHING, msg, qos, now)
            )
            if enc is not None and opts.subid is None:  # brokerlint: ignore[PERF403]
                out.append(enc.publish(msg, opts, qos, pid, version))
            else:
                out.append(self._publish_packet(msg, opts, qos, pid))
        return out

    def deliver_run_native(
        self,
        deliveries: List[Tuple[Message, SubOpts]],
        encoder: "C.DispatchEncoder",
        version: int,
        shed_qos0: bool = False,
        shed_cell: Optional[List[int]] = None,
    ) -> Optional[Tuple[bytearray, Tuple[int, int, int]]]:
        """The window fast path for one client's run: Python makes the
        *decisions* in one pass — the no-local mask, effective QoS, a
        block packet-id allocation and one bulk inflight insert with a
        single clock read — then the native assembler
        (``ops.dispatchasm``) splices the encoder's arena spans into
        ONE contiguous wire buffer (head, 2-byte pid patch, tail per
        delivery) with the GIL released.  Returns
        ``(wire, (n_qos0, n_qos1, n_qos2))``.

        ``None`` = ineligible run, caller takes the per-delivery
        `deliver` loop (bit-identical wire): the native lib is absent,
        a delivery carries a subscription identifier, or the inflight
        window cannot absorb every QoS>0 delivery (the fallback loop
        queues the overflow per delivery)."""
        lib = dispatchasm.load()
        if lib is None:
            return None
        cid = self.clientid
        upgrade = self.upgrade_qos
        si = encoder.slot_index
        slot_for = encoder.slot_for
        hls = encoder.head_lens
        tls = encoder.tail_lens
        slots: List[int] = []
        pid_pos: List[int] = []
        pend: List[Tuple[Message, int]] = []
        n0 = 0
        total = 0
        # ONE pass makes every per-delivery decision; the loop body is
        # the entire per-delivery Python cost of the fast path.  A
        # run's deliveries overwhelmingly share one SubOpts object
        # (one subscription matched the whole window), so the opts
        # fields are re-read only when the identity changes.
        last_opts = None
        oq = nl = rap = 0
        for msg, opts in deliveries:
            if opts is not last_opts:
                # PERF403 ignores: already amortized to one read per
                # opts IDENTITY (not per delivery), and this run-local
                # path is the columns' scalar fallback
                if opts.subid is not None:  # brokerlint: ignore[PERF403]
                    return None  # per-subscriber props: fall back
                oq = opts.qos  # brokerlint: ignore[PERF403]
                nl = opts.no_local  # brokerlint: ignore[PERF403]
                rap = opts.retain_as_published  # brokerlint: ignore[PERF403]
                last_opts = opts
            mq = msg.qos
            qos = (mq if mq > oq else oq) if upgrade else (
                mq if mq < oq else oq
            )
            if nl and msg.from_client == cid:
                continue  # [MQTT-3.8.3-3]
            if shed_qos0 and qos == 0 and not msg.sys:
                # olp L2: effective-QoS0 deliveries shed ($SYS exempt)
                if shed_cell is not None:
                    shed_cell[0] += 1
                continue
            retain = rap if msg.retain else False
            slot = si.get((id(msg), qos, retain, version))
            if slot is None:
                slot = slot_for(msg, qos, retain, version)
            if qos == 0:
                n0 += 1
            else:
                pid_pos.append(len(slots))
                pend.append((msg, qos))
            slots.append(slot)
            total += hls[slot] + tls[slot]
        k = len(pend)
        if k and not self.inflight.room_for(k):
            return None  # full/near-full window: fallback queues overflow
        n = len(slots)
        n1 = n2 = 0
        if n == 0:
            return bytearray(), (0, 0, 0)
        body = np.asarray(slots, dtype=np.int64)
        if k:
            total += 2 * k
            pid_arr = np.full(n, -1, dtype=np.int64)
            now = time.time()  # ONE clock read per run
            pids = self.bookkeep_run(pend, now)
            pid_arr[pid_pos] = pids
            for _m, q in pend:
                if q == 1:
                    n1 += 1
                else:
                    n2 += 1
            pid_ptr = pid_arr.ctypes.data_as(dispatchasm._I64P)
        else:
            pid_ptr = _neg1_ptr(n)
        out = bytearray(total)
        wrote = dispatchasm.assemble_run(
            lib, encoder.native_views(), body, pid_ptr, n, out,
        )
        if wrote != total:  # defensive: never ship a short splice
            raise RuntimeError(
                f"native assembly wrote {wrote} of {total} bytes"
            )
        return out, (n0, n1, n2)

    def bookkeep_run(
        self, pend: List[Tuple[Message, int]], now: float
    ) -> List[int]:
        """QoS>0 bookkeeping for one delivery run: block packet-id
        allocation plus ONE bulk inflight insert, all entries stamped
        with the caller's single clock read.  ``pend`` is the run's
        kept QoS>0 deliveries as ``(msg, effective_qos)`` in delivery
        order; the caller has already checked `Inflight.room_for`.
        Shared by `deliver_run_native` and the window decision-column
        path (which makes one call per run but assembles the whole
        window's wire in one native splice)."""
        pids = self.alloc_packet_ids(len(pend))
        self.inflight.insert_run(
            pids,
            [_InflightEntry(_PUBLISHING, m, q, now) for m, q in pend],
        )
        return pids

    def bookkeep_entries(self, entries: List[_InflightEntry]):
        """`bookkeep_run` for pre-built entries: the columns dispatch
        builds ONE entry a distinct (message, qos) of the window
        (`window_entries`) and shares it across every subscriber
        (entries are replace-not-mutate, see `_InflightEntry`), so a
        fanout-256 window constructs 64 entries instead of 16384.

        Returns an ``int`` first-pid when the block is the consecutive
        fast path (ids ``pid..pid+n-1``, no list ever materialized) or
        the explicit pid ``List[int]`` from the exact allocator."""
        lo = self._consecutive_block(len(entries))
        if lo is not None:
            self.inflight.insert_seq(lo, entries)
            return lo
        # straight to the exact allocator: the probe just failed, so
        # alloc_packet_ids' fast path would only repeat the scan
        pids = self._alloc_exact(len(entries))
        self.inflight.insert_run(pids, entries)
        return pids

    def _effective_qos(self, msg_qos: int, opts: SubOpts) -> int:
        if self.upgrade_qos:
            return max(msg_qos, opts.qos)
        return min(msg_qos, opts.qos)

    def _queued(self, msg: Message, opts: SubOpts, qos: int) -> Message:
        # bake the effective qos + subopts into the queued copy so the
        # dequeue path needs no lookup (subscription may even be gone)
        q = Message(
            topic=msg.topic,
            payload=msg.payload,
            qos=qos,
            retain=msg.retain and opts.retain_as_published,
            from_client=msg.from_client,
            from_username=msg.from_username,
            mid=msg.mid,
            timestamp=msg.timestamp,
            properties=dict(msg.properties),
        )
        if opts.subid is not None:
            q.properties["subscription_identifier"] = [opts.subid]
        return q

    def _publish_packet(
        self,
        msg: Message,
        opts: Optional[SubOpts],
        qos: int,
        pid: Optional[int],
        dup: bool = False,
    ) -> C.Publish:
        props = dict(msg.properties)
        if opts is not None and opts.subid is not None:
            props["subscription_identifier"] = [opts.subid]
        left = msg.remaining_expiry()
        if left is not None:
            props["message_expiry_interval"] = left  # [MQTT-3.3.2-6]
        retain = msg.retain and (opts is None or opts.retain_as_published)
        return C.Publish(
            topic=msg.topic,
            payload=msg.payload,
            qos=qos,
            retain=retain,
            dup=dup,
            packet_id=pid,
            properties=props,
        )

    def _dequeue(self) -> List[C.Packet]:
        out: List[C.Packet] = []
        while not self.inflight.is_full():
            msg = self.mqueue.pop()
            if msg is None:
                break
            if msg.expired():
                if self.on_dropped is not None:
                    self.on_dropped(msg, "expired")
                continue
            if msg.qos == 0:
                out.append(self._publish_packet(msg, None, 0, None))
                continue
            pid = self._alloc_packet_id()
            self.inflight.insert(
                pid, _InflightEntry(_PUBLISHING, msg, msg.qos, time.time())
            )
            out.append(self._publish_packet(msg, None, msg.qos, pid))
        if not len(self.mqueue):
            # the watermark-parked backlog (if any) fully drained:
            # new deliveries may ride the fast path again
            self.out_parked = False
        return out

    # ------------------------------------------- client acks (out path)

    def puback(self, pid: int) -> Tuple[bool, List[C.Packet]]:
        """PUBACK for a QoS 1 delivery; returns (known, follow-ups)."""
        entry = self.inflight.get(pid)
        if entry is None or entry.qos != 1:
            return False, []
        self.inflight.delete(pid)
        return True, self._dequeue()

    def puback_run(self, pids) -> Tuple[List[int], List[C.Packet]]:
        """The PUBACKs of one run, in wire order: exactly what a
        `puback` call an id gives, as (the known ids in order, every
        follow-up in order).  With the queue empty as the run starts
        no ack has a follow-up (nothing enters the queue meanwhile:
        the loop thread is synchronous), so the ids leave the window
        together and `_dequeue` runs once, for ``out_parked``.  With a
        backlog the walk stays id by id: `_alloc_packet_id` skips the
        ids still in flight, so which ids the follow-ups get depends
        on which acks came before them."""
        if not len(self.mqueue):
            known = self.inflight.delete_run(pids, 1)
            return known, self._dequeue() if known else []
        known: List[int] = []
        out: List[C.Packet] = []
        for pid in pids:
            ok, more = self.puback(pid)
            if ok:
                known.append(pid)
                out += more
        return known, out

    def pubrec(self, pid: int) -> Tuple[bool, List[C.Packet]]:
        """PUBREC for a QoS 2 delivery: advance to PUBREL phase."""
        entry = self.inflight.get(pid)
        if entry is None or entry.qos != 2 or entry.phase != _PUBLISHING:
            return False, []
        self.inflight.update(
            pid, _InflightEntry(_PUBREL, None, 2, time.time())
        )
        return True, [C.Pubrel(packet_id=pid)]

    def pubcomp(self, pid: int) -> Tuple[bool, List[C.Packet]]:
        entry = self.inflight.get(pid)
        if entry is None or entry.phase != _PUBREL:
            return False, []
        self.inflight.delete(pid)
        return True, self._dequeue()

    # ------------------------------------------- incoming QoS 2 dedup

    def awaiting_rel_add(self, pid: int) -> str:
        """Register an incoming QoS 2 packet id.  Returns 'ok',
        'in_use' (duplicate), or 'full'."""
        if pid in self.awaiting_rel:
            return "in_use"
        if (
            self.max_awaiting_rel
            and len(self.awaiting_rel) >= self.max_awaiting_rel
        ):
            return "full"
        self.awaiting_rel[pid] = time.time()
        return "ok"

    def pubrel(self, pid: int) -> bool:
        return self.awaiting_rel.pop(pid, None) is not None

    def expire_awaiting_rel(self, now: Optional[float] = None) -> int:
        now = now if now is not None else time.time()
        stale = [
            pid
            for pid, ts in self.awaiting_rel.items()
            if now - ts > self.await_rel_timeout
        ]
        for pid in stale:
            del self.awaiting_rel[pid]
        return len(stale)

    # ------------------------------------------------- retry / resume

    def retry(self, now: Optional[float] = None) -> List[C.Packet]:
        """Retransmit timed-out inflight entries (emqx_session_mem
        retry timer)."""
        now = now if now is not None else time.time()
        out: List[C.Packet] = []
        for pid, entry in self.inflight.items():
            if now - entry.ts < self.retry_interval:
                continue
            if entry.phase == _PUBLISHING and entry.msg is not None:
                if entry.msg.expired(now):
                    self.inflight.delete(pid)
                    continue
                self.inflight.update(
                    pid,
                    _InflightEntry(_PUBLISHING, entry.msg, entry.qos, now),
                )
                out.append(
                    self._publish_packet(
                        entry.msg, None, entry.qos, pid, dup=True
                    )
                )
            elif entry.phase == _PUBREL:
                self.inflight.update(pid, _InflightEntry(_PUBREL, None, 2, now))
                out.append(C.Pubrel(packet_id=pid))
        return out

    def resume(self) -> List[C.Packet]:
        """Redeliver state to a reconnected client: all inflight
        PUBLISHes (dup=1) and PUBRELs in original order, then drain the
        queue into the window (emqx_session_mem:replay)."""
        out: List[C.Packet] = []
        now = time.time()
        for pid, entry in self.inflight.items():
            if entry.phase == _PUBLISHING and entry.msg is not None:
                self.inflight.update(
                    pid,
                    _InflightEntry(_PUBLISHING, entry.msg, entry.qos, now),
                )
                out.append(
                    self._publish_packet(
                        entry.msg, None, entry.qos, pid, dup=True
                    )
                )
            elif entry.phase == _PUBREL:
                out.append(C.Pubrel(packet_id=pid))
        out.extend(self._dequeue())
        return out

    def info(self) -> Dict[str, object]:
        return {
            "clientid": self.clientid,
            "created_at": self.created_at,
            "subscriptions_cnt": len(self.subscriptions),
            "mqueue_len": len(self.mqueue),
            "mqueue_dropped": self.mqueue.dropped,
            "inflight_cnt": len(self.inflight),
            "awaiting_rel_cnt": len(self.awaiting_rel),
        }
