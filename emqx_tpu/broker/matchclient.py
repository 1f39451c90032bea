"""Worker-side client for the multicore match service.

`ServiceMatchEngine` is a drop-in `MatchEngine` for broker workers in
a `multicore` pool: every mutation updates a local HOST-ONLY mirror
(the superclass, pinned ``use_device=False``) AND streams a route
delta to the match service, and every publish window is submitted
over the worker's shared-memory `WindowRing` with a doorbell on the
control socket.  The mirror is the correctness anchor: any ring
trouble (service down, ring full, timeout, injected fault) degrades
THAT WINDOW to the in-process host path, which is bit-identical to
what the service computes — the referee property the multicore tests
pin.

Ordering makes the service exact, not approximate: route deltas and
window doorbells share one ordered control stream, so a window
submitted after `insert` returned is always matched against a route
table that includes that insert.  On re-attach (service restart) the
client replays its full route set from the mirror BEFORE new windows
flow, under the same write lock, so the stream stays ordered.

Slot lifetime under faults: a window that times out ABANDONS its slot
(quarantined in ``_abandoned``) instead of freeing it — a hung
service incarnation may still write there, and freeing would let a
fresh request be overwritten.  Abandoned slots return to the free
list when their late completion arrives or when the incarnation
provably dies (EOF → detach).

Threading: mutations arrive on the event loop, window submit/finish
on batcher executor threads, decide on the loop, and completions on
the dedicated reader thread.  ALL client state is guarded by
``_lk``/``_cond``; control-socket writes serialize under ``_slk``.
Lock order is ``_slk`` outer, ``_lk`` inner — never the reverse.
"""

from __future__ import annotations

import json
import logging
import socket
import threading
import time
from typing import Dict, Hashable, List, Optional, Sequence, Set, Tuple

import numpy as np

from .. import failpoints, flightrec
from ..engine import MatchEngine
from ..ops import matchsvc as wire
from . import shmring

log = logging.getLogger("emqx_tpu.matchclient")

_ROUTE_CHUNK = 2000  # route-replay entries per control line


class ServiceMatchEngine(MatchEngine):
    """MatchEngine facade that matches/decides via the shared service
    (shm ring + unix control socket) and falls back per-window to its
    own bit-identical host mirror."""

    def __init__(
        self,
        socket_path: str,
        worker_id: int,
        ring_slots: int = 8,
        ring_slot_bytes: int = 1 << 18,
        decide_min: int = 64,
        rpc_timeout: float = 2.0,
        reconnect_backoff: float = 0.2,
        **engine_kw,
    ) -> None:
        # the mirror must never grab the device the service owns
        engine_kw["use_device"] = False
        super().__init__(**engine_kw)
        self.socket_path = socket_path
        self.worker_id = int(worker_id)
        self.decide_min = int(decide_min)
        self.rpc_timeout = float(rpc_timeout)
        self.reconnect_backoff = float(reconnect_backoff)
        self._ring = shmring.WindowRing.create(
            slots=ring_slots, slot_bytes=ring_slot_bytes
        )
        self._lk = threading.Lock()
        self._cond = threading.Condition(self._lk)
        self._slk = threading.Lock()  # control-socket write serial
        self._sock: Optional[socket.socket] = None
        self._rfile = None
        self._attached = False
        self._svc_device = False
        self._closed = False
        self._epoch = 0
        self._seq = 0
        self._rseq = 0
        self._done: Dict[int, Dict] = {}       # seq -> doorbell obj
        self._waiting: Set[int] = set()
        self._abandoned: Dict[int, int] = {}   # seq -> quarantined slot
        self._fid_id: Dict[Hashable, int] = {}
        self._fid_obj: Dict[int, Hashable] = {}
        self._next_fid = 0
        self._cols_sent_rev: Optional[int] = None
        self.svc_stats = {
            "windows": 0, "decides": 0, "fallbacks": 0, "ring_full": 0,
            "reconnects": 0, "route_lines": 0, "quarantined": 0,
            "oversize": 0,
        }
        # observability wiring (set by the owning Broker): the flight
        # recorder sees ring-full edges / detaches and carries the
        # cross-process dump broadcast; the metrics registry gets the
        # multicore.ring.* counters
        self.flight = None
        self.metrics = None
        self._flight_pending: Optional[Tuple[str, str]] = None
        self._svc_remote: Dict = {}   # last pong payload from service
        self._ring_full_log_ts = 0.0  # rate-limits the degrade warning
        self._reader = threading.Thread(
            target=self._reader_main,
            name=f"matchsvc-client-w{worker_id}", daemon=True,
        )
        self._reader.start()

    # ------------------------------------------------------ lifecycle

    @property
    def ring_name(self) -> str:
        return self._ring.name

    @property
    def attached(self) -> bool:
        with self._lk:
            return self._attached

    def service_info(self) -> Dict:
        """Attachment + fallback counters for /api/v5/nodes, plus the
        ring occupancy snapshot and the service's last pong payload
        (service-side counters + stage histograms)."""
        with self._lk:
            return {
                "attached": self._attached,
                "service_device": self._svc_device,
                "epoch": self._epoch,
                "ring_free": self._ring.free_slots(),
                "ring": self._ring.stats(),
                "service": dict(self._svc_remote),
                **dict(self.svc_stats),
            }

    def poll_service(self) -> bool:
        """Fire-and-forget service stats poll (1 Hz from the broker
        tick): the pong lands on the reader thread and is cached in
        ``_svc_remote`` for service_info / /metrics."""
        return self._send({"t": "ping"})

    def flight_broadcast(self, trig_id: str, reason: str) -> None:
        """Carry a flight-dump trigger to the service (which dumps its
        own ring under the same id and relays to the other workers).
        When the anomaly IS the lost service connection, the line is
        queued and sent right after the next successful re-attach —
        the service's post-restart incarnation still holds its
        (fresh) ring, and every sibling worker still holds the window
        of history that matters."""
        msg = {"t": "flight", "id": trig_id, "reason": reason,
               "worker": self.worker_id}
        if not self._send(msg):
            with self._lk:
                if not self._closed:
                    self._flight_pending = (trig_id, reason)

    def close(self) -> None:
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._attached = False
            sock = self._sock
            self._sock = None
            self._cond.notify_all()
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            sock.close()
        self._reader.join(timeout=2.0)
        self._ring.close()

    # ---------------------------------------------------- route sync

    def _fid_for(self, fid: Hashable) -> int:
        """Interned wire id for a fid object.  Caller holds ``_lk``."""
        fid_id = self._fid_id.get(fid)
        if fid_id is None:
            fid_id = self._next_fid
            self._next_fid += 1
            self._fid_id[fid] = fid_id
            self._fid_obj[fid_id] = fid
        return fid_id

    def insert(self, flt: str, fid: Hashable) -> None:
        super().insert(flt, fid)
        self._route_send([(flt, fid)], ())

    def insert_many(self, pairs: Sequence[Tuple[str, Hashable]]) -> None:
        super().insert_many(pairs)
        self._route_send(pairs, ())

    def delete(self, fid: Hashable) -> bool:
        ok = super().delete(fid)
        if ok:
            self._route_send((), (fid,))
        return ok

    def delete_many(self, fids: Sequence[Hashable]) -> int:
        n = super().delete_many(fids)
        if n:
            self._route_send((), fids)
        return n

    def apply_routes(self, pairs, fids) -> None:
        # the mirror's batch under `_mlock`, then the delta streamed
        # with the lock released (`_lk` is never taken inside `_mlock`)
        super().apply_routes(pairs, fids)
        self._route_send(pairs, fids)

    def _route_send(self, add, delete) -> None:
        """Stream one route delta; a detached service just skips (the
        re-attach replay covers it from the mirror)."""
        with self._slk:
            with self._lk:
                if not self._attached or self._closed:
                    return
                msg = {"t": "routes", "seq": self._rseq}
                self._rseq += 1
                if add:
                    msg["add"] = [
                        [self._fid_for(fid), flt] for flt, fid in add
                    ]
                if delete:
                    dels = []
                    for fid in delete:
                        fid_id = self._fid_id.pop(fid, None)
                        if fid_id is not None:
                            self._fid_obj.pop(fid_id, None)
                            dels.append(fid_id)
                    if not dels and not add:
                        return
                    msg["del"] = dels
                sock = self._sock
            self._send_locked(sock, msg)

    def _route_snapshot(self) -> List[List]:
        """Full (fid_id, filter) replay list from the mirror.  Caller
        holds ``_lk``; mirror reads take the engine's own ``_mlock``
        (strictly after ``_lk`` in every code path, never inverted)."""
        with self._mlock:
            pairs = list(self._by_fid.items())
        return [[self._fid_for(fid), flt] for fid, flt in pairs]

    # ------------------------------------------------------ transport

    def _send_locked(self, sock: Optional[socket.socket],
                     obj: Dict) -> bool:
        """Write one control line.  Caller holds ``_slk``."""
        if sock is None:
            return False
        try:
            sock.sendall(json.dumps(obj).encode() + b"\n")
            return True
        except OSError:
            return False

    def _send(self, obj: Dict) -> bool:
        with self._slk:
            with self._lk:
                if not self._attached:
                    return False
                sock = self._sock
            return self._send_locked(sock, obj)

    # --------------------------------------------------- reader thread

    def _reader_main(self) -> None:
        backoff = self.reconnect_backoff
        while True:
            with self._lk:
                if self._closed:
                    return
            sock = self._reconnect_once()
            if sock is None:
                time.sleep(min(backoff, 2.0))
                backoff = min(backoff * 2, 2.0)
                continue
            backoff = self.reconnect_backoff
            try:
                self._serve_conn(sock)
            finally:
                self._detach(sock)

    def _reconnect_once(self) -> Optional[socket.socket]:
        """One attach attempt: connect, hello, replay the full route
        set, and only then mark attached (ordered with ``_slk`` held so
        no delta can slip ahead of the replay)."""
        sock = None
        try:
            if failpoints.evaluate(
                "multicore.service.restart", key=str(self.worker_id)
            ) == "drop":
                raise ConnectionError("attach attempt dropped")
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            sock.settimeout(self.rpc_timeout)
            sock.connect(self.socket_path)
            rfile = sock.makefile("rb")
            with self._lk:
                if self._closed:
                    raise ConnectionError("client closed")
                epoch = self._epoch + 1
            sock.sendall(json.dumps({
                "t": "hello", "worker": self.worker_id, "epoch": epoch,
                "ring": self._ring.name,
            }).encode() + b"\n")
            reply = json.loads(rfile.readline() or b"{}")
            if reply.get("t") != "hello_ok":
                raise ConnectionError(f"hello rejected: {reply}")
            with self._slk:
                with self._cond:
                    if self._closed:
                        raise ConnectionError("client closed")
                    snapshot = self._route_snapshot()
                    self._epoch = epoch
                    self._sock = sock
                    self._rfile = rfile
                    self._svc_device = bool(reply.get("device"))
                    self._attached = True
                    self._cols_sent_rev = None
                    # the previous incarnation is gone: quarantined
                    # slots can never be written again
                    for slot in self._abandoned.values():
                        self._ring.release(slot)
                    self._abandoned.clear()
                    self.svc_stats["reconnects"] += 1
                    self._cond.notify_all()
                for i in range(0, len(snapshot), _ROUTE_CHUNK):
                    self._send_locked(sock, {
                        "t": "routes", "seq": 0,
                        "add": snapshot[i:i + _ROUTE_CHUNK],
                    })
                    with self._lk:
                        self.svc_stats["route_lines"] += 1
                # a dump broadcast that raced the outage goes out the
                # moment the control stream exists again, so the
                # restarted service still joins the correlated capture
                with self._lk:
                    pending = self._flight_pending
                    self._flight_pending = None
                if pending is not None:
                    self._send_locked(sock, {
                        "t": "flight", "id": pending[0],
                        "reason": pending[1], "worker": self.worker_id,
                    })
            sock.settimeout(None)
            log.info("attached to match service %s (epoch %d, "
                     "device=%s, %d routes)", self.socket_path, epoch,
                     reply.get("device"), len(snapshot))
            return sock
        except (OSError, ValueError, json.JSONDecodeError) as exc:
            log.debug("match service attach failed: %s", exc)
            if sock is not None:
                sock.close()
            return None

    def _serve_conn(self, sock: socket.socket) -> None:
        rfile = self._rfile
        while True:
            try:
                line = rfile.readline()
            except OSError:
                return
            if not line:
                return
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                log.warning("bad service line: %r", line[:80])
                continue
            t = obj.get("t")
            if t in ("c", "e"):
                seq = int(obj.get("seq", -1))
                with self._cond:
                    slot = self._abandoned.pop(seq, None)
                    if slot is not None:
                        # late completion for a timed-out window: the
                        # service is done writing, the slot is safe
                        self._ring.release(slot)
                    elif seq in self._waiting:
                        self._done[seq] = obj
                        self._cond.notify_all()
            elif t == "flight":
                # correlated dump request initiated elsewhere in the
                # pool: freeze + persist THIS worker's ring under the
                # initiator's id (idempotent per id)
                fl = self.flight
                if fl is not None:
                    fl.dump_remote(
                        str(obj.get("id") or ""),
                        str(obj.get("reason") or ""),
                    )
            elif t == "pong":
                with self._lk:
                    self._svc_remote = {
                        "stats": obj.get("stats") or {},
                        "hist": obj.get("hist") or {},
                        "routes": obj.get("routes"),
                        "flight": obj.get("flight") or {},
                        "at": time.time(),
                    }
            # routes_ok / unknown lines are informational

    def _detach(self, sock: socket.socket) -> None:
        with self._cond:
            was_attached = self._attached
            closed = self._closed
            dead_epoch = self._epoch
            self._attached = False
            self._svc_device = False
            if self._sock is sock:
                self._sock = None
            # EOF proves the incarnation is dead: nothing will write
            # these slots again
            for slot in self._abandoned.values():
                self._ring.release(slot)
            self._abandoned.clear()
            self._done.clear()
            self._cond.notify_all()
        sock.close()
        # outside the locks: the trigger dumps and then broadcasts via
        # flight_broadcast, which re-enters _slk/_lk
        if was_attached and not closed:
            fl = self.flight
            if fl is not None:
                # epoch-keyed deterministic id: every worker watching
                # incarnation N die mints the SAME id, so one service
                # death yields one correlated capture even though the
                # relay hub is down at detection time
                fl.service_restart({
                    "socket": self.socket_path,
                    "worker": self.worker_id,
                }, key=f"e{dead_epoch}")

    # ------------------------------------------------------- windows

    def _note_ring_full(self) -> None:
        """Ring-full degrade bookkeeping: counters, a flight event,
        and a rate-limited warning that names WHICH ring saturated and
        at what depth (the window itself degrades to the in-process
        path — correct, just slower)."""
        with self._lk:
            self.svc_stats["ring_full"] += 1
        m = self.metrics
        if m is not None:
            m.inc("multicore.ring.full")
        st = self._ring.stats()
        fl = self.flight
        if fl is not None:
            fl.record(flightrec.EV_RING_FULL, float(st["slots"]),
                      float(st["full"]))
        now = time.monotonic()
        if now - self._ring_full_log_ts >= 1.0:
            self._ring_full_log_ts = now
            log.warning(
                "worker %d ring %s full at depth %d/%d (hwm %d, "
                "%d refusals total); window degrades to in-process "
                "match", self.worker_id, st["name"], st["in_flight"],
                st["slots"], st["high_watermark"], st["full"],
            )

    def _note_oversize(self) -> None:
        with self._lk:
            self.svc_stats["oversize"] += 1
        m = self.metrics
        if m is not None:
            m.inc("multicore.ring.oversize")

    def _ring_submit(self, topics: Sequence[str], congested: bool):
        """Submit one match window over the ring.  Returns a pending
        handle, or None → the caller serves the window in-process."""
        if failpoints.enabled:
            if failpoints.evaluate(
                "multicore.ring.submit", key=str(self.worker_id)
            ) == "drop":
                return None
        with self._lk:
            if not self._attached or self._closed:
                return None
            epoch = self._epoch
        try:
            slot = self._ring.acquire()
        except shmring.RingFull:
            self._note_ring_full()
            return None
        with self._lk:
            self._seq += 1
            seq = self._seq
        try:
            self._ring.write(
                slot, epoch, seq, shmring.KIND_MATCH_REQ,
                wire.pack_match_req(list(topics), congested),
            )
        except ValueError:  # window exceeds slot payload
            self._ring.release(slot)
            self._note_oversize()
            return None
        with self._lk:
            self._waiting.add(seq)
        if not self._send({"t": "w", "slot": slot, "seq": seq}):
            with self._lk:
                self._waiting.discard(seq)
            self._ring.release(slot)
            return None
        return (epoch, seq, slot)

    def _ring_complete(self, epoch: int, seq: int, slot: int
                       ) -> Optional[bytes]:
        """Wait out one submitted window; returns the raw response
        payload or None → fallback.  Never leaks the slot: success and
        hard errors free it, a timeout quarantines it (the service may
        still write there), and detach/attach drains the quarantine."""
        try:
            if failpoints.enabled:
                if failpoints.evaluate(
                    "multicore.ring.complete", key=str(seq)
                ) == "drop":
                    raise ConnectionError("completion dropped")
            deadline = time.monotonic() + self.rpc_timeout
            with self._cond:
                while True:
                    obj = self._done.pop(seq, None)
                    if obj is not None:
                        self._waiting.discard(seq)
                        break
                    if (self._closed or not self._attached
                            or self._epoch != epoch):
                        # incarnation gone: slot provably unreachable
                        self._waiting.discard(seq)
                        self._ring.release(slot)
                        return None
                    left = deadline - time.monotonic()
                    if left <= 0:
                        self._waiting.discard(seq)
                        self._abandoned[seq] = slot
                        self.svc_stats["quarantined"] += 1
                        m = self.metrics
                        if m is not None:
                            m.inc("multicore.ring.quarantined")
                        return None
                    self._cond.wait(left)
            if obj.get("t") != "c":
                self._ring.release(slot)
                return None
            got = self._ring.read(slot, epoch, seq)
            self._ring.release(slot)
            if got is None:
                return None
            return got[1]
        except failpoints.FailpointPanic:
            raise
        except Exception:
            with self._cond:
                self._waiting.discard(seq)
                if self._done.pop(seq, None) is not None:
                    # the completion had already arrived: the service
                    # is done writing and no later doorbell will come
                    # to drain a quarantine, so free the slot now
                    self._ring.release(slot)
                    return None
                self._abandoned[seq] = slot
                self.svc_stats["quarantined"] += 1
            m = self.metrics
            if m is not None:
                m.inc("multicore.ring.quarantined")
            return None

    # --------------------------------------------- MatchEngine facade

    def match_batch_submit(
        self, topics: Sequence[str], congested: bool = False,
        _force_device: bool = False,
    ):
        handle = self._ring_submit(topics, congested)
        if handle is not None:
            # (no timings: a window shipped to the service is timed there)
            return ("svc", handle, list(topics), ())
        return super().match_batch_submit(
            topics, congested, _force_device=_force_device
        )

    def match_batch_finish(self, pending, info=None):
        if pending[0] != "svc":
            return super().match_batch_finish(pending, info=info)
        _, (epoch, seq, slot), topics, _ = pending
        payload = self._ring_complete(epoch, seq, slot)
        if payload is None:
            with self._lk:
                self.svc_stats["fallbacks"] += 1
            if info is not None:
                info["path"] = "host-fallback"
            return self.match_batch_host(topics)
        try:
            id_rows = wire.unpack_match_resp(payload)
        except Exception:
            log.exception("bad match response for window of %d",
                          len(topics))
            if info is not None:
                info["path"] = "host-fallback"
            return self.match_batch_host(topics)
        with self._lk:
            fo = self._fid_obj
            # an id deleted between service match and here maps to
            # nothing — same outcome as a local match after the delete
            out = [
                {fo[i] for i in (int(x) for x in row) if i in fo}
                for row in id_rows
            ]
            self.svc_stats["windows"] += 1
        if info is not None:
            info["path"] = "svc"
        return out

    def match_batch(self, topics: Sequence[str],
                    congested: bool = False):
        """Loop-thread sync matches (forwarded dispatch, mgmt probes)
        stay on the local mirror: never block the event loop on the
        ring round-trip."""
        return super().match_batch_finish(
            super().match_batch_submit(topics, congested)
        )

    def decide_window(
        self,
        cols: Tuple,
        rev: int,
        opts_rows: np.ndarray,
        client_rows: np.ndarray,
        msg_idx: np.ndarray,
        m_qos: np.ndarray,
        m_retain: np.ndarray,
        m_from_row: np.ndarray,
        info=None,
    ) -> Tuple[np.ndarray, str]:
        with self._lk:
            use_svc = (
                self._attached and self._svc_device
                and len(opts_rows) >= self.decide_min
            )
        if use_svc:
            out = self._ring_decide(
                cols, rev, opts_rows, client_rows, msg_idx, m_qos,
                m_retain, m_from_row,
            )
            if out is not None:
                return out
            with self._lk:
                self.svc_stats["fallbacks"] += 1
        return super().decide_window(
            cols, rev, opts_rows, client_rows, msg_idx, m_qos,
            m_retain, m_from_row, info,
        )

    def _ring_decide(self, cols, rev, opts_rows, client_rows, msg_idx,
                     m_qos, m_retain, m_from_row):
        """Ship one decide window to the service's device kernel; the
        SubOpts columns ride along only when their rev changed since
        the last ship (the service caches them per worker)."""
        if failpoints.enabled:
            if failpoints.evaluate(
                "multicore.ring.submit", key="decide"
            ) == "drop":
                return None
        with self._lk:
            if not self._attached or self._closed:
                return None
            epoch = self._epoch
            send_cols = self._cols_sent_rev != rev
        try:
            slot = self._ring.acquire()
        except shmring.RingFull:
            self._note_ring_full()
            return None
        with self._lk:
            self._seq += 1
            seq = self._seq
        try:
            self._ring.write(
                slot, epoch, seq, shmring.KIND_DECIDE_REQ,
                wire.pack_decide_req(
                    cols if send_cols else None, rev, opts_rows,
                    client_rows, msg_idx, m_qos, m_retain, m_from_row,
                ),
            )
        except ValueError:
            self._ring.release(slot)
            self._note_oversize()
            return None
        with self._lk:
            self._waiting.add(seq)
        if not self._send({"t": "w", "slot": slot, "seq": seq}):
            with self._lk:
                self._waiting.discard(seq)
            self._ring.release(slot)
            return None
        if send_cols:
            with self._lk:
                # ordered stream: the service caches these cols before
                # any later window at this rev is served
                if self._epoch == epoch:
                    self._cols_sent_rev = rev
        payload = self._ring_complete(epoch, seq, slot)
        if payload is None:
            with self._lk:
                if self._cols_sent_rev == rev:
                    self._cols_sent_rev = None  # resend next time
            return None
        try:
            packed, path = wire.unpack_decide_resp(payload)
        except Exception:
            log.exception("bad decide response")
            return None
        if len(packed) != len(opts_rows):
            return None
        with self._lk:
            self.svc_stats["decides"] += 1
        return packed, path


__all__ = ["ServiceMatchEngine"]
