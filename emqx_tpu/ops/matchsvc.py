"""Match service: the layer-2 half of the multicore split.

One process owns the trie-automaton (the ONLY device-enabled
`MatchEngine` in a worker pool), the interned (worker, fid) route
registry — rule fids included — and the session-agnostic decide
kernel.  N broker workers (layer 1: SO_REUSEPORT listeners, sessions,
channels, inflight) submit dispatch windows over per-worker
shared-memory rings (`broker.shmring.WindowRing`) and receive matched
fid CSR columns (or packed decide bytes) back in the same slot; a unix
control socket carries only hellos, route deltas, and 40-byte
doorbells.  This is the EMQX layer split (one ``emqx_broker`` per
scheduler over one shared ``emqx_router``) with the router table as a
process instead of an ETS table.

Route state is per-worker and rebuilt from the workers: a ``hello``
from worker *i* drops worker *i*'s previous routes (fresh worker, or a
re-attach after a service restart — either way the worker re-sends its
full live set), and a disconnect drops them too.  The service
therefore needs NO persistence: its entire state is a fold of its
workers' current subscriptions, exactly like `emqx_router`'s ETS
table.

Run it standalone (``python -m emqx_tpu.ops.matchsvc --socket P``) or
let `broker.multicore.WorkerPool` spawn and supervise it.
"""

from __future__ import annotations

import asyncio
import json
import logging
import struct
import time
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from .. import flightrec as _flight

log = logging.getLogger("emqx_tpu.matchsvc")

# service-side per-stage histograms (µs): one window's wall time split
# the same way the broker profiler splits its dispatch stages
SVC_STAGES = ("unpack", "match", "decide", "pack")

_U32 = struct.Struct("<I")
_DEC_HDR = struct.Struct("<IQIII")  # has_cols, rev, S, n, b

# ------------------------------------------------------ payload codec
#
# The slot payload formats both sides agree on.  Kept here (the
# service facade) so the worker-side client imports ONE source of
# truth; all numpy columns cross as raw little-endian bytes.


def pack_match_req(topics: List[str], congested: bool) -> Tuple[bytes, ...]:
    parts: List[bytes] = [
        struct.pack("<BI", 1 if congested else 0, len(topics))
    ]
    for t in topics:
        tb = t.encode("utf-8")
        parts.append(struct.pack("<H", len(tb)))
        parts.append(tb)
    return tuple(parts)


def unpack_match_req(payload: bytes) -> Tuple[List[str], bool]:
    congested, n = struct.unpack_from("<BI", payload, 0)
    pos = 5
    topics: List[str] = []
    for _ in range(n):
        (ln,) = struct.unpack_from("<H", payload, pos)
        pos += 2
        topics.append(payload[pos:pos + ln].decode("utf-8"))
        pos += ln
    return topics, bool(congested)


def pack_match_resp(id_sets: List[List[int]]) -> Tuple[bytes, ...]:
    n = len(id_sets)
    lens = np.fromiter((len(s) for s in id_sets), np.uint32, n)
    offsets = np.zeros(n + 1, dtype=np.uint32)
    np.cumsum(lens, out=offsets[1:])
    total = int(offsets[-1])
    fids = np.empty(total, dtype=np.uint32)
    pos = 0
    for s in id_sets:
        fids[pos:pos + len(s)] = s
        pos += len(s)
    return (
        struct.pack("<II", n, total),
        offsets.tobytes(),
        fids.tobytes(),
    )


def unpack_match_resp(payload: bytes) -> List[np.ndarray]:
    n, total = struct.unpack_from("<II", payload, 0)
    pos = 8
    offsets = np.frombuffer(payload, np.uint32, n + 1, pos)
    pos += (n + 1) * 4
    fids = np.frombuffer(payload, np.uint32, total, pos)
    return [
        fids[offsets[i]:offsets[i + 1]] for i in range(n)
    ]


def pack_decide_req(
    cols: Optional[Tuple[np.ndarray, ...]], rev: int,
    opts_rows: np.ndarray, client_rows: np.ndarray,
    msg_idx: np.ndarray, m_qos: np.ndarray, m_retain: np.ndarray,
    m_from_row: np.ndarray,
) -> Tuple[bytes, ...]:
    n = len(opts_rows)
    b = len(m_qos)
    s = len(cols[0]) if cols is not None else 0
    parts: List[bytes] = [
        _DEC_HDR.pack(1 if cols is not None else 0, rev, s, n, b)
    ]
    if cols is not None:
        oa_qos, oa_nl, oa_rap, oa_subid = cols
        parts += [
            np.ascontiguousarray(oa_qos, dtype=np.int8).tobytes(),
            np.ascontiguousarray(oa_nl, dtype=np.uint8).tobytes(),
            np.ascontiguousarray(oa_rap, dtype=np.uint8).tobytes(),
            np.ascontiguousarray(oa_subid, dtype=np.uint8).tobytes(),
        ]
    parts += [
        np.ascontiguousarray(opts_rows, dtype=np.int64).tobytes(),
        np.ascontiguousarray(client_rows, dtype=np.int64).tobytes(),
        np.ascontiguousarray(msg_idx, dtype=np.int64).tobytes(),
        np.ascontiguousarray(m_qos, dtype=np.int8).tobytes(),
        np.ascontiguousarray(m_retain, dtype=np.uint8).tobytes(),
        np.ascontiguousarray(m_from_row, dtype=np.int32).tobytes(),
    ]
    return tuple(parts)


def unpack_decide_req(payload: bytes):
    has_cols, rev, s, n, b = _DEC_HDR.unpack_from(payload, 0)
    pos = _DEC_HDR.size
    cols = None
    if has_cols:
        oa_qos = np.frombuffer(payload, np.int8, s, pos)
        pos += s
        oa_nl = np.frombuffer(payload, np.uint8, s, pos).view(bool)
        pos += s
        oa_rap = np.frombuffer(payload, np.uint8, s, pos).view(bool)
        pos += s
        oa_subid = np.frombuffer(payload, np.uint8, s, pos).view(bool)
        pos += s
        cols = (oa_qos, oa_nl, oa_rap, oa_subid)
    opts_rows = np.frombuffer(payload, np.int64, n, pos)
    pos += n * 8
    client_rows = np.frombuffer(payload, np.int64, n, pos)
    pos += n * 8
    msg_idx = np.frombuffer(payload, np.int64, n, pos)
    pos += n * 8
    m_qos = np.frombuffer(payload, np.int8, b, pos)
    pos += b
    m_retain = np.frombuffer(payload, np.uint8, b, pos).view(bool)
    pos += b
    m_from_row = np.frombuffer(payload, np.int32, b, pos)
    return (cols, rev, opts_rows, client_rows, msg_idx, m_qos,
            m_retain, m_from_row)


def pack_decide_resp(packed: np.ndarray, path: str) -> Tuple[bytes, ...]:
    return (
        struct.pack("<B", 1 if path == "dev" else 0),
        np.ascontiguousarray(packed, dtype=np.uint8).tobytes(),
    )


def unpack_decide_resp(payload: bytes) -> Tuple[np.ndarray, str]:
    # COPY out of the message buffer: the decision column outlives
    # this frame
    packed = np.frombuffer(payload, np.uint8, len(payload) - 1, 1).copy()
    return packed, ("dev" if payload[0] else "host")


# ----------------------------------------------------------- service


class _Worker:
    """One attached worker's connection state."""

    __slots__ = ("wid", "epoch", "ring", "writer", "cols_rev", "cols",
                 "fids")

    def __init__(self, wid: int, epoch: int, ring, writer) -> None:
        self.wid = wid
        self.epoch = epoch
        self.ring = ring
        self.writer = writer
        self.cols_rev: Optional[int] = None
        self.cols: Optional[Tuple[np.ndarray, ...]] = None
        self.fids: Set[int] = set()


class MatchService:
    """The shared match/decide process.  Single event loop, no worker
    threads: every route mutation and window runs loop-serialized, the
    same single-writer discipline `emqx_router`'s gen_server gives the
    reference (and the reason this class carries no locks)."""

    # the pong payload's stats keys (wire compat with the worker-side
    # cache): registry counter matchsvc.<key>
    STAT_KEYS = ("windows", "topics", "decides", "route_ops", "errors",
                 "flight_relayed")

    def __init__(self, socket_path: str,
                 use_device: Optional[bool] = None,
                 engine_kw: Optional[Dict] = None,
                 flight=None) -> None:
        from ..engine import MatchEngine
        from ..metrics import Metrics
        from ..observability import Histogram

        self.socket_path = socket_path
        kw = dict(engine_kw or {})
        kw.setdefault("use_device", use_device)
        self.engine = MatchEngine(**kw)
        if self.engine.use_device is not False:
            # under --workers N this process owns the chip, so its
            # shape-class compiles are the ones worth keeping on disk
            from ..engine import enable_compile_cache

            enable_compile_cache()
        self._workers: Dict[int, _Worker] = {}
        self._server: Optional[asyncio.AbstractServer] = None
        # real metrics registry (the reference's emqx_metrics slots),
        # not an ad-hoc dict: the broker re-exposes these through
        # /metrics as emqx_matchsvc_* via the pong payload
        self.metrics = Metrics()
        self._hist: Dict[str, Histogram] = {
            name: Histogram() for name in SVC_STAGES
        }
        # flight recorder for THIS process (flightrec.FlightRecorder);
        # None = not armed (in-process test services usually pass one)
        self.flight = flight
        if flight is not None:
            flight.on_trigger = self._broadcast_flight
        self._inc = self.metrics.inc

    def stats_dict(self) -> Dict[str, int]:
        val = self.metrics.val
        return {k: val(f"matchsvc.{k}") for k in self.STAT_KEYS}

    def hist_dict(self) -> Dict[str, Dict]:
        return {
            name: h.snapshot().raw_dict()
            for name, h in self._hist.items()
        }

    # ------------------------------------------------------ lifecycle

    async def start(self) -> None:
        if self.engine.use_device is not False:
            # the engine is empty until the workers replay their
            # routes: this sets the bucket width its folds and
            # rebuilds warm a new automaton for, before the swap —
            # the workers' shipped window width (their batch_max is
            # not on the wire)
            from ..config import BrokerEngineConfig

            self.engine.warmup(BrokerEngineConfig().batch_max)
        self._server = await asyncio.start_unix_server(
            self._serve, path=self.socket_path
        )
        log.info("match service on %s (device=%s)",
                 self.socket_path, self._device_on())

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()  # no new workers
        # drop the attached workers BEFORE waiting: since Python 3.12
        # wait_closed() waits for every accepted connection, and a
        # worker's stays open until its writer is closed here
        for w in list(self._workers.values()):
            self._drop_worker(w)
        if self._server is not None:
            await self._server.wait_closed()
            self._server = None

    def _device_on(self) -> bool:
        """Whether an accelerator backs the engine.  A pinned device
        (``use_device=True``) that JAX cannot initialize is an error
        the operator must see, not "no device"; auto mode serves on
        the host trie and says so."""
        eng = self.engine
        if eng.use_device is False:
            return False
        import jax

        try:
            return jax.devices()[0].platform != "cpu"
        except RuntimeError:
            if eng.use_device is True:
                raise
            log.warning("no JAX backend: matching serves on the host "
                        "trie", exc_info=True)
            return False

    # ------------------------------------------------------- routes

    def _drop_worker(self, w: _Worker) -> None:
        self._workers.pop(w.wid, None)
        for fid_id in list(w.fids):
            self.engine.delete((w.wid, fid_id))
        w.fids.clear()
        if w.ring is not None:
            w.ring.close()
            w.ring = None
        try:
            w.writer.close()
        except Exception:
            pass

    def _apply_routes(self, w: _Worker, add, delete) -> None:
        for fid_id, flt in add:
            fid_id = int(fid_id)
            self.engine.insert(flt, (w.wid, fid_id))
            w.fids.add(fid_id)
        for fid_id in delete:
            fid_id = int(fid_id)
            self.engine.delete((w.wid, fid_id))
            w.fids.discard(fid_id)
        self._inc("matchsvc.route_ops", len(add) + len(delete))

    # ------------------------------------------------------- windows

    def _serve_window(self, w: _Worker, slot: int, seq: int) -> Dict:
        """One doorbelled slot: read request, compute, write response
        into the same slot.  Returns the completion doorbell dict."""
        if w.ring is None or self._workers.get(w.wid) is not w:
            # superseded/dropped incarnation: its ring is closed — a
            # late doorbell from the old connection must not touch it
            self._inc("matchsvc.errors")
            return {"t": "e", "slot": slot, "seq": seq,
                    "err": "worker detached"}
        got = w.ring.read(slot, w.epoch, seq)
        if got is None:
            self._inc("matchsvc.errors")
            return {"t": "e", "slot": slot, "seq": seq,
                    "err": "stale slot header"}
        kind, payload = got
        hist = self._hist
        t0 = time.perf_counter()
        try:
            from ..broker import shmring

            if kind == shmring.KIND_MATCH_REQ:
                topics, congested = unpack_match_req(payload)
                t1 = time.perf_counter()
                matched = self.engine.match_batch(
                    topics, congested=congested
                )
                t2 = time.perf_counter()
                wid = w.wid
                ids = [
                    [f[1] for f in s if type(f) is tuple and f[0] == wid]
                    for s in matched
                ]
                parts = pack_match_resp(ids)
                w.ring.write(slot, w.epoch, seq,
                             shmring.KIND_MATCH_RESP, parts)
                t3 = time.perf_counter()
                hist["unpack"].record((t1 - t0) * 1e6)
                hist["match"].record((t2 - t1) * 1e6)
                hist["pack"].record((t3 - t2) * 1e6)
                self._inc("matchsvc.windows")
                self._inc("matchsvc.topics", len(topics))
                fl = self.flight
                if fl is not None:
                    fl.record(_flight.EV_SVC_WINDOW, float(len(topics)),
                              (t3 - t0) * 1e6, float(seq), float(wid))
            elif kind == shmring.KIND_DECIDE_REQ:
                (cols, rev, opts_rows, client_rows, msg_idx, m_qos,
                 m_retain, m_from_row) = unpack_decide_req(payload)
                if cols is not None:
                    # own the columns beyond this slot's lifetime
                    w.cols = tuple(np.array(c) for c in cols)
                    w.cols_rev = rev
                elif w.cols_rev != rev or w.cols is None:
                    self._inc("matchsvc.errors")
                    return {"t": "e", "slot": slot, "seq": seq,
                            "err": "cols cache miss"}
                t1 = time.perf_counter()
                packed, path = self.engine.decide_window(
                    w.cols, (w.wid << 32) | (rev & 0xFFFFFFFF),
                    np.array(opts_rows), np.array(client_rows),
                    np.array(msg_idx), np.array(m_qos),
                    np.array(m_retain), np.array(m_from_row),
                )
                t2 = time.perf_counter()
                w.ring.write(slot, w.epoch, seq,
                             shmring.KIND_DECIDE_RESP,
                             pack_decide_resp(packed, path))
                t3 = time.perf_counter()
                hist["unpack"].record((t1 - t0) * 1e6)
                hist["decide"].record((t2 - t1) * 1e6)
                hist["pack"].record((t3 - t2) * 1e6)
                self._inc("matchsvc.decides")
                fl = self.flight
                if fl is not None:
                    fl.record(_flight.EV_SVC_WINDOW,
                              float(len(opts_rows)), (t3 - t0) * 1e6,
                              float(seq), float(w.wid))
            else:
                self._inc("matchsvc.errors")
                return {"t": "e", "slot": slot, "seq": seq,
                        "err": f"unknown kind {kind}"}
        except Exception as exc:  # degrade THIS window, not the worker
            log.exception("window slot=%d seq=%d failed", slot, seq)
            self._inc("matchsvc.errors")
            fl = self.flight
            if fl is not None:
                fl.note("svc_window_error", slot=slot, seq=seq,
                        error=repr(exc))
            return {"t": "e", "slot": slot, "seq": seq, "err": str(exc)}
        return {"t": "c", "slot": slot, "seq": seq}

    # ---------------------------------------------------- connection

    async def _serve(self, reader: asyncio.StreamReader,
                     writer: asyncio.StreamWriter) -> None:
        w: Optional[_Worker] = None
        try:
            while True:
                line = await reader.readline()
                if not line:
                    break
                try:
                    obj = json.loads(line)
                except json.JSONDecodeError:
                    log.warning("bad control line: %r", line[:80])
                    continue
                t = obj.get("t")
                if t == "hello":
                    w = await self._handle_hello(obj, writer)
                elif w is None:
                    self._send(writer, {"t": "e", "err": "hello first"})
                elif t == "routes":
                    self._apply_routes(
                        w, obj.get("add") or (), obj.get("del") or ()
                    )
                    self._send(writer, {"t": "routes_ok",
                                        "seq": obj.get("seq", 0)})
                elif t == "w":
                    out = self._serve_window(
                        w, int(obj["slot"]), int(obj["seq"])
                    )
                    self._send(writer, out)
                elif t == "ping":
                    fl = self.flight
                    self._send(writer, {
                        "t": "pong",
                        "stats": self.stats_dict(),
                        "hist": self.hist_dict(),
                        "routes": len(self.engine),
                        "flight": fl.status() if fl is not None else {},
                    })
                elif t == "flight":
                    self._handle_flight(obj, w)
                await writer.drain()
        except (ConnectionError, asyncio.IncompleteReadError):
            pass
        finally:
            if w is not None and self._workers.get(w.wid) is w:
                log.info("worker %d detached; dropping %d routes",
                         w.wid, len(w.fids))
                self._drop_worker(w)
            else:
                writer.close()

    async def _handle_hello(self, obj: Dict,
                            writer: asyncio.StreamWriter
                            ) -> Optional[_Worker]:
        from ..broker import shmring

        wid = int(obj["worker"])
        epoch = int(obj.get("epoch", 0))
        old = self._workers.get(wid)
        if old is not None:
            # a newer incarnation of this worker supersedes the old
            # connection (and its route set) atomically
            self._drop_worker(old)
        try:
            ring = shmring.WindowRing.attach(obj["ring"])
        except Exception as exc:
            log.warning("worker %d ring attach failed: %s", wid, exc)
            self._send(writer, {"t": "e", "err": f"ring: {exc}"})
            return None
        w = _Worker(wid, epoch, ring, writer)
        self._workers[wid] = w
        self._send(writer, {"t": "hello_ok",
                            "device": self._device_on()})
        log.info("worker %d attached (epoch %d, ring %s)",
                 wid, epoch, obj["ring"])
        return w

    # ----------------------------------------------- flight recorder

    def _handle_flight(self, obj: Dict, sender: Optional[_Worker]
                       ) -> None:
        """A worker tripped an anomaly: dump THIS process's ring under
        the initiator's id and relay the request to every OTHER
        attached worker — the service is the natural hub, so one
        trigger anywhere becomes one pool-wide correlated capture."""
        trig_id = str(obj.get("id") or "")
        reason = str(obj.get("reason") or "")
        if not trig_id:
            return
        fl = self.flight
        if fl is not None:
            fl.dump_remote(trig_id, reason)
        self._relay_flight(trig_id, reason,
                           skip_wid=sender.wid if sender else None)

    def _broadcast_flight(self, trig_id: str, reason: str) -> None:
        """on_trigger hook for SERVICE-side anomalies (watchdog stall,
        unhandled fault): push the dump request to every worker."""
        self._relay_flight(trig_id, reason, skip_wid=None)

    def _relay_flight(self, trig_id: str, reason: str,
                      skip_wid: Optional[int]) -> None:
        msg = {"t": "flight", "id": trig_id, "reason": reason}
        for ow in list(self._workers.values()):
            if skip_wid is not None and ow.wid == skip_wid:
                continue
            try:
                self._send(ow.writer, msg)
                self._inc("matchsvc.flight_relayed")
            except Exception:
                log.debug("flight relay to worker %d failed", ow.wid)

    def tick(self) -> None:
        """1 Hz housekeeping from the CLI runner: flight heartbeat +
        sensor drain for the service process."""
        fl = self.flight
        if fl is not None:
            fl.tick()

    @staticmethod
    def _send(writer: asyncio.StreamWriter, obj: Dict) -> None:
        writer.write(json.dumps(obj).encode() + b"\n")


# --------------------------------------------------------------- cli


def main(argv=None) -> None:
    import argparse
    import os
    import signal

    ap = argparse.ArgumentParser(
        description="emqx_tpu multicore match service"
    )
    ap.add_argument("--socket", required=True,
                    help="unix control socket path")
    ap.add_argument("--engine-json", default=None,
                    help="MatchEngine kwargs as JSON")
    ap.add_argument("--flight-json", default=None,
                    help="flight recorder kwargs as JSON "
                         "(FlightConfig fields incl. dump_dir)")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )
    engine_kw = json.loads(args.engine_json) if args.engine_json else None
    flight = None
    if args.flight_json:
        fl_kw = json.loads(args.flight_json)
        flight = _flight.FlightRecorder(
            role="matchsvc", process_label="matchsvc", **fl_kw
        )
    if os.path.exists(args.socket):
        os.unlink(args.socket)

    async def run() -> None:
        svc = MatchService(args.socket, engine_kw=engine_kw,
                           flight=flight)
        if flight is not None:
            flight.metrics = svc.metrics
            flight.arm_watchdog()
        await svc.start()
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)

        async def ticker() -> None:
            while not stop.is_set():
                svc.tick()
                await asyncio.sleep(1.0)

        tick_task = asyncio.ensure_future(ticker())
        try:
            await stop.wait()
        finally:
            tick_task.cancel()
            if flight is not None:
                flight.stop()
            await svc.stop()

    asyncio.run(run())


if __name__ == "__main__":
    main()
