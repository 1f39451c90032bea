"""Asyncio TCP connection: the owner of one client socket.

Re-creates `emqx_connection` (/root/reference/apps/emqx/src/
emqx_connection.erl:371-386 run_loop, :750-777 parse_incoming): reads
socket chunks into the incremental `StreamParser`, feeds packets to the
channel FSM, serializes outgoing packets, and drives the keepalive /
retry timers that the reference hangs off its process timers.

A socket's bytes reach `Connection._read` one of two ways, chosen once
from what the listener is.  On a plain TCP or TLS listener the
`Connection` is the transport's protocol: `data_received` keeps a read
and tells the listener's `ReadTurn`, which handles the reads of one
loop turn together once the turn's last ``recv`` is back, with no
task, future or coroutine a read, and what the coroutine awaits is
reading paused and resumed (a limiter's pause between two packets of
one read among them).  A WebSocket stream is no transport and keeps
the coroutine, `run`, over a reader / writer pair.

Who does the ``recv`` on the first way is decided from what the socket
is: a plain-TCP socket is read by the native reader thread
(ops/sockreader.py), which hands the loop a batch of reads a wake-up
and calls `data_received` for each, its transport kept paused for
reading from `connection_made` on; TLS (``sslproto`` owns the bytes)
and every socket where the library is absent are read by their
transport.
"""

from __future__ import annotations

import asyncio
import errno
import functools
import logging
import socket
import time
from typing import Callable, Iterator, List, Optional

from .. import failpoints
from ..codec import mqtt as C
from .broker import Broker
from .channel import Channel, CONNECTING

log = logging.getLogger("emqx_tpu.connection")

_TIMER_TICK = 5.0  # keepalive/retry check cadence
_ACKS = frozenset((C.PUBACK, C.PUBREC, C.PUBREL, C.PUBCOMP))


def _plain_tcp_fd(transport) -> int:
    """The descriptor of the plain TCP socket under ``transport`` (no
    TLS on it, AF_INET / AF_INET6 stream), or -1."""
    if (
        transport.get_extra_info("ssl_object") is not None
        or transport.get_extra_info("sslcontext") is not None
    ):
        return -1
    sock = transport.get_extra_info("socket")
    if (
        sock is not None
        and sock.family in (socket.AF_INET, socket.AF_INET6)
        and sock.type == socket.SOCK_STREAM
    ):
        return sock.fileno()
    return -1


class ReadTurn:
    """The reads of one loop turn on a direct listener, handled one
    after the other once the turn's last ``recv`` is back: one
    ``call_soon`` a turn, nothing a read.  Why not inside
    `data_received`: the same parse and `handle_in` cost twice as
    much right after a ``recv`` as in a row (the chip's host, PR 34:
    a PUBLISH read 29 us in a row, 57 between system calls, and a
    turn of a loaded broker holds a hundred reads; the cause was not
    witnessed: that a system call leaves the caches and the address
    translations cold is a hypothesis that fits the two orders'
    readings).  The run is queued during the turn, ahead of whatever
    the next poll finds readable, so a pause that a read asks for
    still takes effect before the connection's next ``recv``; and a
    transport that ends the connection in the turn of its last read
    (an EOF or a TLS ``close_notify`` behind the data) has that read
    handled first, by the connection."""

    def __init__(self, clock=None) -> None:
        self._conns: List["Connection"] = []
        # the profiler's `LoopClock`, or None: the turn's first read
        # opens its ``recv`` phase, the run is its ``reads`` phase (a
        # clock read a turn each, none a read)
        self._clock = clock

    def add(self, conn: "Connection") -> None:
        if not self._conns:
            asyncio.get_running_loop().call_soon(self._run)
            if self._clock is not None:
                self._clock.recv()
        self._conns.append(conn)

    def _run(self) -> None:
        clock = self._clock
        if clock is not None:
            clock.mark(clock.READS)
        conns, self._conns = self._conns, []
        for i, conn in enumerate(conns):
            closed = False
            try:
                closed = conn._handle_reads()
            except Exception:
                # (a boundary that must keep running: the turn's other
                # reads are off their sockets already)
                log.exception("read turn: a connection failed")
            if closed and i + 1 < len(conns):
                # a read closed its connection and queued the teardown:
                # the turn's later reads wait behind it, as they would
                # behind the next poll (a client's DISCONNECT, then its
                # reconnect's CONNECT, read in one batch)
                if not self._conns:
                    asyncio.get_running_loop().call_soon(self._run)
                self._conns[:0] = conns[i + 1:]
                break
        if clock is not None:
            clock.mark(clock.TAIL)


class Connection(asyncio.Protocol):
    """With a reader / writer pair: the coroutine path, `run`.  With
    neither: a protocol for ``loop.create_server``, whose transport
    is the writer from `connection_made` on; the listener's
    ``admit(conn)`` is then asked whether it has room, its ``turn``
    told of every read and ``on_lost(conn)`` told once the connection
    has ended and the transport is gone."""

    def __init__(
        self,
        broker: Broker,
        reader: Optional[asyncio.StreamReader] = None,
        writer: Optional[asyncio.StreamWriter] = None,
        mountpoint: Optional[str] = None,
        limiter=None,
        admit: Optional[Callable[["Connection"], bool]] = None,
        on_lost: Optional[Callable[["Connection"], None]] = None,
        turn: Optional[ReadTurn] = None,
    ) -> None:
        self.broker = broker
        self.reader = reader
        self.writer = writer
        self.mountpoint = mountpoint
        self.limiter = limiter
        self._admit = admit
        self._on_lost = on_lost
        self._turn = turn
        self.channel: Optional[Channel] = None
        self._closed = asyncio.Event()
        self._congested = False
        # a send on the sender thread failed, or the reader thread's
        # recv was reset: the connection ends as ``peer_reset``
        self._failed = False
        self._torn = False  # `_teardown` ran
        self._timer: Optional[asyncio.Task] = None
        # the direct path: what was received and is not handled yet
        # (the turn's read; further ones only behind a limiter's
        # pause), and the stand-ins for what `run` awaits: why
        # reading is paused ("lane", "defer", "write", "limiter";
        # it resumes when no reason is left), the CONNECTING idle
        # timeout, a limiter's pause, why the transport ended inside
        # that pause (the close waits until it is paid) and whether
        # the transport is gone
        self._reads: List[bytes] = []
        self._paused: set = set()
        self._idle: Optional[asyncio.TimerHandle] = None
        self._owed: Optional[asyncio.TimerHandle] = None
        self._ended: Optional[str] = None
        self._lost = False
        # the native reader thread and this connection's slot there
        # (from `connection_made`, where the socket is plain TCP)
        self._reader = None
        self._rslot = -1
        if writer is not None:
            self._attach(writer, getattr(writer, "transport", None))

    def _attach(self, writer, transport) -> None:
        """The socket is known: the channel, and where writes go."""
        self.writer = writer
        peername = writer.get_extra_info("peername")
        peer = f"{peername[0]}:{peername[1]}" if peername else "?"
        self.channel = Channel(
            self.broker,
            send=self._send_packets,
            close=self._close,
            peer=peer,
            mountpoint=self.mountpoint,
        )
        # outbound high-watermark input: the transport's write buffer
        # is where a stalled subscriber's bytes pile up (WS streams
        # that can't report simply leave the watermark inactive)
        if transport is not None and hasattr(
            transport, "get_write_buffer_size"
        ):
            self._tbuf = transport.get_write_buffer_size
            self.channel.transport_buffered = self._out_buffered
        else:
            self._tbuf = None
        # where a flush scope's writes go, decided once from what the
        # socket is: plain TCP under a transport that writes straight
        # to it -> the native sender thread (ops/sockwriter.py); TLS,
        # WebSocket (no transport), anything else -> the transport
        self._sender = None
        self._slot = -1
        self._handed = False  # the sender may still hold bytes of ours
        self._parked = False  # the transport took parked bytes back
        self._plain_fd = (
            _plain_tcp_fd(transport) if self._tbuf is not None else -1
        )
        snd = self.broker.sender
        if snd is not None and self._plain_fd >= 0:
            self._slot = snd.open(self._plain_fd, self)
            if self._slot >= 0:
                self._sender = snd
        # (a read's PUBACKs come as one `AckRun`, not as k packets)
        self.parser = C.StreamParser(
            max_packet_size=self.broker.config.mqtt.max_packet_size,
            ack_runs=True,
        )

    # -------------------------------------------------------- output

    # a socket whose kernel/transport send buffer holds more than this
    # is a congested subscriber (emqx_congestion's alarm_congestion on
    # sndbuf full); alarm per clientid, cleared when the buffer drains
    CONGESTION_BYTES = 1 << 20

    def _send_packets(self, packets: List[C.Packet]) -> None:
        if self.writer.is_closing():
            return
        # the loop's own clock: two reads a socket write, none a packet
        lc = self.broker.profiler.loop
        t_out = time.perf_counter() if lc is not None else 0.0
        m = self.broker.metrics
        version = self.channel.version
        n = 0
        parts = []
        for p in packets:
            parts.append(C.serialize(p, version))
            # a Raw blob (native window assembly) carries a whole
            # delivery run in one buffer — count its real packets
            n += getattr(p, "n_packets", 1)
        data = b"".join(parts)
        m.inc("packets.sent", n)
        m.inc("bytes.sent", len(data))
        handed = self._sender is not None and self._hand_over(data)
        if not handed:
            self.writer.write(data)
        if lc is not None:
            lc.egress(t_out, n, handed)
        self._note_buffered()

    def _hand_over(self, data: bytes) -> bool:
        """The order rule of the two sinks.  The wire carries a
        connection's bytes in the order `_send_packets` was called:
        a write goes to the sender thread inside a flush scope while
        the transport's buffer is empty, and ANY write follows bytes
        the thread still holds; it goes to the transport (False) only
        while the thread holds nothing.  After a hand-back the
        transport owns the connection until its buffer is empty."""
        snd, slot = self._sender, self._slot
        if self._handed and snd.pending(slot) == 0:
            self._handed = False
        if not self._handed:
            if self._parked:
                if self._tbuf():
                    return False
                self._parked = False
                snd.unpark(slot)
            if not snd.in_scope or self._tbuf():
                return False
        if failpoints.enabled:
            try:
                act = failpoints.evaluate(
                    "conn.sender.send", key=self.channel.peer
                )
            except ConnectionError:
                # as a send(2) that failed on the thread
                self.on_sender_failed(0)
                return True
            if act == "drop":
                return True  # the bytes the network ate
            if act == "duplicate":
                snd.add(slot, data)
        snd.add(slot, data)
        self._handed = True
        return True

    def on_sender_parked(self, data: bytes) -> None:
        """The socket would not take a write on the sender thread:
        here is the remainder and what was queued behind it, in
        order, for the transport (which tells `drain` and watches
        the socket for room)."""
        if self.writer.is_closing():
            return
        self._parked = True
        self.writer.write(data)
        self._note_buffered()

    def on_sender_failed(self, err: int) -> None:
        """A ``send`` on the sender thread failed (EPIPE, ECONNRESET):
        the connection closes as on the transport's
        ``connection_lost``."""
        log.debug("sender write to %s failed: errno %d",
                  self.channel.peer, err)
        self._failed = True
        self._close("peer_reset")

    def _out_buffered(self) -> int:
        """Bytes buffered toward this client: the transport's write
        buffer plus what the sender thread still holds."""
        n = self._tbuf()
        if self._handed:
            n += self._sender.pending(self._slot)
        return n

    def _note_buffered(self) -> None:
        # ONE accessor for the write-buffer signal — the same
        # `out_buffered` the dispatch watermark reads (0 when the
        # transport can't report, which also skips the alarm below)
        buffered = self.channel.out_buffered()
        if buffered == 0 and not self._congested:
            return
        cid = (
            self.channel.client.clientid
            if self.channel.client is not None else self.channel.peer
        )
        name = f"conn_congestion/{cid}"
        if buffered >= self.CONGESTION_BYTES:
            if not self._congested:
                self._congested = True
                self.broker.metrics.inc("connection.congested")
                self.broker.alarms.activate(
                    name,
                    details={"clientid": cid, "buffered": buffered},
                    message="connection send buffer congested "
                    "(slow consumer)",
                )
        elif self._congested and buffered < self.CONGESTION_BYTES // 4:
            self._congested = False
            self.broker.alarms.deactivate(name)

    def _close(self, reason: str) -> None:
        self._clear_alarm()
        self._release_slot()
        if not self.writer.is_closing():
            self.writer.close()
        self._closed.set()
        if self.reader is None and not self._torn:
            # (the direct path) no read loop wakes to find the flag:
            # the teardown runs as it would there, after the caller's
            # own work (a takeover registers the new channel first)
            # and whatever the transport still has to flush
            asyncio.get_running_loop().call_soon(self._teardown, "closed")

    def _clear_alarm(self) -> None:
        if self._congested:
            # a congestion alarm must not outlive its connection
            self._congested = False
            cid = (
                self.channel.client.clientid
                if self.channel.client is not None
                else self.channel.peer
            )
            self.broker.alarms.deactivate(f"conn_congestion/{cid}")

    def _release_slot(self) -> None:
        """Nothing more goes to the sender thread: it sends what it
        was handed, then closes its own descriptor (queue order); and
        no read of the reader thread's reaches the connection."""
        snd, self._sender = self._sender, None
        if snd is not None:
            snd.close(self._slot)
            self._slot = -1
            self._handed = False
        rdr, self._reader = self._reader, None
        if rdr is not None:
            rdr.close(self._rslot)
            self._rslot = -1

    def _teardown(self, reason: str) -> None:
        """The connection ends, once, whichever path read it and
        whoever noticed first; a direct listener is told once it has
        ended and its transport is gone, whichever comes last."""
        if not self._torn:
            self._torn = True
            if self._timer is not None:
                self._timer.cancel()
            for handle in (self._idle, self._owed):
                if handle is not None:
                    handle.cancel()
            self._idle = self._owed = None
            if self._failed:
                reason = "peer_reset"
            self.channel.connection_lost(reason)
            self._clear_alarm()
            self._release_slot()
            if not self.writer.is_closing():
                self.writer.close()
            self._closed.set()
        if self._lost and self._on_lost is not None:
            on_lost, self._on_lost = self._on_lost, None
            on_lost(self)

    # --------------------------------------------------------- input

    def _read(self, data: bytes, t_in: float, direct: bool = False,
              native: bool = False) -> Iterator[float]:
        """One socket read's work on the loop, the same on both read
        paths: count, parse, hand each packet to the channel, clock
        (``t_in``: when the read came back).  A generator only for
        the limiter, whose pauses sit between packets of one read: it
        yields the seconds owed and goes on when they are paid.  With
        no limiter it never yields."""
        # the loop's own clock: two reads a socket read, none a packet
        lc = self.broker.profiler.loop
        limiter = self.limiter
        channel = self.channel
        closed = self._closed
        self.broker.metrics.inc("bytes.received", len(data))
        # enforcement sits INSIDE the packet loop: one large TCP read
        # can carry a whole flood, so pausing only future reads would
        # let the burst straight through.  The pause throttles
        # processing (and the client, via the unread socket) without
        # disconnecting — the reference hibernates the socket the same
        # way.  The FULL deficit is paid: shared listener/zone buckets
        # hand out long waits under contention and cutting them short
        # would let the aggregate rate scale with the number of
        # connections.  (A pause is no work of the loop's: it moves
        # the read's start forward by what it took.)
        if limiter is not None:
            delay = limiter.consume(len(data), 0)
            if delay > 0:
                t_in += yield from self._owe(delay)
        # (an `AckRun` counts as the PUBACKs it carries)
        n_pubs = n_acks = n_run = n_other = 0
        for pkt in self.parser.feed(data):
            t = pkt.type
            if t == C.ACK_RUN:
                # (acks cost a limiter nothing: bytes are charged a
                # read, messages a PUBLISH)
                n_run += len(pkt.packet_ids)
            elif t == C.PUBLISH:
                n_pubs += 1
                if limiter is not None:
                    delay = limiter.consume(0, 1)
                    if delay > 0:
                        t_in += yield from self._owe(delay)
            elif t in _ACKS:
                n_acks += 1
            else:
                n_other += 1
            channel.handle_in(pkt)
            if closed.is_set():
                break
        if lc is not None:
            n_acks += n_run
            lc.ingress(t_in, n_pubs + n_acks + n_other,
                       n_pubs, n_acks, n_run, direct, native)

    def _owe(self, delay: float) -> Iterator[float]:
        """A limiter's pause inside `_read`: yields the seconds owed,
        returns the seconds it took to pay them."""
        self.broker.metrics.inc("connection.rate_limited")
        t0 = time.perf_counter()
        yield delay
        return time.perf_counter() - t0

    async def run(self) -> None:
        """The connection's receive loop (emqx_connection:run_loop),
        where the bytes come from a reader."""
        self._timer = asyncio.get_running_loop().create_task(self._timers())
        reason = "closed"
        lc = self.broker.profiler.loop
        try:
            idle = self.broker.config.mqtt.idle_timeout
            while not self._closed.is_set():
                timeout = idle if self.channel.state == CONNECTING else None
                try:
                    data = await asyncio.wait_for(
                        self.reader.read(65536), timeout
                    )
                except asyncio.TimeoutError:
                    reason = "idle_timeout"
                    break
                if not data:
                    break
                t_in = time.perf_counter() if lc is not None else 0.0
                for delay in self._read(data, t_in):
                    await self._pause(delay)
                await self._drain()
                batcher = self.broker.batcher
                if batcher is not None and batcher.congested(self.channel):
                    # stop reading until the publish queue drains: TCP
                    # backpressure propagates to the client, bounding
                    # broker memory and queueing delay (the esockd
                    # active_n / emqx_olp role)
                    await batcher.wait_uncongested(self.channel)
                if self.channel.defer_saturated:
                    # the async-verdict chain sits UPSTREAM of the
                    # batcher lanes: without its own pause a flooder
                    # could grow the chain without ever registering as
                    # lane congestion
                    await self.channel.wait_defer_drain()
        except C.MqttError as exc:
            log.debug("codec error from %s: %s", self.channel.peer, exc)
            reason = "frame_error"
        except (ConnectionResetError, BrokenPipeError):
            reason = "peer_reset"
        except asyncio.CancelledError:
            reason = "server_stopped"
        finally:
            self._teardown(reason)
            try:
                await self.writer.wait_closed()
            except (ConnectionError, asyncio.CancelledError):
                pass

    async def _pause(self, delay: float) -> None:
        """Sleep a limiter deficit in 1s slices, bailing early when
        the connection is closed (kick/stop must not wait out a long
        shared-bucket debt)."""
        while delay > 0 and not self._closed.is_set():
            step = min(delay, 1.0)
            await asyncio.sleep(step)
            delay -= step

    async def _drain(self) -> None:
        try:
            await self.writer.drain()
        except (ConnectionError, asyncio.CancelledError):
            self._closed.set()

    async def _timers(self) -> None:
        """Keepalive + redelivery ticks (the reference's per-channel
        timer messages, emqx_channel:handle_timeout/3)."""
        while not self._closed.is_set():
            await asyncio.sleep(_TIMER_TICK)
            if self.channel.keepalive_expired():
                self.channel.close("keepalive_timeout")
                return
            self.channel.retry_deliveries()
            if self.reader is not None:
                # (a transport tells its protocol: `pause_writing`)
                await self._drain()

    # ---------------------------------------- input, the direct path
    #
    # Every await of `run` is reading paused and resumed here, with
    # the same bound on memory and the same order: a pause takes
    # effect between reads, as the awaits sit after the packet loop,
    # and a paused connection's bytes wait in the kernel's socket
    # buffer, as they do for a `reader.read` nobody has awaited.

    def connection_made(self, transport: asyncio.Transport) -> None:
        if self._admit is not None and not self._admit(self):
            transport.close()  # the listener is full
            self._closed.set()
            return
        self._attach(transport, transport)
        rdr = self.broker.reader
        if rdr is not None and self._plain_fd >= 0:
            # (the transport starts reading after this callback: paused
            # now, it never does, and keeps its writes and its close)
            self._rslot = rdr.open(self._plain_fd, self)
            if self._rslot >= 0:
                self._reader = rdr
                transport.pause_reading()
        loop = asyncio.get_running_loop()
        self._timer = loop.create_task(self._timers())
        # one handle a connection, nothing a read
        self._idle = loop.call_later(
            self.broker.config.mqtt.idle_timeout, self._idle_expired
        )

    def data_received(self, data: bytes) -> None:
        if not self._reads:
            self._turn.add(self)
        self._reads.append(data)

    def _handle_reads(self) -> bool:
        """Handle what was received, in order, as far as a limiter's
        pause lets it: the turn's run, and first whoever ends the
        connection in the turn of its last read.  True where a read
        closed the connection."""
        reads = self._reads
        was_open = not self._closed.is_set()
        while reads and self._owed is None:
            if self._closed.is_set():
                reads.clear()
                break
            lc = self.broker.profiler.loop
            t_in = time.perf_counter() if lc is not None else 0.0
            self._step(self._read(reads.pop(0), t_in, True,
                                  self._reader is not None))
        return was_open and self._closed.is_set()

    def _step(self, body: Iterator[float]) -> None:
        """Run a read's body to its end, or to a limiter's pause:
        the rest of the read's packets wait in ``body``, in order,
        and no further read is handled meanwhile.  Then what `run`
        awaits after a read.  Nothing raised here escapes: one
        connection's fault may not cost the turn's others their
        reads."""
        try:
            delay = next(body, None)
            if delay is not None:
                self._pause_reading("limiter")
                self._owed = asyncio.get_running_loop().call_later(
                    delay, self._paid, body
                )
                return
            if self._closed.is_set():
                return
            if self._idle is not None and self.channel.state != CONNECTING:
                self._idle.cancel()
                self._idle = None
            batcher = self.broker.batcher
            if batcher is not None and batcher.congested(self.channel):
                # (see `run`) resumed by the release `wait_uncongested`
                # waits for
                self._pause_reading("lane")
                batcher.when_uncongested(
                    self.channel,
                    functools.partial(self._resume_reading, "lane"),
                )
            if self.channel.defer_saturated:
                self._pause_reading("defer")
                self.channel.when_defer_drained(
                    functools.partial(self._resume_reading, "defer")
                )
        except C.MqttError as exc:
            log.debug("codec error from %s: %s", self.channel.peer, exc)
            self._teardown("frame_error")
        except (ConnectionResetError, BrokenPipeError):
            self._teardown("peer_reset")
        except Exception:
            log.exception("read from %s failed", self.channel.peer)
            self._teardown("closed")

    def _paid(self, body: Iterator[float]) -> None:
        self._owed = None
        self._step(body)
        self._handle_reads()
        if self._owed is not None:
            return  # the next pause
        if self._ended is not None:
            self._teardown(self._ended)
        else:
            self._resume_reading("limiter")

    def _pause_reading(self, why: str) -> None:
        # (the reader thread's slot: a flag, and no recv begins after)
        if not self._paused:
            if self._reader is not None:
                self._reader.pause(self._rslot)
            else:
                self.writer.pause_reading()
        self._paused.add(why)

    def _resume_reading(self, why: str) -> None:
        # (the reader thread's slot: the flag off, and a re-arm)
        self._paused.discard(why)
        if not self._paused and not self._closed.is_set():
            if self._reader is not None:
                self._reader.resume(self._rslot)
            else:
                self.writer.resume_reading()

    def is_reading(self) -> bool:
        """Whether the socket is read, by whichever reads it."""
        if self._reader is not None:
            return self._reader.reading(self._rslot)
        return self.writer.is_reading()

    def pause_writing(self) -> None:
        # the write buffer is over its high-water mark: what
        # `writer.drain()` waits out
        self._pause_reading("write")

    def resume_writing(self) -> None:
        self._resume_reading("write")

    def _idle_expired(self) -> None:
        self._idle = None
        if self.channel.state == CONNECTING:
            self._teardown("idle_timeout")

    # The transport may end the connection in the turn of its last
    # read, before the turn's run: an EOF behind the data, a TLS
    # ``close_notify`` in the record after it (a one-shot publisher's
    # PUBLISH, DISCONNECT, close).  That read is handled first.  If a
    # limiter pauses it, the transport is gone before the pause is
    # paid (so too where a write fails inside one): the read's other
    # packets are handled when it is, as `run` would, and the
    # connection ends after them (`_paid`).

    def eof_received(self) -> bool:
        self._handle_reads()
        if self._owed is None:
            self._teardown("closed")
        return False  # the transport closes itself

    # The reader thread's end of a stream and its failed ``recv``, as
    # the selector transport handles its own: the same `eof_received`,
    # and the close (an errno aborts, as ``_force_close`` does).

    def on_reader_eof(self) -> None:
        if not self.eof_received():
            self.writer.close()

    def on_reader_failed(self, err: int) -> None:
        log.debug("read from %s failed: errno %d", self.channel.peer, err)
        if err in (errno.ECONNRESET, errno.EPIPE, errno.ESHUTDOWN):
            self._failed = True  # the transport's peer_reset errors
        self.writer.abort()

    def connection_lost(self, exc: Optional[Exception]) -> None:
        if self.channel is None:
            return  # never admitted
        self._handle_reads()
        self._lost = True
        reason = (
            "peer_reset"
            if isinstance(exc, (ConnectionResetError, BrokenPipeError))
            else "closed"
        )
        if self._owed is not None:
            self._ended = reason
        else:
            self._teardown(reason)

    def stop(self, reason: str) -> Optional[asyncio.Task]:
        """The listener's stop, on the direct path what cancelling
        `run` is on the other; the timer task, for the caller to
        wait for."""
        self._teardown(reason)
        return self._timer
