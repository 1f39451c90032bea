"""Asyncio TCP connection: the owning loop for one client socket.

Re-creates `emqx_connection` (/root/reference/apps/emqx/src/
emqx_connection.erl:371-386 run_loop, :750-777 parse_incoming): reads
socket chunks into the incremental `StreamParser`, feeds packets to the
channel FSM, serializes outgoing packets, and drives the keepalive /
retry timers that the reference hangs off its process timers.
"""

from __future__ import annotations

import asyncio
import logging
import socket
import time
from typing import List, Optional

from .. import failpoints
from ..codec import mqtt as C
from .broker import Broker
from .channel import Channel, CONNECTING

log = logging.getLogger("emqx_tpu.connection")

_TIMER_TICK = 5.0  # keepalive/retry check cadence
_ACKS = frozenset((C.PUBACK, C.PUBREC, C.PUBREL, C.PUBCOMP))


class Connection:
    def __init__(
        self,
        broker: Broker,
        reader: asyncio.StreamReader,
        writer: asyncio.StreamWriter,
        mountpoint: Optional[str] = None,
        limiter=None,
    ) -> None:
        self.broker = broker
        self.reader = reader
        self.writer = writer
        self.limiter = limiter
        peername = writer.get_extra_info("peername")
        peer = f"{peername[0]}:{peername[1]}" if peername else "?"
        self.channel = Channel(
            broker,
            send=self._send_packets,
            close=self._close,
            peer=peer,
            mountpoint=mountpoint,
        )
        # outbound high-watermark input: the transport's write buffer
        # is where a stalled subscriber's bytes pile up (WS streams
        # that can't report simply leave the watermark inactive)
        transport = getattr(writer, "transport", None)
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
        snd = broker.sender
        if snd is not None and self._tbuf is not None and (
            transport.get_extra_info("ssl_object") is None
            and transport.get_extra_info("sslcontext") is None
        ):
            sock = transport.get_extra_info("socket")
            if (
                sock is not None
                and sock.family in (socket.AF_INET, socket.AF_INET6)
                and sock.type == socket.SOCK_STREAM
                and sock.fileno() >= 0
            ):
                self._slot = snd.open(sock.fileno(), self)
                if self._slot >= 0:
                    self._sender = snd
        # (a read's PUBACKs come as one `AckRun`, not as k packets)
        self.parser = C.StreamParser(
            max_packet_size=broker.config.mqtt.max_packet_size,
            ack_runs=True,
        )
        self._closed = asyncio.Event()
        self._congested = False
        self._failed = False  # a send failed on the sender thread

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
            lc.egress(t_out, len(data), n, handed)
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
        if self._congested:
            # a congestion alarm must not outlive its connection
            self._congested = False
            cid = (
                self.channel.client.clientid
                if self.channel.client is not None
                else self.channel.peer
            )
            self.broker.alarms.deactivate(f"conn_congestion/{cid}")
        self._release_slot()
        if not self.writer.is_closing():
            self.writer.close()
        self._closed.set()

    def _release_slot(self) -> None:
        """Nothing more goes to the sender thread: it sends what it
        was handed, then closes its own descriptor (queue order)."""
        snd, self._sender = self._sender, None
        if snd is not None:
            snd.close(self._slot)
            self._slot = -1
            self._handed = False

    # --------------------------------------------------------- input

    async def run(self) -> None:
        """The connection's receive loop (emqx_connection:run_loop)."""
        timer = asyncio.get_running_loop().create_task(self._timers())
        reason = "closed"
        # the loop's own clock: two reads a socket read, none a packet
        lc = self.broker.profiler.loop
        t_in = 0.0
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
                if lc is not None:
                    t_in = time.perf_counter()
                self.broker.metrics.inc("bytes.received", len(data))
                # (an `AckRun` counts as the PUBACKs it carries)
                n_pubs = n_acks = n_run = n_other = 0
                if self.limiter is None:
                    for pkt in self.parser.feed(data):
                        t = pkt.type
                        if t == C.ACK_RUN:
                            n_run += len(pkt.packet_ids)
                        elif t == C.PUBLISH:
                            n_pubs += 1
                        elif t in _ACKS:
                            n_acks += 1
                        else:
                            n_other += 1
                        self.channel.handle_in(pkt)
                        if self._closed.is_set():
                            break
                else:
                    # enforcement sits INSIDE the packet loop: one large
                    # TCP read can carry a whole flood, so pausing only
                    # future reads would let the burst straight through.
                    # The pause throttles processing (and the client,
                    # via the unread socket) without disconnecting —
                    # the reference hibernates the socket the same way.
                    # The FULL deficit is slept (in 1s slices so close
                    # stays responsive): shared listener/zone buckets
                    # hand out long waits under contention and cutting
                    # them short would let the aggregate rate scale
                    # with the number of connections.
                    # (a pause is no work of the loop's: it moves the
                    # read's start forward by what was slept)
                    delay = self.limiter.consume(len(data), 0)
                    if delay > 0:
                        self.broker.metrics.inc("connection.rate_limited")
                        t_in += await self._pause(delay)
                    for pkt in self.parser.feed(data):
                        t = pkt.type
                        if t == C.ACK_RUN:
                            # (acks cost nothing here: bytes are
                            # charged a read, messages a PUBLISH)
                            n_run += len(pkt.packet_ids)
                        elif t == C.PUBLISH:
                            n_pubs += 1
                            delay = self.limiter.consume(0, 1)
                            if delay > 0:
                                self.broker.metrics.inc(
                                    "connection.rate_limited"
                                )
                                t_in += await self._pause(delay)
                        elif t in _ACKS:
                            n_acks += 1
                        else:
                            n_other += 1
                        self.channel.handle_in(pkt)
                        if self._closed.is_set():
                            break
                if lc is not None:
                    n_acks += n_run
                    lc.ingress(t_in, len(data), n_pubs + n_acks + n_other,
                               n_pubs, n_acks, n_run)
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
            timer.cancel()
            if self._failed:
                reason = "peer_reset"
            self.channel.connection_lost(reason)
            self._release_slot()
            if not self.writer.is_closing():
                self.writer.close()
            try:
                await self.writer.wait_closed()
            except (ConnectionError, asyncio.CancelledError):
                pass

    async def _pause(self, delay: float) -> float:
        """Sleep a limiter deficit in 1s slices, bailing early when
        the connection is closed (kick/stop must not wait out a long
        shared-bucket debt).  Returns the seconds slept."""
        slept = 0.0
        while delay > 0 and not self._closed.is_set():
            step = min(delay, 1.0)
            await asyncio.sleep(step)
            delay -= step
            slept += step
        return slept

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
            await self._drain()
