"""QUIC listener: MQTT-over-QUIC terminating into the channel FSM.

The reference runs MQTT over MsQuic streams, reusing emqx_channel for
the protocol logic (/root/reference/apps/emqx/src/
emqx_quic_connection.erl + emqx_quic_data_stream.erl); same shape
here on the from-scratch QUIC transport (emqx_tpu/quic/): one UDP
socket, connections demultiplexed by connection id, and the client's
first bidirectional stream (id 0) carrying the MQTT byte stream into
a `Channel` — subsequent packets ride the same stream, exactly like
the reference's single data stream mode.

Also provides `QuicClientTransport`, the test-side client (open a
connection, speak MQTT over stream 0)."""

from __future__ import annotations

import asyncio
import logging
from typing import Dict, List, Optional, Tuple

from ..aio import cancel_and_wait
from ..codec import mqtt as C
from ..quic.connection import QuicConnection
from .channel import Channel

log = logging.getLogger("emqx_tpu.quic")

_PTO = 0.3  # retransmission probe cadence (loopback/LAN scope)


def load_cert_key(certfile: str, keyfile: str):
    from cryptography import x509
    from cryptography.hazmat.primitives import serialization

    with open(certfile, "rb") as f:
        cert = x509.load_pem_x509_certificate(f.read())
    with open(keyfile, "rb") as f:
        key = serialization.load_pem_private_key(f.read(), None)
    return cert.public_bytes(serialization.Encoding.DER), key


class _QuicChannelBridge:
    """One accepted QUIC connection: stream 0 <-> Channel."""

    def __init__(self, listener: "QuicListener",
                 conn: QuicConnection, addr) -> None:
        self.listener = listener
        self.conn = conn
        self.addr = addr
        now = asyncio.get_event_loop().time()
        self.created = now
        self.last_rx = now
        # anti-amplification accounting (RFC 9000 §8.1): until the
        # peer's address validates, sends are capped at 3x receives
        self.bytes_rx = 0
        self.bytes_tx = 0
        self.rx_datagrams = 0
        self.hs_counted = True  # in the per-source handshake census
        self.parser = C.StreamParser(
            max_packet_size=listener.broker.config.mqtt.max_packet_size,
            ack_runs=True,
        )
        self.channel = Channel(
            listener.broker,
            send=self._send_packets,
            close=self._close,
            peer=f"{addr[0]}:{addr[1]}",
            mountpoint=listener.mountpoint,
        )
        self.stream_id: Optional[int] = None

    def _send_packets(self, packets: List[C.Packet]) -> None:
        if self.conn.closed or self.stream_id is None:
            return
        data = b"".join(
            C.serialize(p, self.channel.version) for p in packets
        )
        self.conn.send_stream(self.stream_id, data)
        self.listener.transmit(self)

    def _close(self, reason: str) -> None:
        self.conn.close(0)
        self.listener.transmit(self)
        self.listener.forget(self)

    def on_events(self) -> None:
        for ev in self.conn.events():
            if ev[0] == "stream":
                _, sid, data, fin = ev
                if self.stream_id is None:
                    self.stream_id = sid  # the client's data stream
                if sid != self.stream_id:
                    continue  # single data stream mode
                try:
                    for pkt in self.parser.feed(data):
                        self.channel.handle_in(pkt)
                except Exception:
                    log.exception("quic: channel feed failed")
                    self._close("protocol_error")
                    return
                if fin:
                    self.channel.connection_lost("peer_fin")
                    self.listener.forget(self)
            elif ev[0] == "closed":
                self.channel.connection_lost("quic_closed")
                self.listener.forget(self)


class _ServerProtocol(asyncio.DatagramProtocol):
    def __init__(self, listener: "QuicListener") -> None:
        self.listener = listener
        self.transport = None

    def connection_made(self, transport) -> None:
        self.transport = transport

    def datagram_received(self, data: bytes, addr) -> None:
        self.listener.on_datagram(data, addr)


class QuicListener:
    """UDP endpoint owning every QUIC connection on one port."""

    def __init__(
        self,
        broker,
        bind: str = "0.0.0.0",
        port: int = 14567,
        certfile: str = "",
        keyfile: str = "",
        mountpoint: Optional[str] = None,
    ) -> None:
        self.broker = broker
        self.bind = bind
        self.port = port
        self.mountpoint = mountpoint
        self.cert_der, self.key = load_cert_key(certfile, keyfile)
        self._by_cid: Dict[bytes, _QuicChannelBridge] = {}
        self._transport = None
        self._pto_task: Optional[asyncio.Task] = None
        # handshake-phase connections per source IP: spoofed Initials
        # must not mint unbounded half-open conn+Channel state
        self._hs_per_src: Dict[str, int] = {}

    async def start(self) -> None:
        loop = asyncio.get_running_loop()
        self._transport, _proto = await loop.create_datagram_endpoint(
            lambda: _ServerProtocol(self),
            local_addr=(self.bind, self.port),
        )
        self.port = self._transport.get_extra_info("sockname")[1]
        self._pto_task = loop.create_task(self._pto_loop())
        log.info("quic listener on %s:%d", self.bind, self.port)

    async def stop(self) -> None:
        if self._pto_task is not None:
            await cancel_and_wait(self._pto_task)
            self._pto_task = None
        for bridge in list(self._by_cid.values()):
            bridge.conn.close(0)
            self.transmit(bridge)
        self._by_cid.clear()
        if self._transport is not None:
            self._transport.close()
            self._transport = None

    # ---------------------------------------------------------- data

    def on_datagram(self, data: bytes, addr) -> None:
        if not data:
            return
        bridge = self._demux(data, addr)
        if bridge is None:
            return
        bridge.last_rx = asyncio.get_event_loop().time()
        bridge.bytes_rx += len(data)
        bridge.rx_datagrams += 1
        bridge.conn.receive_datagram(data)
        if bridge.hs_counted and bridge.conn.handshake_complete:
            self._hs_uncount(bridge)
        bridge.on_events()
        self.transmit(bridge)
        if (
            not bridge.conn.address_validated
            and not bridge.conn.handshake_complete
            and bridge.rx_datagrams > 1
        ):
            # the client is still sending Initials: our flight was
            # lost or clipped by the amplification cap.  Re-arm it
            # NOW, driven by received bytes (each datagram grows the
            # 3x budget) — never by the timer, which a spoofed source
            # could turn into a reflector.
            bridge.conn.on_timeout()
            self.transmit(bridge)

    def _demux(self, data: bytes,
               addr) -> Optional[_QuicChannelBridge]:
        if data[0] & 0x80:  # long header: explicit dcid length
            dcid_len = data[5]
            dcid = data[6:6 + dcid_len]
        else:  # short header: our 8-byte scid
            dcid = data[1:9]
        bridge = self._by_cid.get(dcid)
        if bridge is not None:
            return bridge
        if not (data[0] & 0x80):
            return None  # short packet for an unknown connection
        if len(data) < 1200:
            return None  # a client Initial flight must fill 1200 bytes
        src = addr[0]
        if self._hs_per_src.get(src, 0) >= self.MAX_HANDSHAKES_PER_SOURCE:
            log.debug("quic: handshake flood from %s; Initial ignored",
                      src)
            return None
        conn = QuicConnection(
            True, cert_der=self.cert_der, key=self.key
        )
        bridge = _QuicChannelBridge(self, conn, addr)
        self._hs_per_src[src] = self._hs_per_src.get(src, 0) + 1
        # reachable by the client's original dcid (retransmitted
        # initials) AND by the scid we advertise
        self._by_cid[dcid] = bridge
        self._by_cid[conn.scid] = bridge
        return bridge

    def _hs_uncount(self, bridge: _QuicChannelBridge) -> None:
        if not bridge.hs_counted:
            return
        bridge.hs_counted = False
        src = bridge.addr[0]
        n = self._hs_per_src.get(src, 1) - 1
        if n > 0:
            self._hs_per_src[src] = n
        else:
            self._hs_per_src.pop(src, None)

    def transmit(self, bridge: _QuicChannelBridge) -> None:
        if self._transport is None:
            return
        for dgram in bridge.conn.datagrams_to_send():
            if (
                not bridge.conn.address_validated
                and bridge.bytes_tx + len(dgram) > 3 * bridge.bytes_rx
            ):
                # RFC 9000 §8.1 3x cap: a spoofed 1200-byte Initial
                # can reflect at most ~3600 bytes.  A clipped (or
                # lost) flight re-arms when the real client
                # retransmits — more rx budget — see on_datagram.
                continue
            bridge.bytes_tx += len(dgram)
            self._transport.sendto(dgram, bridge.addr)

    def forget(self, bridge: _QuicChannelBridge) -> None:
        self._hs_uncount(bridge)
        for cid in [
            cid for cid, b in self._by_cid.items() if b is bridge
        ]:
            del self._by_cid[cid]

    # a handshake not done within this window is abandoned (spoofed/
    # lost Initials must not hold half-open state forever), and a
    # completed connection with no datagrams for idle_timeout is
    # evicted — the advertised max_idle_timeout, enforced
    HANDSHAKE_DEADLINE = 10.0
    IDLE_TIMEOUT = 30.0
    MAX_HANDSHAKES_PER_SOURCE = 32

    async def _pto_loop(self) -> None:
        while True:
            await asyncio.sleep(_PTO)
            now = asyncio.get_event_loop().time()
            for bridge in set(self._by_cid.values()):
                if not bridge.conn.handshake_complete:
                    if now - bridge.created > self.HANDSHAKE_DEADLINE:
                        bridge.conn.close(0)
                        self.forget(bridge)
                        continue
                    if not bridge.conn.address_validated:
                        # no timer-driven retransmits to unvalidated
                        # peers: a spoofed Initial must not buy a 10s
                        # stream of cert flights to the victim.  Loss
                        # recovery is rx-driven (on_datagram).
                        continue
                    bridge.conn.on_timeout()
                    self.transmit(bridge)
                elif now - bridge.last_rx > self.IDLE_TIMEOUT:
                    bridge.channel.connection_lost("idle_timeout")
                    bridge.conn.close(0)
                    self.transmit(bridge)
                    self.forget(bridge)


class QuicClientTransport:
    """Test-side MQTT-over-QUIC client: connect, then a byte-stream
    API over stream 0."""

    def __init__(self, host: str, port: int) -> None:
        self.host = host
        self.port = port
        self.conn = QuicConnection(False)
        self._recv_buf = bytearray()
        self._recv_evt = asyncio.Event()
        self._transport = None
        self.stream_id: Optional[int] = None

    async def connect(self, timeout: float = 5.0) -> None:
        loop = asyncio.get_running_loop()

        outer = self

        class _Proto(asyncio.DatagramProtocol):
            def connection_made(self, transport) -> None:
                pass

            def datagram_received(self, data: bytes, addr) -> None:
                outer.conn.receive_datagram(data)
                outer._drain_events()
                outer._transmit()

        self._transport, _ = await loop.create_datagram_endpoint(
            lambda: _Proto(),
            remote_addr=(self.host, self.port),
        )
        self.conn.connect()
        self._transmit()
        deadline = loop.time() + timeout
        while not self.conn.handshake_complete:
            if loop.time() > deadline:
                raise TimeoutError("quic handshake timed out")
            await asyncio.sleep(0.01)
            self.conn.on_timeout()
            self._transmit()
        self.stream_id = self.conn.open_stream()

    def _drain_events(self) -> None:
        for ev in self.conn.events():
            if ev[0] == "stream":
                self._recv_buf += ev[2]
                self._recv_evt.set()

    def _transmit(self) -> None:
        if self._transport is None:
            return
        for dgram in self.conn.datagrams_to_send():
            self._transport.sendto(dgram)

    def write(self, data: bytes) -> None:
        self.conn.send_stream(self.stream_id, data)
        self._transmit()

    async def read(self, timeout: float = 5.0) -> bytes:
        if not self._recv_buf:
            self._recv_evt.clear()
            await asyncio.wait_for(self._recv_evt.wait(), timeout)
        out, self._recv_buf = bytes(self._recv_buf), bytearray()
        return out

    def close(self) -> None:
        self.conn.close(0)
        self._transmit()
        if self._transport is not None:
            self._transport.close()
            self._transport = None
