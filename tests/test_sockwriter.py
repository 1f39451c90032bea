"""The native sender thread (native/sockwriter.cpp, ops/sockwriter.py)
and the invariants `Connection` holds over its two sinks: per
connection the wire carries bytes in the order `_send_packets` was
called, across the sender and the transport; a socket that will not
take a write parks and its bytes come back for the transport, nothing
lost, doubled or reordered; no byte reaches a descriptor number after
its connection closed it; a failed send closes with ``peer_reset``;
`stop()` joins the thread with a full queue and leaves no descriptor.

The rig drives real `Connection`s over loopback TCP through
`_send_packets`, inside flush scopes and outside them.  Native cases
skip when the library is absent; the transport path is injected the
way the other native libraries' Python twins are."""

import asyncio
import errno
import os
import random
import socket
import struct
import time

import pytest

from emqx_tpu import failpoints
from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.connection import Connection
from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.codec import mqtt as C
from emqx_tpu.config import BrokerConfig, ListenerConfig
from emqx_tpu.ops import nativelib, sockwriter
from tools.racesim import run_seeds

native = pytest.mark.skipif(
    sockwriter.load() is None, reason="native sockwriter not built"
)


def run(coro, timeout=60.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def n_fds():
    return len(os.listdir("/proc/self/fd"))


def n_threads():
    """The process's sender threads (they name themselves)."""
    n = 0
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/comm") as f:
                n += f.read().strip() == "sockwriter"
        except OSError:
            pass  # a thread that ended meanwhile
    return n


class Rig:
    """A `Broker` with a sender and one real `Connection` per accepted
    loopback socket; the peer is a raw non-blocking socket the test
    reads as it pleases."""

    def __init__(self):
        self.broker = Broker(BrokerConfig())
        self.snd = None
        self._accepted = asyncio.Queue()
        self._hold = asyncio.Event()

    async def __aenter__(self):
        self.snd = sockwriter.start(
            asyncio.get_running_loop(), self.broker.profiler.loop
        )
        assert self.snd is not None
        self.broker.sender = self.snd
        self.server = await asyncio.start_server(
            self._on_client, "127.0.0.1", 0
        )
        self.addr = self.server.sockets[0].getsockname()
        return self

    async def __aexit__(self, *exc):
        self._hold.set()
        self.server.close()
        await self.server.wait_closed()
        self.snd.stop()
        self.broker.sender = None

    async def _on_client(self, reader, writer):
        conn = Connection(self.broker, reader, writer)
        await self._accepted.put(conn)
        await self._hold.wait()
        conn._close("rig_done")

    async def pair(self, rcvbuf=None, sndbuf=None, sock=None):
        if sock is None:
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        if rcvbuf:
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
        sock.setblocking(False)
        await asyncio.get_running_loop().sock_connect(sock, self.addr)
        conn = await self._accepted.get()
        if sndbuf:
            conn.writer.transport.get_extra_info("socket").setsockopt(
                socket.SOL_SOCKET, socket.SO_SNDBUF, sndbuf
            )
        return conn, sock

    def scope(self):
        return _Scope(self.snd)


class _Scope:
    """A flush scope, as `_dispatch_window` and `_uncork_all` open."""

    def __init__(self, snd):
        self.snd = snd

    def __enter__(self):
        self.snd.begin()

    def __exit__(self, *exc):
        self.snd.end()


def write(conn, data):
    conn._send_packets([C.Raw(data, conn.channel.version, 1)])


async def read_exact(sock, n, timeout=20.0):
    loop = asyncio.get_running_loop()
    out = bytearray()
    deadline = time.monotonic() + timeout
    while len(out) < n:
        left = deadline - time.monotonic()
        assert left > 0, f"read {len(out)} of {n} bytes"
        chunk = await asyncio.wait_for(
            loop.sock_recv(sock, min(n - len(out), 1 << 20)), left
        )
        if not chunk:
            break
        out += chunk
    return bytes(out)


async def settle(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition never held"
        await asyncio.sleep(0.005)


async def gone():
    """No sender thread is left.  `stop` has joined it by now, but a
    joined thread stays listed under /proc/self/task until the kernel
    has released its task: a moment, longer on a busy machine."""
    await settle(lambda: n_threads() == 0, 2.0)


def stamped(conn_id, seq, size):
    """A write that says whose it is and where it belongs."""
    head = struct.pack(">HI", conn_id, seq)
    body = bytes((conn_id * 31 + seq * 7 + k) & 0xFF for k in range(
        min(size, 64)
    ))
    return (head + body * (size // max(len(body), 1) + 1))[:max(size, 1)]


# ------------------------------------------------------- the library


@native
def test_library_loads_and_counts_its_own_clock():
    async def main():
        loop = asyncio.get_running_loop()
        snd = sockwriter.start(loop)
        a, b = socket.socketpair()
        try:
            slot = snd.open(a.fileno(), object())
            assert slot >= 0 and snd.pending(slot) == 0
            snd.begin()
            snd.add(slot, b"he")
            snd.add(slot, b"llo")
            assert snd.stats()["sends"] == 0  # nothing before the end
            snd.end()
            b.setblocking(False)
            assert await read_exact(b, 5) == b"hello"
            await settle(lambda: snd.pending(slot) == 0)
            st = snd.stats()
            assert st["sends"] == 2 and st["send_ns"] > 0
            assert st["parks"] == 0 and st["queued_bytes"] == 0
            assert st["slots"] == 1
            send_s, sends = snd.clock()
            assert sends == 2 and send_s == st["send_ns"] * 1e-9
            snd.close(slot)
        finally:
            snd.stop()
            a.close()
            b.close()
        # stopped: every call is a no-op, nothing raises
        assert snd.pending(0) == 0 and snd.open(0, object()) == -1
        snd.stop()

    run(main())


def test_absent_library_leaves_every_connection_on_its_transport(
    monkeypatch,
):
    monkeypatch.setitem(nativelib._libs, "sockwriter", None)

    async def main():
        assert sockwriter.start(asyncio.get_running_loop()) is None
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        try:
            assert srv.broker.sender is None
            from mqtt_client import TestClient

            sub = TestClient(srv.listeners[0].port, "s")
            await sub.connect()
            await sub.subscribe("t/#", qos=1)
            pub = TestClient(srv.listeners[0].port, "p")
            await pub.connect()
            for i in range(20):
                await pub.publish("t/x", b"%d" % i, qos=1)
            for i in range(20):
                assert (await sub.recv_publish()).payload == b"%d" % i
            lc = srv.broker.profiler.loop
            assert lc.egress_writes > 0 and lc.egress_writes_sender == 0
            await sub.close()
            await pub.close()
        finally:
            await srv.stop()

    run(main())


# ------------------------------------------------------ 3a: the order


@native
@pytest.mark.parametrize("size", [1, 4, 700, 9300, 65536, 262144])
def test_per_connection_fifo_byte_exact_across_both_sinks(size):
    """Six connections, sixty writes each of up to ``size`` bytes,
    some inside flush scopes and some lone, two of the peers behind
    tiny socket buffers: each peer reads exactly the concatenation of
    its writes in call order."""
    rng = random.Random(size)

    async def main():
        async with Rig() as rig:
            pairs = [
                await rig.pair(rcvbuf=4096 if k < 2 else None,
                               sndbuf=4096 if k < 2 else None)
                for k in range(6)
            ]
            want = [bytearray() for _ in pairs]
            seqs = [0] * len(pairs)
            readers = []

            def one(k):
                n = size if rng.random() < 0.3 else rng.randint(1, size)
                data = stamped(k, seqs[k], n)
                seqs[k] += 1
                want[k] += data
                write(pairs[k][0], data)

            for _round in range(20):
                with rig.scope():
                    for k in rng.sample(range(6), 6):
                        one(k)
                        if rng.random() < 0.5:
                            one(k)
                one(rng.randrange(6))  # a lone write
                if _round == 5:
                    readers = [
                        asyncio.ensure_future(read_exact(s, 1 << 30))
                        for _c, s in pairs
                    ]
                await asyncio.sleep(0)
            totals = [len(w) for w in want]
            # (the readers asked for more than there is: they end at
            # the close, so nothing after the last write goes unseen)
            if not readers:
                readers = [
                    asyncio.ensure_future(read_exact(s, 1 << 30))
                    for _c, s in pairs
                ]
            for conn, _s in pairs:
                await settle(lambda c=conn: c.channel.out_buffered() == 0,
                             timeout=30.0)
                conn._close("done")
            got = await asyncio.gather(*readers)
            for k, data in enumerate(got):
                assert len(data) == totals[k], (k, len(data), totals[k])
                assert data == bytes(want[k]), k
            lc = rig.broker.profiler.loop
            assert lc.egress_writes == sum(seqs)
            assert 0 < lc.egress_writes_sender <= lc.egress_writes
            for _c, s in pairs:
                s.close()

    run(main(), 120.0)


@native
def test_a_lone_write_follows_bytes_still_with_the_sender():
    """Outside a scope a write goes to the transport, unless the
    thread still holds bytes of the connection: then it follows
    them.  The thread is kept busy with another connection's backlog
    so that it provably holds them."""
    async def main():
        async with Rig() as rig:
            busy, busy_sock = await rig.pair()
            conn, sock = await rig.pair()
            lc = rig.broker.profiler.loop
            write(conn, b"lone-before;")  # nothing held: the transport
            assert lc.egress_writes_sender == 0
            drain = asyncio.ensure_future(read_exact(busy_sock, 40000))
            with rig.scope():
                for _ in range(40000):
                    rig.snd.add(busy._slot, b"x")
                write(conn, b"scoped;")
            held = rig.snd.pending(conn._slot)
            write(conn, b"lone-after;")
            assert held > 0, "the backlog did not outlast the hand-over"
            # both went to the thread, the lone one behind the scoped
            assert lc.egress_writes_sender == 2
            assert rig.snd.pending(conn._slot) >= held
            got = await read_exact(sock, len(b"lone-before;scoped;lone-after;"))
            assert got == b"lone-before;scoped;lone-after;"
            await drain
            # drained: the next lone write is the transport's again
            await settle(lambda: rig.snd.pending(conn._slot) == 0)
            write(conn, b"!")
            assert lc.egress_writes_sender == 2
            assert await read_exact(sock, 1) == b"!"
            busy_sock.close()
            sock.close()

    run(main())


# ---------------------------------------------- 3b: the back-pressure


@native
def test_park_and_hand_back_loses_doubles_and_reorders_nothing():
    """A peer that does not read behind a tiny send buffer: the thread
    parks the connection, the loop takes the bytes back for the
    transport (`egress_parked`), `out_buffered` sees them wherever
    they are, the transport owns the connection until its buffer is
    empty, and then the thread takes its writes again."""
    async def main():
        async with Rig() as rig:
            conn, sock = await rig.pair(rcvbuf=4096, sndbuf=4096)
            lc = rig.broker.profiler.loop
            want = bytearray()
            seq = 0
            for _round in range(8):
                with rig.scope():
                    for _ in range(4):
                        data = stamped(1, seq, 60000)
                        seq += 1
                        want += data
                        write(conn, data)
                await asyncio.sleep(0.01)
            await settle(lambda: lc.egress_parked > 0)
            assert rig.snd.stats()["parks"] >= 1
            # every byte not yet in the kernel is accounted for
            await settle(lambda: rig.snd.pending(conn._slot) == 0)
            held = conn.channel.out_buffered()
            assert held == conn.writer.transport.get_write_buffer_size() > 0
            assert conn._parked
            # while the transport owns it, a scope's write is its too
            n_sender = lc.egress_writes_sender
            with rig.scope():
                data = stamped(1, seq, 1000)
                seq += 1
                want += data
                write(conn, data)
            assert lc.egress_writes_sender == n_sender
            assert conn.channel.out_buffered() == held + 1000
            got = await read_exact(sock, len(want))
            assert got == bytes(want)
            await settle(lambda: conn.channel.out_buffered() == 0)
            # the buffer is empty: back to the thread
            with rig.scope():
                write(conn, b"again")
            assert lc.egress_writes_sender == n_sender + 1
            assert not conn._parked
            assert await read_exact(sock, 5) == b"again"
            sock.close()

    run(main())


def _alarm_names(broker):
    return {a.name for a in broker.alarms.active()}


@pytest.mark.parametrize("sink", [
    pytest.param("sender", marks=native), "transport",
])
def test_stalled_subscriber_raises_and_clears_the_congestion_alarm(
    sink, monkeypatch
):
    """A subscriber that stops reading: `conn_congestion/<clientid>`
    is raised once CONGESTION_BYTES wait for it and cleared under a
    quarter of that, on the sender's path exactly as on the
    transport's (the parent's)."""
    if sink == "transport":
        monkeypatch.setitem(nativelib._libs, "sockwriter", None)
    from mqtt_client import TestClient

    async def main():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
        cfg.mqtt.max_inflight = 4096
        srv = BrokerServer(cfg)
        await srv.start()
        try:
            assert (srv.broker.sender is not None) == (sink == "sender")
            port = srv.listeners[0].port
            sub = TestClient(port, "slow")
            await sub.connect()
            await sub.subscribe("big/#", qos=0)
            # stop reading: the pump is the only reader of the socket
            sub._pump.cancel()
            sub.writer.transport.pause_reading()
            pub = TestClient(port, "fast")
            await pub.connect()
            ch = srv.broker.cm.channel("slow")
            name = "conn_congestion/slow"
            payload = b"x" * 65536
            seen = []
            for i in range(600):
                await pub.publish("big/1", payload, qos=1)
                seen.append(ch.out_buffered())
                if name in _alarm_names(srv.broker):
                    break
            assert name in _alarm_names(srv.broker), max(seen)
            # raised by the first write that left a megabyte waiting
            assert seen[-1] >= Connection.CONGESTION_BYTES
            assert all(b < Connection.CONGESTION_BYTES + 2 * 65536 + 64
                       for b in seen[:-1])
            alarm = [a for a in srv.broker.alarms.active()
                     if a.name == name][0]
            assert alarm.details["buffered"] >= Connection.CONGESTION_BYTES
            # the subscriber reads again: cleared by the first write
            # that finds under a quarter of the threshold waiting
            sub.writer.transport.resume_reading()
            sub._pump = asyncio.ensure_future(sub._read_loop())
            await settle(lambda: ch.out_buffered() == 0, timeout=30.0)
            assert name in _alarm_names(srv.broker)  # no write yet
            await pub.publish("big/1", b"small", qos=1)
            await settle(lambda: name not in _alarm_names(srv.broker))
            await sub.close()
            await pub.close()
        finally:
            await srv.stop()

    run(main(), 120.0)


# --------------------------------------------------------- 3c: close


@native
def test_close_with_bytes_pending_never_writes_to_the_reused_descriptor():
    """A connection closes with bytes still queued on the thread and
    the next accept takes its descriptor number: the newcomer's peer
    reads its own bytes and not one of the other's."""
    async def main():
        async with Rig() as rig:
            busy, busy_sock = await rig.pair()
            old, old_sock = await rig.pair()
            old_fd = old.writer.transport.get_extra_info("socket").fileno()
            # (made now, so that it does not take the freed number)
            new_sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            drain = asyncio.ensure_future(read_exact(busy_sock, 60000))
            with rig.scope():
                for _ in range(60000):
                    rig.snd.add(busy._slot, b"x")
                for _ in range(50):
                    write(old, b"OLD!" * 256)
            assert rig.snd.pending(old._slot) > 0
            old._close("kicked")
            assert old._slot == -1 and old._sender is None
            await asyncio.sleep(0)  # the transport closes its descriptor
            await asyncio.sleep(0)
            new, new_sock = await rig.pair(sock=new_sock)
            new_fd = new.writer.transport.get_extra_info("socket").fileno()
            assert new_fd == old_fd, "the descriptor number was not reused"
            with rig.scope():
                write(new, b"new!" * 100)
            assert await read_exact(new_sock, 400) == b"new!" * 100
            # what was handed over before the close went out in queue
            # order, to the old peer alone, then the end of stream
            got = await read_exact(old_sock, 1 << 30)
            assert got == b"OLD!" * 256 * 50
            await drain
            new._close("done")
            assert await read_exact(new_sock, 1 << 30) == b""
            for s in (busy_sock, old_sock, new_sock):
                s.close()

    run(main())


@native
def test_a_closing_connections_parked_bytes_are_dropped():
    async def main():
        async with Rig() as rig:
            fds = n_fds()
            conn, sock = await rig.pair(rcvbuf=4096, sndbuf=4096)
            with rig.scope():
                for _ in range(20):
                    write(conn, b"z" * 60000)
            # (spun, not awaited: the loop must not take them back yet)
            deadline = time.monotonic() + 10.0
            while not (rig.snd.stats()["parks"] >= 1
                       and rig.snd.stats()["queued_bytes"] == 0):
                assert time.monotonic() < deadline
                time.sleep(0.001)
            conn._close("kicked")
            write(conn, b"after close")  # refused: the writer is closing
            got = await read_exact(sock, 1 << 30)
            assert 0 < len(got) < 20 * 60000 and set(got) == {ord("z")}
            sock.close()
            # the slot is free again and takes the next connection
            await settle(lambda: rig.snd.stats()["slots"] == 0)
            assert n_fds() <= fds
            conn2, sock2 = await rig.pair()
            assert conn2._slot == 0
            sock2.close()

    run(main())


@native
def test_closed_inside_a_scope_its_writes_never_follow_the_marker():
    """A connection that closes inside the flush scope it had written
    in: the scope's batch is handed over after the close marker was
    queued, so those writes are dropped with the cork buffer's, and
    the slot's next owner sends only its own."""
    async def main():
        async with Rig() as rig:
            old, old_sock = await rig.pair()
            other, other_sock = await rig.pair()
            slot = old._slot
            with rig.scope():
                write(old, b"stale" * 100)
                write(other, b"kept")
                old._close("kicked")
                write(other, b"too")
            assert await read_exact(other_sock, 7) == b"kepttoo"
            assert await read_exact(old_sock, 1 << 30) == b""
            await settle(lambda: rig.snd.stats()["slots"] == 1)
            new, new_sock = await rig.pair()
            assert new._slot == slot and rig.snd.pending(slot) == 0
            with rig.scope():
                write(new, b"mine")
            assert await read_exact(new_sock, 4) == b"mine"
            new._close("done")
            assert await read_exact(new_sock, 1 << 30) == b""
            for s in (old_sock, other_sock, new_sock):
                s.close()

    run(main())


# -------------------------------------------------------- 3d: errors


@native
def test_a_failed_send_reaches_the_loop_with_its_errno():
    async def main():
        loop = asyncio.get_running_loop()
        snd = sockwriter.start(loop)

        class Conn:
            failed = None
            parked = b""

            def on_sender_failed(self, err):
                self.failed = err

            def on_sender_parked(self, data):
                self.parked += data

        a, b = socket.socketpair()
        conn = Conn()
        try:
            slot = snd.open(a.fileno(), conn)
            b.close()
            snd.begin()
            snd.add(slot, b"into the void")
            snd.add(slot, b"and again")
            snd.end()
            await settle(lambda: conn.failed is not None)
            assert conn.failed == errno.EPIPE
            assert conn.parked == b"" and snd.pending(slot) == 0
        finally:
            snd.stop()
            a.close()

    run(main())


@native
def test_sender_failure_closes_the_connection_with_peer_reset():
    from mqtt_client import TestClient

    async def main():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        gone = []
        srv.broker.hooks.add(
            "client.disconnected",
            lambda client, reason: gone.append((client.clientid, reason)),
        )
        try:
            sub = TestClient(srv.listeners[0].port, "victim")
            await sub.connect()
            await sub.subscribe("t/#")
            ch = srv.broker.cm.channel("victim")
            conn = ch._send.__self__
            assert conn._sender is srv.broker.sender
            conn.on_sender_failed(errno.ECONNRESET)
            await settle(lambda: gone)
            assert gone == [("victim", "peer_reset")]
            assert conn._slot == -1
            assert await sub.recv() is None  # the stream ended
            await sub.close()
        finally:
            await srv.stop()

    run(main())


# ---------------------------------------------------------- lifetime


@native
def test_stop_with_a_full_queue_joins_the_thread_and_leaks_nothing():
    async def main():
        fds = n_fds()
        await gone()
        async with Rig() as rig:
            await settle(lambda: n_threads() == 1)
            pairs = [await rig.pair(rcvbuf=4096, sndbuf=4096)
                     for _ in range(8)]
            with rig.scope():
                for _ in range(40):
                    for conn, _s in pairs:
                        write(conn, b"q" * 100000)
            # (queued or parked: the thread holds them, nobody reads)
            assert sum(rig.snd.pending(c._slot) for c, _s in pairs) > 0
            t0 = time.monotonic()
            rig.snd.stop()  # nobody reads: it must not wait for them
            assert time.monotonic() - t0 < 5.0
            await gone()
            # stopped under live connections: their writes are the
            # transports' from here on
            write(pairs[0][0], b"tail")
            for _c, s in pairs:
                s.close()
        await asyncio.sleep(0.05)
        assert n_fds() <= fds

    run(main())


@native
def test_every_server_starts_and_stops_its_own_thread():
    async def main():
        fds = []
        for _ in range(3):
            cfg = BrokerConfig()
            cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
            srv = BrokerServer(cfg)
            await srv.start()
            assert srv.broker.sender is not None
            await settle(lambda: n_threads() == 1)
            assert srv.broker.profiler.loop.sender_clock is not None
            await srv.stop()
            assert srv.broker.sender is None
            await gone()
            assert srv.broker.profiler.loop.sender_clock is None
            await asyncio.sleep(0.05)
            fds.append(n_fds())
        # (what the first server opened for good, a library or a log,
        # is not the sender's; the later ones leave nothing behind)
        assert fds[2] <= fds[0], fds

    run(main())


@native
def test_which_connections_take_the_sender():
    """Plain TCP takes a slot; a stream with no transport (the
    WebSocket's) and a TLS transport keep the transport path."""
    async def main():
        async with Rig() as rig:
            conn, sock = await rig.pair()
            assert conn._sender is rig.snd and conn._slot >= 0

            class NoTransport:
                def get_extra_info(self, _k, default=None):
                    return default

                def is_closing(self):
                    return False

            ws = Connection(rig.broker, conn.reader, NoTransport())
            assert ws._sender is None and ws._slot == -1

            class Tls:
                transport = None

                def __init__(self, inner):
                    self.inner = inner
                    self.transport = self

                def get_extra_info(self, k, default=None):
                    if k == "ssl_object":
                        return object()
                    return self.inner.get_extra_info(k, default)

                def get_write_buffer_size(self):
                    return 0

            tls = Connection(rig.broker, conn.reader,
                             Tls(conn.writer.transport))
            assert tls._sender is None and tls._slot == -1
            sock.close()

    run(main())


# ------------------------------------------------------ broker level


@pytest.mark.parametrize("sink", [
    pytest.param("sender", marks=native), "transport",
])
def test_five_thousand_qos1_messages_in_order_across_both_sinks(
    sink, monkeypatch
):
    """One QoS1 publisher, one QoS1 subscriber with a small receive
    maximum (so deliveries leave both in window flushes and as lone
    writes when an ack frees a slot): 5,000 messages, order and count
    exact, every PUBACK back."""
    if sink == "transport":
        monkeypatch.setitem(nativelib._libs, "sockwriter", None)
    from mqtt_client import TestClient

    async def main():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
        cfg.mqtt.max_inflight = 16
        cfg.mqtt.max_mqueue_len = 10000
        srv = BrokerServer(cfg)
        await srv.start()
        try:
            port = srv.listeners[0].port
            sub = TestClient(port, "sub")
            await sub.connect()
            await sub.subscribe("seq/#", qos=1)
            pub = TestClient(port, "pub")
            await pub.connect()
            n = 5000

            async def publish_all():
                for i in range(n):
                    pub.writer.write(C.serialize(C.Publish(
                        topic="seq/1", payload=b"%d" % i, qos=1,
                        packet_id=i % 60000 + 1,
                    ), pub.version))
                    if i % 64 == 63:
                        await pub.writer.drain()
                        await asyncio.sleep(0)

            async def acks():
                got = 0
                while got < n:
                    pkt = await pub.recv(30.0)
                    assert pkt is not None and pkt.type == C.PUBACK
                    got += 1
                return got

            async def receive_all():
                out = []
                while len(out) < n:
                    pkt = await sub.recv_publish(30.0)
                    out.append(int(pkt.payload))
                return out

            _p, n_acks, got = await asyncio.gather(
                publish_all(), acks(), receive_all()
            )
            assert n_acks == n and got == list(range(n))
            lc = srv.broker.profiler.loop
            if sink == "sender":
                assert 0 < lc.egress_writes_sender < lc.egress_writes
                assert srv.broker.sender.stats()["sends"] > 0
            else:
                assert lc.egress_writes_sender == 0
            await sub.close()
            await pub.close()
        finally:
            await srv.stop()

    run(main(), 120.0)


# ------------------------------------- 3e: nothing earlier or later


@native
def test_no_puback_before_the_window_dispatched_and_none_held_past_it():
    """The acks' scope opens after `publish_dispatch` returned and
    closes in the same callback: a PUBACK is handed over only once
    its window dispatched, and when `_uncork_all` returns the scope's
    batch is with the thread, never kept for a later scope."""
    from emqx_tpu.broker.broker import PublishBatcher
    from mqtt_client import TestClient

    async def main():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        try:
            broker, snd = srv.broker, srv.broker.sender
            order = []
            real_dispatch = broker.publish_dispatch
            real_uncork = PublishBatcher._uncork_all

            def dispatch(*a, **kw):
                out = real_dispatch(*a, **kw)
                order.append(("dispatched", snd.stats()["sends"]))
                return out

            def uncork_all(channels, sender=None):
                assert sender is snd
                real_uncork(channels, sender)
                order.append(("acks_submitted", len(snd._slots),
                              snd.in_scope))

            broker.publish_dispatch = dispatch
            broker.batcher._uncork_all = uncork_all
            pub = TestClient(srv.listeners[0].port, "p")
            await pub.connect()
            for i in range(5):
                await pub.publish("a/b", b"x", qos=1)
            kinds = [o[0] for o in order]
            assert kinds == ["dispatched", "acks_submitted"] * 5
            for o in order:
                if o[0] == "acks_submitted":
                    assert o[1:] == (0, False)  # handed over, scope shut
            await pub.close()
        finally:
            await srv.stop()

    run(main())


# ------------------------------------------- racesim: forced schedules


@native
def test_racesim_park_and_hand_back_against_loop_side_writes():
    """Three tasks write to one connection behind a tiny buffer (one
    in flush scopes, two lone) while a fourth reads slowly, under
    forced interleavings: hand-backs land between any two of their
    steps, and the stream still carries every write whole, each
    task's in its own order."""
    parked = []

    def workload():
        async def main():
            async with Rig() as rig:
                conn, sock = await rig.pair(rcvbuf=4096, sndbuf=4096)
                wrote = []
                rec = 3000

                async def writer(who, scoped):
                    for seq in range(40):
                        data = stamped(who, seq, rec)
                        if scoped:
                            with rig.scope():
                                write(conn, data)
                                wrote.append((who, seq))
                        else:
                            write(conn, data)
                            wrote.append((who, seq))
                        await asyncio.sleep(0)

                async def reader():
                    out = bytearray()
                    while len(out) < 120 * rec:
                        out += await read_exact(
                            sock, min(rec, 120 * rec - len(out))
                        )
                        await asyncio.sleep(0)
                    return bytes(out)

                got, *_ = await asyncio.gather(
                    reader(), writer(1, True), writer(2, False),
                    writer(3, False),
                )
                seen = [struct.unpack(">HI", got[k:k + 6])
                        for k in range(0, len(got), rec)]
                assert seen == wrote  # call order IS wire order
                for k, (who, seq) in enumerate(seen):
                    assert got[k * rec:(k + 1) * rec] == stamped(
                        who, seq, rec
                    )
                parked.append(rig.broker.profiler.loop.egress_parked)
                sock.close()
        return main()

    outcomes = run_seeds(workload, seeds=range(6), timeout=60.0)
    failed = [(o.label, repr(o.error)) for o in outcomes if o.failed]
    assert not failed, failed
    assert len(parked) == 6 and sum(parked) > 0, parked


# -------------------------------------------- chaos: conn.sender.send


@native
def test_chaos_sender_seam_error_drop_and_duplicate():
    """`error` on the hand-over is a send that failed on the thread:
    that connection closes with ``peer_reset`` and every other one is
    served; `drop` eats one write (a QoS1 delivery comes again on the
    retry); `duplicate` hands a write over twice (whole packets, so
    the stream stays well-formed)."""
    from mqtt_client import TestClient

    async def main():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        gone = []
        srv.broker.hooks.add(
            "client.disconnected",
            lambda client, reason: gone.append((client.clientid, reason)),
        )
        try:
            port = srv.listeners[0].port
            a, b = TestClient(port, "a"), TestClient(port, "b")
            await a.connect()
            await b.connect()
            await a.subscribe("c/#", qos=1)
            await b.subscribe("c/#", qos=1)
            pub = TestClient(port, "pub")
            await pub.connect()
            peer_a = srv.broker.cm.channel("a").peer

            failpoints.configure("conn.sender.send", "error",
                                 match=peer_a, times=1)
            await pub.publish("c/1", b"one", qos=1)
            assert (await b.recv_publish()).payload == b"one"
            await settle(lambda: gone)
            assert gone == [("a", "peer_reset")]
            assert await a.recv() is None

            peer_b = srv.broker.cm.channel("b").peer
            failpoints.configure("conn.sender.send", "duplicate",
                                 match=peer_b, times=1)
            await pub.publish("c/1", b"two", qos=1)
            first = await b.recv_publish()
            second = await b.recv_publish()
            assert first.payload == second.payload == b"two"
            assert first.packet_id == second.packet_id

            failpoints.configure("conn.sender.send", "drop",
                                 match=peer_b, times=1)
            await pub.publish("c/1", b"three", qos=1)
            with pytest.raises(asyncio.TimeoutError):
                await b.recv_publish(timeout=0.3)
            failpoints.clear()
            srv.broker.cm.lookup("b").retry_interval = 0.0
            srv.broker.cm.channel("b").retry_deliveries()
            again = await b.recv_publish()
            assert again.payload == b"three" and again.dup
            fired = [f[2] for f in failpoints.RECENT_FIRES
                     if f[1] == "conn.sender.send"]
            assert fired[-3:] == ["error", "duplicate", "drop"]
            await b.close()
            await pub.close()
        finally:
            failpoints.clear()
            await srv.stop()

    run(main())
