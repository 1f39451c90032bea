"""The native reader thread (native/sockreader.cpp, ops/sockreader.py):
per connection the loop gets the bytes in the order the socket gave
them, across batches; the end of a stream comes after the stream's
last bytes, a reset as an errno; a paused slot is not read, its bytes
wait in the kernel until it is re-armed; a close is in queue order, so
a descriptor number (and a slot) that is handed out again is never fed
the old connection's bytes; a full arena holds the thread back and
loses nothing; `stop()` joins the thread and leaves no descriptor.

The rig drives the loop's side, `SockReader`, over loopback TCP with
stand-in connections that record what they are handed; the broker
level is `tests/test_connection_direct.py`'s.  Native cases skip when
the library is absent; the transport path is injected the way the
other native libraries' Python twins are."""

import asyncio
import errno
import fcntl
import os
import socket
import struct
import sys
import termios
import threading
import time

import pytest

from emqx_tpu.broker.connection import Connection
from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.codec import mqtt as C
from emqx_tpu.config import BrokerConfig, ListenerConfig
from emqx_tpu.observability import LoopClock
from emqx_tpu.ops import nativelib, sockreader

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))

from test_benchmark_rehearsal import on_cpu  # noqa: E402,F401

native = pytest.mark.skipif(
    sockreader.load() is None, reason="native sockreader not built"
)


def run(coro, timeout=60.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def n_fds():
    return len(os.listdir("/proc/self/fd"))


def n_threads():
    """The process's reader threads (they name themselves)."""
    n = 0
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/comm") as f:
                n += f.read().strip() == "sockreader"
        except OSError:
            pass  # a thread that ended meanwhile
    return n


async def settle(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition never held"
        await asyncio.sleep(0.002)


def spin(cond, timeout=10.0):
    """Wait on the loop thread without letting the loop run: what the
    reader thread does meanwhile stays in its batch, untaken."""
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.001)


class Conn:
    """A stand-in for `Connection`: what the reader hands it, in
    order; ``pause_after`` pauses its slot from inside the read, as a
    limiter or a congested lane does."""

    def __init__(self, rdr=None, pause_after=False):
        self.rdr = rdr
        self.slot = -1
        self.pause_after = pause_after
        self.events = []  # ("data", bytes) | ("eof",) | ("failed", errno)

    @property
    def data(self):
        return b"".join(e[1] for e in self.events if e[0] == "data")

    def data_received(self, data):
        self.events.append(("data", data))
        if self.pause_after:
            self.rdr.pause(self.slot)

    def on_reader_eof(self):
        self.events.append(("eof",))

    def on_reader_failed(self, err):
        self.events.append(("failed", err))


class Rig:
    """A reader on the running loop and loopback pairs: the near side
    is the slot's descriptor (the broker's socket), the far side a
    plain socket the test writes as it pleases."""

    def __init__(self, clock=None):
        self.clock = clock
        self.pairs = []

    async def __aenter__(self):
        self.rdr = sockreader.start(asyncio.get_running_loop(), self.clock)
        assert self.rdr is not None
        self.lsock = socket.create_server(("127.0.0.1", 0))
        return self

    async def __aexit__(self, *exc):
        self.rdr.stop()
        for near, far in self.pairs:
            near.close()
            far.close()
        self.lsock.close()

    def pair(self, far=None):
        if far is None:
            far = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        far.connect(self.lsock.getsockname())
        near, _ = self.lsock.accept()
        self.pairs.append((near, far))
        return near, far

    def open(self, near, conn):
        conn.rdr = self.rdr
        conn.slot = self.rdr.open(near.fileno(), conn)
        assert conn.slot >= 0
        return conn


# ------------------------------------------------------- the library


@native
def test_library_loads_and_counts_its_own_clock():
    async def main():
        lc = LoopClock()
        async with Rig(lc) as rig:
            assert lc.reader_clock is not None
            near, far = rig.pair()
            conn = rig.open(near, Conn())
            assert rig.rdr.reading(conn.slot)
            far.sendall(b"hello")
            await settle(lambda: conn.data == b"hello")
            st = rig.rdr.stats()
            assert st["recvs"] >= 1 and st["recv_ns"] > 0
            assert st["slots"] == 1 and st["records"] == 0
            recv_s, recvs, wakes = rig.rdr.clock()
            assert recvs == st["recvs"] and recv_s == st["recv_ns"] * 1e-9
            assert wakes == rig.rdr.wakes >= 1
            grown = lc.take_reader()
            assert grown == (recv_s, recvs, wakes)
            rig.rdr.close(conn.slot)
            assert not rig.rdr.reading(conn.slot)
            await settle(lambda: rig.rdr.stats()["slots"] == 0)
        assert lc.reader_clock is None
        # stopped: every call is a no-op, nothing raises
        rdr = rig.rdr
        assert rdr.open(0, Conn()) == -1 and not rdr.reading(0)
        rdr.pause(0)
        rdr.resume(0)
        rdr.close(0)
        rdr.stop()

    run(main())


def test_absent_library_leaves_every_connection_on_data_received(
    monkeypatch,
):
    monkeypatch.setitem(nativelib._libs, "sockreader", None)
    handed = []
    real = Connection.data_received

    def data_received(conn, data):
        handed.append(conn._reader is None and conn.writer.is_reading())
        return real(conn, data)

    monkeypatch.setattr(Connection, "data_received", data_received)

    async def main():
        assert sockreader.start(asyncio.get_running_loop()) is None
        cfg = BrokerConfig()
        cfg.engine.use_device = False
        cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        try:
            assert srv.broker.reader is None
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
            assert lc.ingress_reads_direct == lc.ingress_reads > 20
            assert lc.ingress_reads_native == 0
            await sub.close()
            await pub.close()
        finally:
            await srv.stop()

    run(main())
    assert len(handed) > 20 and all(handed)


# ------------------------------------------------------ the order


@native
@pytest.mark.parametrize("size", [1, 700, 9300, 65536, 300000])
def test_per_slot_order_across_batches(size):
    """Eight peers each send a numbered stream in pieces; each slot's
    bytes come back whole and in order, over many batches."""
    async def main():
        async with Rig() as rig:
            conns, fars = [], []
            for _ in range(8):
                near, far = rig.pair()
                conns.append(rig.open(near, Conn()))
                fars.append(far)
            streams = [
                bytes((k * 37 + i) & 0xFF for i in range(size * 6))
                for k in range(8)
            ]

            def send(far, stream):
                for at in range(0, len(stream), size):
                    far.sendall(stream[at:at + size])
                    time.sleep(0.0005)

            senders = [threading.Thread(target=send, args=(f, s))
                       for f, s in zip(fars, streams)]
            for t in senders:
                t.start()
            await settle(lambda: all(
                len(c.data) == len(s) for c, s in zip(conns, streams)
            ), 30.0)
            for t in senders:
                t.join()
            for c, s in zip(conns, streams):
                assert c.data == s
                assert all(e[0] == "data" for e in c.events)
            assert sum(len(c.events) for c in conns) >= 8 * 2
            assert rig.rdr.wakes > 1

    run(main())


@native
@pytest.mark.parametrize("how", ["shutdown", "close"])
def test_the_end_of_a_stream_comes_after_its_last_bytes(how):
    async def main():
        async with Rig() as rig:
            near, far = rig.pair()
            conn = rig.open(near, Conn())
            far.sendall(b"a" * 5000)
            far.sendall(b"b" * 5000)
            if how == "shutdown":
                far.shutdown(socket.SHUT_WR)
            else:
                far.close()
            await settle(lambda: conn.events and conn.events[-1] == ("eof",))
            assert conn.data == b"a" * 5000 + b"b" * 5000
            assert [e[0] for e in conn.events].count("eof") == 1
            # not armed again after the end: nothing more comes
            await asyncio.sleep(0.05)
            assert conn.events[-1] == ("eof",)

    run(main())


@native
def test_a_reset_peer_is_an_errno_record():
    async def main():
        async with Rig() as rig:
            near, far = rig.pair()
            conn = rig.open(near, Conn())
            far.sendall(b"x")
            await settle(lambda: conn.data == b"x")
            far.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                           b"\x01\x00\x00\x00\x00\x00\x00\x00")
            far.close()  # an RST, not a FIN
            await settle(lambda: conn.events[-1][0] != "data")
            assert conn.events[-1] == ("failed", errno.ECONNRESET)

    run(main())


# ------------------------------------------------- pause and re-arm


@native
def test_a_paused_slot_is_not_read_and_its_bytes_wait_in_the_kernel():
    async def main():
        async with Rig() as rig:
            near, far = rig.pair()
            conn = rig.open(near, Conn())
            rig.rdr.pause(conn.slot)
            assert not rig.rdr.reading(conn.slot)
            recvs = rig.rdr.stats()["recvs"]
            far.sendall(b"held" * 100)
            await asyncio.sleep(0.1)
            assert conn.events == []
            assert rig.rdr.stats()["recvs"] == recvs
            # the bytes are the kernel's, on the broker's socket
            assert near.recv(4096, socket.MSG_PEEK | socket.MSG_DONTWAIT) \
                == b"held" * 100
            rig.rdr.resume(conn.slot)
            assert rig.rdr.reading(conn.slot)
            await settle(lambda: conn.data == b"held" * 100)

    run(main())


@native
def test_a_pause_asked_for_by_a_read_holds_before_the_next_recv():
    """The read's own handling pauses the slot (a limiter, a congested
    lane): no further ``recv`` of it, however long it waits, until the
    resume; then the rest, in order."""
    async def main():
        async with Rig() as rig:
            near, far = rig.pair()
            conn = rig.open(near, Conn(pause_after=True))
            far.sendall(b"first")
            await settle(lambda: conn.data == b"first")
            recvs = rig.rdr.stats()["recvs"]
            for i in range(20):
                far.sendall(b"-%d" % i)
            await asyncio.sleep(0.1)
            assert conn.data == b"first" and len(conn.events) == 1
            assert rig.rdr.stats()["recvs"] == recvs
            conn.pause_after = False
            rig.rdr.resume(conn.slot)
            want = b"first" + b"".join(b"-%d" % i for i in range(20))
            await settle(lambda: conn.data == want)

    run(main())


@native
def test_one_read_a_slot_until_the_loop_rearms_it():
    """With the loop held, a slot whose peer keeps writing is read
    once: the next read waits for the re-arm behind the taken batch."""
    async def main():
        async with Rig() as rig:
            near, far = rig.pair()
            conn = rig.open(near, Conn())
            far.sendall(b"1")
            spin(lambda: rig.rdr.stats()["records"] == 1)
            recvs = rig.rdr.stats()["recvs"]
            far.sendall(b"2")
            time.sleep(0.05)  # (the loop does not run: no re-arm)
            assert rig.rdr.stats()["recvs"] == recvs
            assert rig.rdr.stats()["records"] == 1
            await settle(lambda: conn.data == b"12")
            assert [e[1] for e in conn.events] == [b"1", b"2"]

    run(main())


@native
def test_a_new_slot_is_armed_behind_the_rearms_asked_for_before_it():
    """An older connection's bytes came first, while its re-arm waits
    in the thread's queue (the thread is held on a full arena); a
    connection opened after that is read after it, not ahead of it: a
    client's DISCONNECT is handled before its reconnect's CONNECT."""
    async def main():
        async with Rig() as rig:
            order = []

            class Seen(Conn):
                def data_received(self, data):
                    order.append(self.name)
                    super().data_received(data)

            old_near, old_far = rig.pair()
            old = rig.open(old_near, Seen())
            old.name = "old"
            # paused, and its bytes come: the thread takes the event
            # and leaves the slot unarmed, the bytes in the kernel
            rig.rdr.pause(old.slot)
            old_far.sendall(b"DISCONNECT")
            await asyncio.sleep(0.05)
            assert old.events == []
            # hold the thread on a full arena, the loop taking nothing
            fars, nears = [], []
            for _ in range(32):
                far = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                far.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 20)
                near, far = rig.pair(far)
                near.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 20)
                fars.append(far)
                nears.append(near)
            streams = [os.urandom(1 << 20) for _ in range(32)]
            senders = [threading.Thread(target=f.sendall, args=(s,))
                       for f, s in zip(fars, streams)]
            for t in senders:
                t.start()
            spin(lambda: min(map(pending, nears)) >= 256 << 10)
            flood = [rig.open(near, Conn()) for near in nears]
            spin(lambda: rig.rdr.stats()["full_waits"] > 0)
            # the old slot's re-arm is queued; then the new connection
            # opens and its bytes come
            rig.rdr.resume(old.slot)
            new_near, new_far = rig.pair()
            new = rig.open(new_near, Seen())
            new.name = "new"
            new_far.sendall(b"CONNECT")
            time.sleep(0.05)
            await settle(lambda: old.data == b"DISCONNECT"
                         and new.data == b"CONNECT" and all(
                             len(c.data) == len(s)
                             for c, s in zip(flood, streams)), 30.0)
            for t in senders:
                t.join()
            assert order.index("old") < order.index("new")

    run(main())


# ----------------------------------------------------------- close


@native
def test_a_close_in_queue_order_never_feeds_a_reused_descriptor():
    """A connection's read sits in the batch, untaken, when it closes;
    the descriptor number and the slot go to a newcomer: the newcomer
    gets its own bytes and not one of the other's."""
    async def main():
        async with Rig() as rig:
            old_near, old_far = rig.pair()
            old = rig.open(old_near, Conn())
            old_fd = old_near.fileno()
            # (made now, so that it does not take the freed number)
            new_far = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            old_far.sendall(b"OLD!" * 64)
            # (the loop is held: the read stays in the batch)
            spin(lambda: rig.rdr.stats()["records"] == 1)
            rig.rdr.close(old.slot)
            spin(lambda: rig.rdr.stats()["slots"] == 0)
            rig.pairs.remove((old_near, old_far))
            old_near.close()
            new_near, new_far = rig.pair(new_far)
            assert new_near.fileno() == old_fd, "the number was not reused"
            new = rig.open(new_near, Conn())
            assert new.slot == old.slot, "the slot was not reused"
            new_far.sendall(b"new!")
            old_far.sendall(b"late")  # to a socket nobody reads now
            await settle(lambda: new.data == b"new!")
            await asyncio.sleep(0.05)
            assert new.data == b"new!" and old.events == []
            old_far.close()

    run(main())


# ----------------------------------------------------- back-pressure


def pending(sock):
    """Bytes in the kernel's receive buffer of ``sock``."""
    got = fcntl.ioctl(sock.fileno(), termios.FIONREAD, b"\0" * 4)
    return struct.unpack("i", got)[0]


@native
def test_a_full_arena_holds_the_thread_back_and_loses_nothing():
    """Thirty-two peers have 1 MiB each in the kernel when their slots
    open, and the loop takes nothing: a full read a slot fills the
    arena in sixteen, the thread waits for the loop, and every byte
    comes through in order once the loop runs."""
    async def main():
        async with Rig() as rig:
            fars, nears = [], []
            for _ in range(32):
                far = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                far.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 20)
                near, far = rig.pair(far)
                near.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 20)
                fars.append(far)
                nears.append(near)
            streams = [os.urandom(1 << 20) for _ in range(32)]
            senders = [threading.Thread(target=f.sendall, args=(s,))
                       for f, s in zip(fars, streams)]
            for t in senders:
                t.start()
            spin(lambda: min(map(pending, nears)) >= 256 << 10)
            conns = [rig.open(near, Conn()) for near in nears]
            # the loop is held until the thread has waited for room
            spin(lambda: rig.rdr.stats()["full_waits"] > 0)
            assert rig.rdr.stats()["records"] < 32
            await settle(lambda: all(
                len(c.data) == len(s) for c, s in zip(conns, streams)
            ), 30.0)
            for t in senders:
                t.join()
            assert [c.data for c in conns] == streams

    run(main())


# ---------------------------------------------------------- lifetime


@native
def test_stop_with_reads_untaken_joins_the_thread_and_leaks_nothing():
    async def main():
        fds = n_fds()
        await settle(lambda: n_threads() == 0, 2.0)
        async with Rig() as rig:
            await settle(lambda: n_threads() == 1)
            conns = []
            for _ in range(8):
                near, far = rig.pair()
                conns.append(rig.open(near, Conn()))
                far.sendall(b"z" * 100000)
            spin(lambda: rig.rdr.stats()["records"] > 0)
            t0 = time.monotonic()
            rig.rdr.stop()
            assert time.monotonic() - t0 < 5.0
            await settle(lambda: n_threads() == 0, 2.0)
        await asyncio.sleep(0.05)
        assert n_fds() <= fds

    run(main())


@native
def test_every_server_starts_and_stops_its_own_thread():
    async def main():
        fds = []
        for _ in range(3):
            cfg = BrokerConfig()
            cfg.engine.use_device = False
            cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
            srv = BrokerServer(cfg)
            await srv.start()
            assert srv.broker.reader is not None
            await settle(lambda: n_threads() == 1)
            assert srv.broker.profiler.loop.reader_clock is not None
            await srv.stop()
            assert srv.broker.reader is None
            await settle(lambda: n_threads() == 0, 2.0)
            assert srv.broker.profiler.loop.reader_clock is None
            await asyncio.sleep(0.05)
            fds.append(n_fds())
        assert fds[2] <= fds[0], fds

    run(main())


@native
def test_a_served_window_record_carries_the_reader_fields():
    async def main():
        cfg = BrokerConfig()
        cfg.engine.use_device = False
        cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        try:
            from mqtt_client import TestClient

            port = srv.listeners[0].port
            sub, pub = TestClient(port, "sub"), TestClient(port, "pub")
            for c in (sub, pub):
                await c.connect()
            await sub.subscribe("r/#", qos=1)
            for i in range(20):
                await pub.publish("r/%d" % i, b"x", qos=1)
                await sub.recv_publish()
            for c in (sub, pub):
                await c.disconnect()
            return srv.broker.profiler.windows(limit=256)
        finally:
            await srv.stop()

    ring = asyncio.run(main())
    assert ring and all(
        {"reader_recv_us", "reader_recvs", "reader_wakes",
         "loop_ingress_reads_native"} <= set(r) for r in ring
    )
    reads = sum(r["loop_ingress_reads"] for r in ring)
    assert sum(r["loop_ingress_reads_native"] for r in ring) == reads >= 20
    assert sum(r["reader_recvs"] for r in ring) >= 20
    assert sum(r["reader_wakes"] for r in ring) >= 20
    assert sum(r["reader_recv_us"] for r in ring) > 0


def test_which_connections_take_the_reader(monkeypatch):
    """Plain TCP under a transport takes a slot; a stream read by the
    coroutine (a WebSocket's) never does, whatever its socket."""
    if sockreader.load() is None:
        pytest.skip("native sockreader not built")

    async def main():
        cfg = BrokerConfig()
        cfg.engine.use_device = False
        cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        try:
            lst = srv.listeners[0]
            sock = socket.create_connection(("127.0.0.1", lst.port))
            sock.sendall(C.serialize(C.Connect(
                client_id="who", proto_ver=C.MQTT_V5), C.MQTT_V5))
            await settle(lambda: srv.broker.cm.channel("who") is not None)
            conn = srv.broker.cm.channel("who")._close.__self__
            assert conn._reader is srv.broker.reader and conn._rslot >= 0
            assert not conn.writer.is_reading() and conn.is_reading()
            # the same kind of socket, read through a stream
            r, w = await asyncio.open_connection("127.0.0.1", lst.port)
            streamed = Connection(srv.broker, r, w)
            assert streamed._reader is None and streamed._rslot == -1
            streamed._release_slot()  # (its sender slot's dup)
            w.close()
            sock.close()
            await settle(lambda: not lst._conns)
            assert conn._reader is None and conn._rslot == -1
        finally:
            await srv.stop()

    run(main())


# ------------------------------------------------- the benchmark's view


def _rehearse(cell, on_cpu):
    import test_benchmark_rehearsal as fleet
    import test_benchmark_rehearsal_fanout as fanout
    import test_benchmark_rehearsal_p2p as p2p

    if cell == FLEET:
        return fleet.run_cell(on_cpu, cell, seconds="3", trace="1")
    mod = {fanout.CELL: fanout, p2p.CELL: p2p}[cell]
    return mod.run_cell(on_cpu, seconds="3", trace="1")


FLEET = "fleet-1m-rules.flood-qos1"


@native
@pytest.mark.parametrize("cell", [
    FLEET, "exact-1k-fanout.flood-qos1", "p2p-1k.flood-qos1",
])
def test_flood_rehearsal_reports_the_reader_metrics(cell, on_cpu, capsys):
    """A traced CPU rehearsal of each cell that lists them prints both
    of the reader's metrics: every read native, the thread's share of a
    core above zero (not a device number)."""
    from test_benchmark_rehearsal import last_line

    assert _rehearse(cell, on_cpu) == 0
    res, _ = last_line(capsys)
    assert res["correct"] is True
    m = res["metrics"]
    native_pct = m["ingress_native_read_pct.flood"]
    assert native_pct["unit"] == "%" and native_pct["value"] == 100.0
    assert m["ingress_direct_read_pct.flood"]["value"] == 100.0
    assert 0 < m["reader_busy_pct.flood"]["value"] < 100
