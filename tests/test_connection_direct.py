"""The read paths of `Connection` on real loopback sockets: the direct
path, where a plain-TCP socket's ``recv`` is the native reader
thread's (``native``) or, the library absent, the transport's own
callback (``callback``, which TLS always takes), against the `run`
coroutine (what a WebSocket's stream is read by, here over the same
plain sockets).  The same byte stream gives the same packets, the same
bytes back and the same counts on every path; every close reason ends
a connection once; every await of the coroutine is reading paused and
resumed on both direct paths, each case once a path."""

import asyncio
import random
import socket
import ssl

import pytest

from emqx_tpu.broker import connection as connection_mod
from emqx_tpu.broker.channel import Channel
from emqx_tpu.broker.connection import Connection, ReadTurn
from emqx_tpu.broker.listener import BrokerServer, Listener
from emqx_tpu.codec import mqtt as C
from emqx_tpu.config import BrokerConfig, ListenerConfig
from emqx_tpu.hooks import with_async
from emqx_tpu.observability import LoopClock
from emqx_tpu.ops import nativelib, sockreader
from mqtt_client import TestClient
from test_listeners import WsTestClient, _make_cert
from tools.racesim import run_seeds

# the direct path's two readers, and the coroutine
DIRECT = ("native", "callback")
PATHS = DIRECT + ("coroutine",)


def run(coro):
    return asyncio.run(coro)


class Served:
    """A `BrokerServer` on one listener (plain TCP, or what `listen`
    says) that takes `path`, publishes handled inside the read that
    brought them (no batcher, no device), with every
    `Channel.handle_in` and `Channel.connection_lost` recorded.  For
    the callback the reader library is absent, as where it does not
    build; for the coroutine no listener type is direct: the socket is
    read through a reader / writer pair, as a WebSocket's stream is."""

    def __init__(self, monkeypatch, path, batcher=False, listen=None,
                 **mqtt):
        if path == "native" and sockreader.load() is None:
            pytest.skip("native sockreader not built")
        if path == "callback":
            monkeypatch.setitem(nativelib._libs, "sockreader", None)
        if path == "coroutine":
            monkeypatch.setattr(Listener, "DIRECT", ())
        cfg = BrokerConfig()
        cfg.listeners = [
            ListenerConfig(bind="127.0.0.1", port=0, **(listen or {}))
        ]
        cfg.engine.batch_publish = batcher
        cfg.engine.use_device = False
        for k, v in mqtt.items():
            setattr(cfg.mqtt, k, v)
        self.srv = BrokerServer(cfg)
        self.broker = self.srv.broker
        self.handled = []  # (type, what tells two packets apart)
        self.lost = []  # (channel, reason)
        handle_in, lost = Channel.handle_in, Channel.connection_lost

        def seen_in(ch, pkt):
            self.handled.append((
                pkt.type,
                getattr(pkt, "packet_ids", None)
                or getattr(pkt, "packet_id", None),
                getattr(pkt, "payload", None),
            ))
            return handle_in(ch, pkt)

        def seen_lost(ch, reason="closed"):
            self.lost.append((ch, reason))
            return lost(ch, reason)

        monkeypatch.setattr(Channel, "handle_in", seen_in)
        monkeypatch.setattr(Channel, "connection_lost", seen_lost)

    async def __aenter__(self):
        await self.srv.start()
        self.listener = self.srv.listeners[0]
        self.port = self.listener.port
        return self

    async def __aexit__(self, *exc):
        await self.srv.stop()

    def received(self):
        return self.broker.metrics.all().get("bytes.received", 0)

    def conn_of(self, clientid) -> Connection:
        # (the channel's `close` is its connection's bound method)
        return self.broker.cm.channel(clientid)._close.__self__


async def settle(cond, timeout=10.0):
    end = asyncio.get_running_loop().time() + timeout
    while not cond():
        assert asyncio.get_running_loop().time() < end, "never settled"
        await asyncio.sleep(0.002)


# ------------------------------------------------ the same byte stream


def the_stream():
    """One client's life as frames: it subscribes to what it publishes,
    so the broker's deliveries (packet ids 1.. of a fresh session) come
    back to be acknowledged, a run of PUBACKs among them."""
    v = C.MQTT_V5
    frames = [
        C.Connect(client_id="same", proto_ver=v, clean_start=True,
                  keepalive=60),
        C.Subscribe(packet_id=1, subscriptions=[
            C.Subscription(topic_filter="t/#", qos=2)]),
        C.Publish(topic="t/0", payload=b"q0", qos=0),
    ]
    for i in range(1, 5):
        frames.append(C.Publish(topic="t/1", payload=b"q1-%d" % i,
                                qos=1, packet_id=10 + i))
    frames.append(C.Publish(topic="t/2", payload=b"q2", qos=2,
                            packet_id=20))
    frames.append(C.Pubrel(packet_id=20))
    # the four QoS1 deliveries, acknowledged in one run; the QoS2 one
    frames += [C.Puback(packet_id=i) for i in range(1, 5)]
    frames.append(C.Pubrec(packet_id=5))
    frames.append(C.Pubcomp(packet_id=5))
    frames.append(C.Pingreq())
    frames.append(C.Publish(topic="t/1", payload=b"last", qos=1,
                            packet_id=30))
    frames.append(C.Puback(packet_id=6))
    frames.append(C.Disconnect(reason_code=0))
    return [C.serialize(f, v) for f in frames]


def cuts_of(case, frames):
    """Where the stream is cut into reads."""
    total = sum(len(f) for f in frames)
    bounds, at = [], 0
    for f in frames[:-1]:
        at += len(f)
        bounds.append(at)
    if case == "one_read":
        return []
    if case == "a_frame_a_read":
        return bounds
    if case == "every_byte":
        return list(range(1, total))
    rng = random.Random(case)
    # frames split across reads and several frames a read
    inside = rng.sample(range(1, total), rng.randint(3, 24))
    return sorted(set(inside + rng.sample(bounds, rng.randint(0, 6))))


async def play(monkeypatch, path, frames, cuts):
    """Send the stream cut at `cuts`, each piece once the broker has
    taken the one before (one read a piece, on either path)."""
    stream = b"".join(frames)
    async with Served(monkeypatch, path) as s:
        assert s.listener._direct == (path != "coroutine")
        sock = socket.create_connection(("127.0.0.1", s.port))
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        sock.setblocking(False)
        loop = asyncio.get_running_loop()
        sent = 0
        for end in cuts + [len(stream)]:
            await loop.sock_sendall(sock, stream[sent:end])
            sent = end
            await settle(lambda: s.received() == sent)
        back = bytearray()
        while True:
            got = await asyncio.wait_for(loop.sock_recv(sock, 65536), 10)
            if not got:
                break
            back += got
        sock.close()
        await settle(lambda: not s.listener._conns)
        lc = s.broker.profiler.loop
        # (which sink took a write turns on whether the sender thread
        # had sent the one before: its timing, not the read path's)
        counts = {
            f: getattr(lc, f) for f in LoopClock.FIELDS
            if not f.endswith("_s") and f not in (
                "egress_writes_sender", "egress_parked",
            )
        }
        return s.handled, bytes(back), counts, [r for _ch, r in s.lost]


@pytest.mark.parametrize("direct", DIRECT)
@pytest.mark.parametrize(
    "case", ["one_read", "a_frame_a_read", "every_byte"] + list(range(12))
)
def test_the_same_stream_reads_the_same_on_both_paths(case, direct,
                                                      monkeypatch):
    frames = the_stream()
    cuts = cuts_of(case, frames)
    got = {}
    for path in (direct, "coroutine"):
        with monkeypatch.context() as mp:
            got[path] = run(play(mp, path, frames, cuts))
    handled, back, counts, lost = got[direct]
    assert len(handled) >= 12 and back  # the stream did something
    assert counts["ingress_reads"] == len(cuts) + 1
    assert counts.pop("ingress_reads_direct") == counts["ingress_reads"]
    assert counts.pop("ingress_reads_native") == (
        counts["ingress_reads"] if direct == "native" else 0
    )
    handled_c, back_c, counts_c, lost_c = got["coroutine"]
    assert counts_c.pop("ingress_reads_direct") == 0
    assert counts_c.pop("ingress_reads_native") == 0
    assert handled == handled_c
    assert back == back_c
    assert counts == counts_c
    assert lost == lost_c == ["closed"]
    # (the run of four PUBACKs crossed as one `AckRun` wherever a
    # read held all of it)
    assert counts["ingress_acks"] == 8


async def one_shot(monkeypatch, path, listen, ctx):
    """A publisher with a will sends PUBLISH and DISCONNECT in one
    write and closes at once, so the end of the connection (an EOF, a
    TLS ``close_notify``) reaches the broker in the loop turn of its
    last read."""
    v = C.MQTT_V5
    async with Served(monkeypatch, path, listen=listen) as s:
        sub_r, sub_w = await asyncio.open_connection(
            "localhost", s.port, ssl=ctx
        )
        sub_w.write(C.serialize(C.Connect(
            client_id="shot-sub", proto_ver=v), v) + C.serialize(
                C.Subscribe(packet_id=1, subscriptions=[
                    C.Subscription(topic_filter="shot/#")]), v))
        _r, w = await asyncio.open_connection("localhost", s.port, ssl=ctx)
        w.write(C.serialize(C.Connect(
            client_id="shot", proto_ver=v,
            will=C.Will(topic="shot/will", payload=b"gone")), v))
        await settle(lambda: s.broker.cm.channel("shot") is not None
                     and len(s.handled) == 3)
        w.write(
            C.serialize(C.Publish(topic="shot/x", payload=b"once"), v)
            + C.serialize(C.Disconnect(reason_code=0), v)
        )
        w.close()
        await settle(lambda: len(s.lost) == 1)
        await asyncio.sleep(0.05)
        sub_w.write(C.serialize(C.Pingreq(), v))
        got, parser = [], C.StreamParser(version=v)
        while not got or got[-1].type != C.PINGRESP:
            data = await asyncio.wait_for(sub_r.read(65536), 10)
            assert data
            got += parser.feed(data)
        sub_w.close()
        return (
            [h for h in s.handled if h[0] in (C.PUBLISH, C.DISCONNECT)],
            [p.payload for p in got if p.type == C.PUBLISH],
            [r for _ch, r in s.lost],
        )


@pytest.mark.parametrize("direct", DIRECT)
@pytest.mark.parametrize("kind", ["tcp", "ssl"])
def test_a_read_is_handled_before_the_close_that_came_with_it(
    kind, direct, tmp_path, monkeypatch
):
    listen, ctx = {}, None
    if kind == "ssl":
        certfile, keyfile = _make_cert(tmp_path)
        listen = {"type": "ssl", "certfile": certfile, "keyfile": keyfile}
        ctx = ssl.create_default_context(cafile=certfile)
    got = {}
    for path in (direct, "coroutine"):
        with monkeypatch.context() as mp:
            got[path] = run(one_shot(mp, path, listen, ctx))
    handled, delivered, lost = got[direct]
    assert [h[0] for h in handled] == [C.PUBLISH, C.DISCONNECT]
    # the publish arrived, and no will: the broker saw the DISCONNECT
    assert delivered == [b"once"]
    assert lost == ["closed"]
    assert got["coroutine"] == got[direct]


async def eof_after_data(monkeypatch, path):
    """A publisher with a will sends a PUBLISH and closes with no
    DISCONNECT: its last read, then the end of its stream."""
    v = C.MQTT_V5
    async with Served(monkeypatch, path) as s:
        sub = TestClient(s.port, "eof-sub")
        await sub.connect()
        await sub.subscribe("eof/#", qos=0)
        _r, w = await asyncio.open_connection("127.0.0.1", s.port)
        w.write(C.serialize(C.Connect(
            client_id="eof", proto_ver=v,
            will=C.Will(topic="eof/will", payload=b"gone")), v))
        await settle(lambda: s.broker.cm.channel("eof") is not None)
        w.write(C.serialize(C.Publish(topic="eof/x", payload=b"last"), v))
        w.close()
        got = [(await sub.expect(C.PUBLISH)).payload for _ in range(2)]
        await settle(lambda: len(s.lost) == 1)
        await sub.disconnect()
        return got, [r for _ch, r in s.lost][:1]


@pytest.mark.parametrize("path", PATHS)
def test_an_end_of_stream_is_handled_after_the_last_read(path,
                                                         monkeypatch):
    """The bytes first, then the end: the publish is delivered ahead of
    the will the unannounced close sends, on every path."""
    got, lost = run(eof_after_data(monkeypatch, path))
    assert got == [b"last", b"gone"]
    assert lost == ["closed"]


def metric(name):
    """A per-layer metric as the benchmark reads it from a ring."""
    import importlib.util
    import json
    import os

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "metrics", name + ".json")) as f:
        how = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "reader_" + how["reader"],
        os.path.join(bench, "readers", how["reader"] + ".py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return lambda ring: mod.read({"ring": ring, "window_s": 1.0},
                                 **how["args"])


@pytest.mark.parametrize("kind,share", [("tcp", 100.0), ("ssl", 0.0)])
def test_a_tls_listener_keeps_data_received_and_reads_no_native_read(
    kind, share, tmp_path, monkeypatch
):
    """With the reader thread running, a TLS connection's bytes still
    come through its transport's ``data_received`` (``sslproto`` owns
    them) and none of its reads is native; a plain-TCP one's are all
    the thread's: ``ingress_native_read_pct.flood`` reads 100.0 and
    0.0, ``ingress_direct_read_pct.flood`` 100.0 on both."""
    if sockreader.load() is None:
        pytest.skip("native sockreader not built")
    listen, ctx = {}, None
    if kind == "ssl":
        certfile, keyfile = _make_cert(tmp_path)
        listen = {"type": "ssl", "certfile": certfile, "keyfile": keyfile}
        ctx = ssl.create_default_context(cafile=certfile)
    from_transport = []
    real = Connection.data_received

    def data_received(conn, data):
        # (the reader thread's hand-off is `SockReader._on_event`)
        import sys

        caller = sys._getframe(1).f_code.co_name
        from_transport.append(caller != "_on_event")
        return real(conn, data)

    async def main():
        v = C.MQTT_V5
        async with Served(monkeypatch, "native", listen=listen) as s:
            assert s.broker.reader is not None
            monkeypatch.setattr(Connection, "data_received", data_received)
            sub_r, sub_w = await asyncio.open_connection(
                "localhost", s.port, ssl=ctx
            )
            sub_w.write(C.serialize(C.Connect(
                client_id="m-sub", proto_ver=v), v) + C.serialize(
                    C.Subscribe(packet_id=1, subscriptions=[
                        C.Subscription(topic_filter="m/#", qos=1)]), v))
            _r, w = await asyncio.open_connection(
                "localhost", s.port, ssl=ctx
            )
            w.write(C.serialize(C.Connect(client_id="m-pub",
                                          proto_ver=v), v))
            await settle(lambda: s.broker.cm.channel("m-pub") is not None
                         and s.broker.cm.channel("m-sub") is not None)
            conn = s.conn_of("m-pub")
            assert (conn._reader is None) == (kind == "ssl")
            assert conn.writer.is_reading() == (kind == "ssl")
            for i in range(20):
                w.write(C.serialize(C.Publish(
                    topic="m/%d" % i, payload=b"x", qos=1,
                    packet_id=i + 1), v))
                await asyncio.sleep(0.002)
            got, parser = 0, C.StreamParser(version=v)
            while got < 20:
                data = await asyncio.wait_for(sub_r.read(65536), 10)
                assert data
                got += sum(p.type == C.PUBLISH for p in parser.feed(data))
            w.close()
            sub_w.close()
            await settle(lambda: not s.listener._conns)
            return s.broker.profiler.windows(limit=256)

    ring = run(main())
    assert from_transport and all(from_transport) == (kind == "ssl")
    assert not any(from_transport) == (kind == "tcp")
    assert sum(r["loop_ingress_reads"] for r in ring) >= 20
    assert metric("ingress_native_read_pct.flood")(ring) == share
    assert metric("ingress_direct_read_pct.flood")(ring) == 100.0


# ------------------------------------------------- who takes which path


@pytest.mark.parametrize("direct", DIRECT)
def test_plain_tcp_and_tls_read_direct_limited_or_not_websocket_does_not(
    direct, tmp_path, monkeypatch
):
    if direct == "native" and sockreader.load() is None:
        pytest.skip("native sockreader not built")
    if direct == "callback":
        monkeypatch.setitem(nativelib._libs, "sockreader", None)

    async def main():
        certfile, keyfile = _make_cert(tmp_path)
        cfg = BrokerConfig()
        cfg.engine.batch_publish = False
        cfg.engine.use_device = False
        cfg.listeners = [
            ListenerConfig(name="tcp", bind="127.0.0.1", port=0),
            ListenerConfig(name="tls", type="ssl", bind="127.0.0.1",
                           port=0, certfile=certfile, keyfile=keyfile),
            ListenerConfig(name="ws", type="ws", bind="127.0.0.1", port=0),
            ListenerConfig(name="msgs", bind="127.0.0.1", port=0,
                           messages_rate=1000.0),
            ListenerConfig(name="bytes", bind="127.0.0.1", port=0,
                           bytes_rate=1e6),
        ]
        srv = BrokerServer(cfg)
        await srv.start()
        try:
            tcp, tls, ws, msgs, byts = srv.listeners
            assert [lst._direct for lst in srv.listeners] == [
                True, True, False, True, True
            ]
            ctx = ssl.create_default_context(cafile=certfile)

            class Tls(TestClient):
                async def connect(self, **kw):
                    self.reader, self.writer = (
                        await asyncio.open_connection(
                            "localhost", self.port, ssl=ctx
                        )
                    )
                    self._pump = asyncio.get_running_loop().create_task(
                        self._read_loop()
                    )
                    await self.send(C.Connect(
                        client_id=self.client_id, proto_ver=self.version,
                        clean_start=True, keepalive=60,
                    ))
                    return await self.expect(C.CONNACK)

            clients = [
                TestClient(tcp.port, "c-tcp"), Tls(tls.port, "c-tls"),
                WsTestClient(ws.port, "c-ws"),
                TestClient(msgs.port, "c-msgs"),
                TestClient(byts.port, "c-bytes"),
            ]
            for c in clients:
                assert (await c.connect()).reason_code == 0
                await c.subscribe("who/" + c.client_id, qos=1)
            pub = TestClient(tls.port, "c-pub")
            pub.connect = Tls.connect.__get__(pub)
            await pub.connect()
            for c in clients:
                await pub.publish("who/" + c.client_id, b"x", qos=1)
                assert (await c.expect(C.PUBLISH)).payload == b"x"
            lc = srv.broker.profiler.loop
            # (the five clients of the direct listeners: a CONNECT
            # each, four SUBSCRIBEs, five publishes)
            assert 14 <= lc.ingress_reads_direct < lc.ingress_reads
            assert len(tcp._conns) == 1 and len(tls._conns) == 2
            assert (srv.broker.reader is None) == (direct == "callback")
            # (the TLS clients' reads are direct and not native)
            if direct == "native":
                assert 0 < lc.ingress_reads_native < lc.ingress_reads_direct
            else:
                assert lc.ingress_reads_native == 0
            for lst in (tcp, tls, msgs, byts):
                for conn in lst._conns:
                    assert isinstance(conn, Connection)
                    assert conn.reader is None
                    assert conn.writer.get_protocol() is conn
                    assert (conn.limiter is None) == (lst in (tcp, tls))
                    # plain TCP's recv is the reader thread's, its
                    # transport paused for good; TLS reads itself
                    native = direct == "native" and lst is not tls
                    assert (conn._reader is not None) == native
                    assert conn.writer.is_reading() != native
                    assert conn.is_reading()
            (task,) = ws._conns
            assert isinstance(task, asyncio.Task)
            for c in clients + [pub]:
                await c.disconnect()
        finally:
            await srv.stop()

    run(main())


@pytest.mark.parametrize("direct", DIRECT)
def test_a_direct_connection_costs_one_timer_task_and_no_other(
    direct, monkeypatch
):
    """No reader task, no `StreamReader`: a hundred plain-TCP clients
    are a hundred `_timers` tasks, before and after traffic."""
    async def main():
        async with Served(monkeypatch, direct) as s:
            before = asyncio.all_tasks()
            loop = asyncio.get_running_loop()
            socks = []
            for i in range(100):
                sock = socket.create_connection(("127.0.0.1", s.port))
                sock.setblocking(False)
                await loop.sock_sendall(sock, C.serialize(
                    C.Connect(client_id=f"n{i}", proto_ver=C.MQTT_V5,
                              clean_start=True, keepalive=60), C.MQTT_V5,
                ))
                socks.append(sock)
            await settle(lambda: len(s.broker.cm) == 100)
            for sock in socks:
                await loop.sock_sendall(
                    sock, C.serialize(C.Pingreq(), C.MQTT_V5)
                )
            await settle(lambda: s.broker.profiler.loop.ingress_reads >= 200)
            made = asyncio.all_tasks() - before
            assert len(made) == 100 == len(s.listener._conns)
            assert {t.get_coro().__qualname__ for t in made} == {
                "Connection._timers"
            }
            for sock in socks:
                sock.close()
            await settle(lambda: not s.listener._conns)
            assert not (asyncio.all_tasks() - before)

    run(main())


@pytest.mark.parametrize("direct", DIRECT)
def test_a_turns_reads_are_handled_in_one_run_after_its_recvs(
    direct, monkeypatch
):
    """Fifty sockets readable in one poll: fifty `recv`s, then one
    `ReadTurn._run` handles the fifty reads in a row (one `call_soon`
    a turn, none a read)."""
    async def main():
        async with Served(monkeypatch, direct) as s:
            loop = asyncio.get_running_loop()
            socks = [await a_client(s, f"t{i}") for i in range(50)]
            runs = []
            run_turn = ReadTurn._run

            def counted(turn):
                runs.append(len(turn._conns))
                return run_turn(turn)

            monkeypatch.setattr(ReadTurn, "_run", counted)
            lc = s.broker.profiler.loop
            reads = lc.ingress_reads
            ping = C.serialize(C.Pingreq(), C.MQTT_V5)
            for sock in socks:
                sock.send(ping)  # (no await: all before the next poll)
            for sock in socks:
                assert await asyncio.wait_for(
                    loop.sock_recv(sock, 16), 5
                ) == C.serialize(C.Pingresp(), C.MQTT_V5)
            assert lc.ingress_reads - reads == 50 == sum(runs)
            assert len(runs) <= 3, runs
            for sock in socks:
                sock.close()

    run(main())


def test_a_close_in_a_turn_is_torn_down_before_the_turns_later_reads():
    """Two reads of one turn (one batch of the reader thread's): the
    first closes its connection, whose teardown is queued; the second
    is handled after that teardown, as it would be after the next
    poll, so a reconnect's CONNECT finds the old session gone."""
    seen = []

    class Closing:
        def _handle_reads(self):
            asyncio.get_running_loop().call_soon(seen.append, "teardown")
            return True  # the read closed its connection

    class Reading:
        def _handle_reads(self):
            seen.append("read")

    async def main():
        turn = ReadTurn()
        for conn in (Closing(), Reading()):
            turn.add(conn)
        await asyncio.sleep(0.01)

    run(main())
    assert seen == ["teardown", "read"]


@pytest.mark.parametrize("direct", DIRECT)
@pytest.mark.parametrize("where", ["handle_in", "after_packets", "teardown"])
def test_one_connections_fault_costs_the_others_no_read(where, direct,
                                                        monkeypatch):
    """A bug in a packet's handler, in what follows a read's packets
    (the congestion tests), in the teardown itself: the turn's other
    reads are off their sockets already and are handled all the
    same."""
    async def main():
        async with Served(monkeypatch, direct) as s:
            loop = asyncio.get_running_loop()
            socks = {c: await a_client(s, c) for c in ("a", "bad", "z")}

            def bad(ch):
                return ch.client is not None and ch.client.clientid == "bad"

            def faulty(real):
                def fn(ch, *a):
                    if bad(ch):
                        raise RuntimeError("a bug in " + where)
                    return real(ch, *a)
                return fn

            if where == "after_packets":
                monkeypatch.setattr(Channel, "defer_saturated", property(
                    faulty(Channel.defer_saturated.fget)))
            else:
                monkeypatch.setattr(
                    Channel, "handle_in", faulty(Channel.handle_in))
            if where == "teardown":
                monkeypatch.setattr(Channel, "connection_lost", faulty(
                    Channel.connection_lost))
            ping = C.serialize(C.Pingreq(), C.MQTT_V5)
            ping_back = C.serialize(C.Pingresp(), C.MQTT_V5)
            for sock in socks.values():
                sock.send(ping)
            for c in ("a", "z"):
                assert await asyncio.wait_for(
                    loop.sock_recv(socks[c], 16), 5
                ) == ping_back
            if where != "teardown":
                # the faulty one is closed, as a task that raised is
                # (its packets were handled, where the fault came after)
                assert await asyncio.wait_for(
                    loop.sock_recv(socks["bad"], 16), 5
                ) == (ping_back if where == "after_packets" else b"")
                if where == "after_packets":
                    assert await asyncio.wait_for(
                        loop.sock_recv(socks["bad"], 16), 5
                    ) == b""
                await settle(lambda: len(s.listener._conns) == 2)
                assert [r for _ch, r in s.lost] == ["closed"]
            for sock in socks.values():
                sock.close()
            await settle(lambda: not s.listener._conns)

    run(main())


# ------------------------------------------------------- close reasons


async def a_client(s, clientid="closing", keepalive=60):
    sock = socket.create_connection(("127.0.0.1", s.port))
    sock.setblocking(False)
    loop = asyncio.get_running_loop()
    await loop.sock_sendall(sock, C.serialize(
        C.Connect(client_id=clientid, proto_ver=C.MQTT_V5,
                  clean_start=True, keepalive=keepalive), C.MQTT_V5,
    ))
    await settle(lambda: s.broker.cm.channel(clientid) is not None)
    # (the CONNACK, read: a close with bytes unread is a reset)
    assert await asyncio.wait_for(loop.sock_recv(sock, 4096), 5)
    return sock


REASONS = {
    # case -> the reason `Channel.connection_lost` is told
    "closed": "closed",
    "peer_reset": "peer_reset",
    "frame_error": "frame_error",
    "idle_timeout": "idle_timeout",
    # (the channel closes the connection, whose read side then finds
    # the socket closed: the channel told `_close` why)
    "keepalive_timeout": "closed",
    "server_stopped": "server_stopped",
    "sender_failed": "peer_reset",
}


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("case", list(REASONS))
def test_every_close_reason_ends_a_connection_once(
    case, path, monkeypatch
):
    async def main():
        if case == "keepalive_timeout":
            monkeypatch.setattr(connection_mod, "_TIMER_TICK", 0.05)
        mqtt = {"idle_timeout": 0.3} if case == "idle_timeout" else {}
        s = Served(monkeypatch, path, listen={"max_connections": 1}, **mqtt)
        closes = []
        close = Connection._close
        monkeypatch.setattr(
            Connection, "_close",
            lambda conn, reason: (closes.append(reason), close(conn, reason)),
        )
        await s.__aenter__()
        stopped = False
        try:
            loop = asyncio.get_running_loop()
            if case == "idle_timeout":
                # connected, and never a CONNECT
                sock = socket.create_connection(("127.0.0.1", s.port))
                await settle(lambda: len(s.listener._conns) == 1)
                conn = None
            else:
                sock = await a_client(s)
                conn = s.conn_of("closing")
                assert conn._slot >= 0 and conn._sender is not None
            # the listener is full until the connection is gone
            assert len(s.listener._conns) == 1
            late = socket.create_connection(("127.0.0.1", s.port))
            late.setblocking(False)
            assert await asyncio.wait_for(
                loop.sock_recv(late, 16), 5
            ) == b""
            late.close()
            assert not s.lost

            if case == "closed":
                sock.close()
            elif case == "peer_reset":
                sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                b"\x01\x00\x00\x00\x00\x00\x00\x00")
                sock.close()  # an RST, not a FIN
            elif case == "frame_error":
                await loop.sock_sendall(sock, b"\x00\x00")  # type 0
            elif case == "keepalive_timeout":
                s.broker.cm.channel("closing").last_rx -= 1000
            elif case == "server_stopped":
                stopped = True
                await s.srv.stop()
            elif case == "sender_failed":
                conn.on_sender_failed(32)  # as the thread reports EPIPE
            await settle(lambda: s.lost and not s.listener._conns)
            await asyncio.sleep(0.05)  # (a second teardown would show)
            assert [r for _ch, r in s.lost] == [REASONS[case]]
            if case == "keepalive_timeout":
                assert closes == ["keepalive_timeout"]
            if conn is not None:
                assert conn._slot == -1 and conn._sender is None
                assert conn.writer.is_closing()
                assert conn._timer.done() or conn._timer.cancelling()
            assert not s.broker.alarms.active()
            if not stopped:
                # the listener has room again
                again = await a_client(s, "after")
                again.close()
            sock.close()
        finally:
            if not stopped:
                await s.__aexit__()

    run(main())


@pytest.mark.parametrize("direct", DIRECT)
def test_a_congestion_alarm_does_not_outlive_a_direct_connection(
    direct, monkeypatch
):
    async def main():
        async with Served(monkeypatch, direct) as s:
            sock = await a_client(s, "slow")
            conn = s.conn_of("slow")
            monkeypatch.setattr(conn, "_tbuf", lambda: 2 << 20)
            conn._note_buffered()
            assert [a.name for a in s.broker.alarms.active()] == [
                "conn_congestion/slow"
            ]
            sock.close()  # an EOF: the channel never calls `_close`
            await settle(lambda: s.lost)
            assert not s.broker.alarms.active()

    run(main())


# ------------------------------------------------------- back-pressure


def publishes(lo, hi, qos=0, size=0):
    return b"".join(
        C.serialize(C.Publish(
            topic="bp/x", payload=b"%06d" % i + b"." * size, qos=qos,
            packet_id=(i % 60000) + 1 if qos else None,
        ), C.MQTT_V5)
        for i in range(lo, hi)
    )


async def a_pair(s):
    """A subscriber that records what it gets, and a raw publisher
    whose connection the test watches."""
    sub = TestClient(s.port, "bp-sub")
    await sub.connect()
    await sub.subscribe("bp/#", qos=0)
    pub = await a_client(s, "bp-pub")
    conn = s.conn_of("bp-pub")
    assert conn.is_reading() and not conn._paused
    return sub, pub, conn


async def in_order(sub, n):
    for i in range(n):
        pkt = await sub.expect(C.PUBLISH, timeout=20)
        assert pkt.payload[:6] == b"%06d" % i, (i, pkt.payload[:6])


@pytest.mark.parametrize("direct", DIRECT)
def test_a_congested_lane_pauses_reading_and_its_release_resumes_it(
    direct, monkeypatch
):
    async def main():
        async with Served(monkeypatch, direct, batcher=True) as s:
            batcher = s.broker.batcher
            sub, pub, conn = await a_pair(s)
            # the collector stalls: lanes fill, nothing drains them
            batcher.high_watermark, batcher.inflight_max = 8, 0
            loop = asyncio.get_running_loop()
            first = publishes(0, 10)
            base = s.received()
            await loop.sock_sendall(pub, first)
            await settle(lambda: conn._paused == {"lane"})
            assert not conn.is_reading()
            await loop.sock_sendall(pub, publishes(10, 30))
            await asyncio.sleep(0.1)
            # the further publishes wait in the kernel, unread
            assert s.received() == base + len(first)
            assert batcher._lane_depth(conn.channel) == 10
            batcher.inflight_max = 2048
            batcher._inflight_drain.set()
            await in_order(sub, 30)
            await settle(lambda: not conn._paused)
            assert conn.is_reading()
            assert sum(1 for t, _i, _p in s.handled
                       if t == C.PUBLISH) == 30
            pub.close()
            await sub.disconnect()

    run(main())


class SlowVerdicts:
    """An IO-backed ``client.authorize`` hook (as exhook's): every
    PUBLISH is deferred into the channel's chain, which moves when the
    test says."""

    def __init__(self, broker, held=True):
        self.open = asyncio.Event()
        if not held:
            self.open.set()

        async def verdict(_client, _action, _topic, acc):
            await self.open.wait()
            return acc

        broker.hooks.add(
            "client.authorize",
            with_async(lambda *_a: None, verdict),
        )


@pytest.mark.parametrize("direct", DIRECT)
def test_a_saturated_deferral_chain_pauses_reading_and_its_drain_resumes_it(
    direct, monkeypatch
):
    async def main():
        async with Served(monkeypatch, direct) as s:
            sub, pub, conn = await a_pair(s)
            slow = SlowVerdicts(s.broker)
            conn.channel.DEFER_HIGH, conn.channel.DEFER_LOW = 8, 2
            loop = asyncio.get_running_loop()
            first = publishes(0, 10)
            base = s.received()
            await loop.sock_sendall(pub, first)
            await settle(lambda: conn._paused == {"defer"})
            assert not conn.is_reading()
            await loop.sock_sendall(pub, publishes(10, 30))
            await asyncio.sleep(0.1)
            assert s.received() == base + len(first)
            assert conn.channel._defer_depth == 10
            slow.open.set()
            await in_order(sub, 30)
            await settle(lambda: not conn._paused)
            assert conn.is_reading()
            pub.close()
            await sub.disconnect()

    run(main())


@pytest.mark.parametrize("direct", DIRECT)
def test_a_write_buffer_over_its_mark_pauses_reading_until_it_drains(
    direct, monkeypatch
):
    """The client sends and does not read what comes back to it: the
    transport's buffer passes its high-water mark (`pause_writing`,
    what `writer.drain()` waits out on the other path) and the broker
    stops reading that client."""
    async def main():
        async with Served(monkeypatch, direct) as s:
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
            sock.setblocking(False)
            loop = asyncio.get_running_loop()
            await loop.sock_connect(sock, ("127.0.0.1", s.port))
            v = C.MQTT_V5
            await loop.sock_sendall(sock, C.serialize(C.Connect(
                client_id="echo", proto_ver=v, clean_start=True,
                keepalive=60), v) + C.serialize(C.Subscribe(
                    packet_id=1, subscriptions=[
                        C.Subscription(topic_filter="bp/#")]), v))
            await settle(lambda: s.broker.cm.channel("echo") is not None)
            conn = s.conn_of("echo")
            conn.writer.get_extra_info("socket").setsockopt(
                socket.SOL_SOCKET, socket.SO_SNDBUF, 4096
            )
            n, size = 64, 32768
            stream = publishes(0, n, size=size)
            send = loop.create_task(loop.sock_sendall(sock, stream))
            await settle(lambda: "write" in conn._paused)
            assert not conn.is_reading()
            stalled = s.received()
            await asyncio.sleep(0.1)
            assert s.received() == stalled < len(stream)
            assert conn.writer.get_write_buffer_size() > 65536
            # the client reads: the buffer drains, the rest is read
            parser = C.StreamParser(version=v)
            seen = []
            while len(seen) < n:
                data = await asyncio.wait_for(
                    loop.sock_recv(sock, 1 << 20), 20
                )
                assert data
                seen += [p.payload[:6] for p in parser.feed(data)
                         if p.type == C.PUBLISH]
            await send
            assert seen == [b"%06d" % i for i in range(n)]
            await settle(lambda: not conn._paused)
            assert conn.is_reading()
            sock.close()

    run(main())


@pytest.mark.parametrize("direct", DIRECT)
def test_two_reasons_at_once_resume_only_when_both_are_gone(
    direct, monkeypatch
):
    async def main():
        async with Served(monkeypatch, direct) as s:
            sub, pub, conn = await a_pair(s)
            slow = SlowVerdicts(s.broker)
            conn.channel.DEFER_HIGH, conn.channel.DEFER_LOW = 8, 2
            loop = asyncio.get_running_loop()
            base = s.received()
            first = publishes(0, 10)
            await loop.sock_sendall(pub, first)
            await settle(lambda: conn._paused == {"defer"})
            conn.pause_writing()  # as the transport, over its mark
            assert conn._paused == {"defer", "write"}
            await loop.sock_sendall(pub, publishes(10, 20))
            # the first release alone resumes nothing
            slow.open.set()
            await in_order(sub, 10)
            await settle(lambda: conn._paused == {"write"})
            await asyncio.sleep(0.05)
            assert not conn.is_reading()
            assert s.received() == base + len(first)
            conn.resume_writing()
            assert not conn._paused and conn.is_reading()
            for i in range(10, 20):
                pkt = await sub.expect(C.PUBLISH, timeout=20)
                assert pkt.payload[:6] == b"%06d" % i
            # and in the other order
            conn.pause_writing()
            conn._pause_reading("lane")
            conn.resume_writing()
            assert not conn.is_reading()
            conn._resume_reading("lane")
            assert conn.is_reading()
            pub.close()
            await sub.disconnect()

    run(main())


# 20 messages a second after a burst of 20
LIMITED = {"messages_rate": 20.0}


@pytest.mark.parametrize("direct", DIRECT)
def test_a_limiter_on_a_direct_connection_is_paid_between_packets(
    direct, monkeypatch
):
    """A listener with a rate is direct like any other: a limiter's
    pauses are reading paused and a timer, the rest of that read's
    packets (and a read the transport still hands over) wait in order
    and none passes unpaid."""
    async def main():
        async with Served(monkeypatch, direct, listen=LIMITED) as s:
            assert s.listener._direct
            sub, pub, conn = await a_pair(s)
            assert conn.limiter is not None and conn.reader is None
            loop = asyncio.get_running_loop()
            t0 = loop.time()
            await loop.sock_sendall(pub, publishes(0, 30))  # one read
            await settle(lambda: conn._paused == {"limiter"})
            assert not conn.is_reading()
            # (a TLS transport may hold a record it had decrypted)
            conn.data_received(publishes(30, 35))
            await asyncio.sleep(0.05)
            assert conn._reads and sum(
                1 for t, _i, _p in s.handled if t == C.PUBLISH
            ) < 30
            await in_order(sub, 35)
            took = loop.time() - t0
            assert took >= 0.4, took  # 35 at 20 a second, less the burst
            assert s.broker.metrics.all()["connection.rate_limited"] > 0
            await settle(lambda: not conn._paused)
            lc = s.broker.profiler.loop
            # one read, its pauses no part of its time
            assert lc.ingress_publishes >= 35
            assert lc.ingress_s < took / 2
            # closed in the middle of a pause: the timer goes with it
            await loop.sock_sendall(pub, publishes(35, 65))
            await settle(lambda: conn._owed is not None)
            conn.channel.close("kicked")
            await settle(lambda: s.lost)
            assert conn._owed is None
            pub.close()
            await sub.disconnect()

    run(main())


@pytest.mark.parametrize("direct", DIRECT)
@pytest.mark.parametrize(
    "how", ["half_close", "close", "reset", "tls_close", "abort"]
)
def test_a_publisher_gone_inside_a_limiters_pause_loses_no_packet(
    how, direct, tmp_path, monkeypatch
):
    """The publisher is gone before its read's pauses are paid.  Plain
    TCP, reading paused, learns of it after them.  A TLS transport
    ends the connection in the turn of the read (the ``close_notify``
    came with it), a failed write whenever it fails: the rest of the
    read's packets are handled when the pauses are paid, as the
    coroutine would have, and the connection ends after them, once,
    for the reason the transport gave, counted until then."""
    async def main():
        listen, ctx = dict(LIMITED, max_connections=2), None
        if how == "tls_close":
            certfile, keyfile = _make_cert(tmp_path)
            listen.update(type="ssl", certfile=certfile, keyfile=keyfile)
            ctx = ssl.create_default_context(cafile=certfile)
        v = C.MQTT_V5
        async with Served(monkeypatch, direct, listen=listen) as s:
            sub_r, sub_w = await asyncio.open_connection(
                "localhost", s.port, ssl=ctx
            )
            sub_w.write(C.serialize(C.Connect(
                client_id="bp-sub", proto_ver=v), v) + C.serialize(
                    C.Subscribe(packet_id=1, subscriptions=[
                        C.Subscription(topic_filter="bp/#")]), v))
            _r, w = await asyncio.open_connection(
                "localhost", s.port, ssl=ctx
            )
            w.write(C.serialize(C.Connect(
                client_id="bp-pub", proto_ver=v), v))
            await settle(lambda: s.broker.cm.channel("bp-pub") is not None)
            conn = s.conn_of("bp-pub")
            loop = asyncio.get_running_loop()
            t0 = loop.time()
            w.write(publishes(0, 30))
            if how == "tls_close":
                w.close()  # (with the read: no turn between)
            await settle(lambda: conn._owed is not None)
            if how == "half_close":
                w.write_eof()
            elif how == "close":
                w.close()
            elif how == "reset":
                w.get_extra_info("socket").setsockopt(
                    socket.SOL_SOCKET, socket.SO_LINGER,
                    b"\x01\x00\x00\x00\x00\x00\x00\x00")
                w.transport.abort()
            elif how == "abort":
                conn.writer.abort()  # as a write that failed
            if how in ("tls_close", "abort"):
                await settle(lambda: conn._ended is not None)
            await asyncio.sleep(0.05)
            assert not s.lost and conn in s.listener._conns
            got, parser = [], C.StreamParser(version=v)
            while len(got) < 30:
                data = await asyncio.wait_for(sub_r.read(65536), 10)
                assert data
                got += [p.payload[:6] for p in parser.feed(data)
                        if p.type == C.PUBLISH]
            assert got == [b"%06d" % i for i in range(30)]
            assert loop.time() - t0 >= 0.4  # 30 at 20 a second, less 20
            await settle(lambda: s.lost and len(s.listener._conns) == 1)
            await asyncio.sleep(0.05)
            assert [r for _ch, r in s.lost] == [
                "peer_reset" if how == "reset" else "closed"
            ]
            assert conn._owed is None and conn.writer.is_closing()
            w.close()
            sub_w.close()

    run(main())


# ------------------------------------------- forced interleavings


def _release_before_pause(direct):
    """The chain's verdicts come at once, so under a forced schedule
    the drain can land before, between and after the reads that test
    for saturation: every publish is handled once, in order, and the
    connection ends reading."""
    async def main():
        with pytest.MonkeyPatch.context() as mp:
            async with Served(mp, direct) as s:
                sub, pub, conn = await a_pair(s)
                SlowVerdicts(s.broker, held=False)
                conn.channel.DEFER_HIGH, conn.channel.DEFER_LOW = 4, 1
                loop = asyncio.get_running_loop()
                for lo in range(0, 40, 8):
                    await loop.sock_sendall(pub, publishes(lo, lo + 8))
                    if lo % 16:
                        conn.pause_writing()
                        await asyncio.sleep(0)
                        conn.resume_writing()
                await in_order(sub, 40)
                await settle(lambda: not conn._paused)
                assert conn.is_reading()
                assert sum(1 for t, _i, _p in s.handled
                           if t == C.PUBLISH) == 40
                pub.close()
                await sub.disconnect()
    return main()


def _pause_during_close(direct):
    """The connection is kicked while reading is paused for two
    reasons; the releases come after: nothing resumes, nothing
    raises, and the teardown ran once."""
    async def main():
        with pytest.MonkeyPatch.context() as mp:
            async with Served(mp, direct) as s:
                sub, pub, conn = await a_pair(s)
                slow = SlowVerdicts(s.broker)
                conn.channel.DEFER_HIGH, conn.channel.DEFER_LOW = 4, 1
                loop = asyncio.get_running_loop()
                await loop.sock_sendall(pub, publishes(0, 8))
                await settle(lambda: "defer" in conn._paused)
                conn.pause_writing()
                conn.channel.close("kicked")
                slow.open.set()
                conn.resume_writing()
                await settle(lambda: s.lost and not any(
                    c is conn for c in s.listener._conns
                ))
                await asyncio.sleep(0.02)
                assert [r for ch, r in s.lost
                        if ch is conn.channel] == ["closed"]
                assert conn.writer.is_closing()
                assert not conn.is_reading()
                pub.close()
                await sub.disconnect()
    return main()


@pytest.mark.parametrize("direct", DIRECT)
@pytest.mark.parametrize(
    "workload", [_release_before_pause, _pause_during_close]
)
def test_pauses_and_releases_under_forced_interleavings(workload, direct):
    if direct == "native" and sockreader.load() is None:
        pytest.skip("native sockreader not built")
    outcomes = run_seeds(lambda: workload(direct), seeds=range(8),
                         timeout=60.0)
    bad = [o for o in outcomes if o.failed]
    assert not bad, f"{bad[0].label}: {bad[0].error!r}"
