"""Native window deliver (PR 5): GIL-released per-run packet assembly
+ block session bookkeeping.

The referee for the dispatch fast path: the native assembler
(`native/dispatchasm.cpp` via `ops.dispatchasm`) and the pure-Python
per-delivery fallback in `Session.deliver` must put bit-identical
bytes on every connection's wire under random qos / version / RAP /
subid / no_local / upgrade_qos mixes — decoded end-to-end through a
real `Channel` — and the whole suite must stay green with the `.so`
unavailable.  Plus the standalone bulk bookkeeping (block packet-id
allocator, `Inflight.insert_run`), the shared detached-window mqueue
bake, and the window-batched delivered sink."""

import random

import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.channel import Channel
from emqx_tpu.broker.inflight import Inflight
from emqx_tpu.broker.session import Session, SubOpts
from emqx_tpu.codec import mqtt as C
from emqx_tpu.config import BrokerConfig
from emqx_tpu.message import Message
from emqx_tpu.ops import dispatchasm, nativelib


def _broker():
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    return Broker(config=cfg)


class WireChannel(Channel):
    """Real Channel over a capturing transport (true wire bytes, true
    cork behavior), as in test_dispatch_fanout."""

    def __init__(self, broker, version=C.MQTT_V5):
        self.writes = []

        def send(pkts):
            self.writes.append(
                b"".join(C.serialize(p, self.version) for p in pkts)
            )

        super().__init__(broker, send=send, close=lambda r: None)
        self.version = version


def _force_fallback(monkeypatch):
    """Make ops.dispatchasm.load() return None (missing-.so shape)."""
    monkeypatch.setitem(nativelib._libs, "dispatchasm", None)


_native = dispatchasm.load()


# ------------------------------------------------ native/python parity


def _build_world(seed):
    """One randomized subscriber/publish world, returned as plain data
    so the native and fallback brokers are built identically."""
    rng = random.Random(seed)
    clients = []
    for i in range(10):
        subs = []
        for f in range(rng.randint(1, 3)):
            flt = rng.choice(["t/#", "t/+/x", f"t/{f}/x", "s/only"])
            subs.append({
                "flt": flt,
                "qos": rng.randint(0, 2),
                "rap": rng.random() < 0.4,
                "no_local": rng.random() < 0.3,
                "subid": rng.randint(1, 9)
                if rng.random() < 0.2 else None,
            })
        clients.append({
            "cid": f"c{i}",
            "version": rng.choice([C.MQTT_V4, C.MQTT_V5]),
            "upgrade": rng.random() < 0.3,
            "max_inflight": rng.choice([2, 4, 32]),
            "subs": subs,
        })
    windows = []
    for _ in range(4):
        win = []
        for _ in range(rng.randint(1, 12)):
            win.append({
                "topic": rng.choice(
                    ["t/1/x", "t/2/x", "t/0/x", "s/only", "t/deep/x"]
                ),
                "qos": rng.randint(0, 2),
                "retain": rng.random() < 0.3,
                "payload": bytes(
                    rng.randrange(256)
                    for _ in range(rng.randint(0, 200))
                ),
                "from": rng.choice(["c0", "c1", "pub"]),
            })
        windows.append(win)
    return clients, windows


def _run_world(clients, windows):
    b = _broker()
    chans = {}
    for c in clients:
        ch = WireChannel(b, version=c["version"])
        session, _ = b.cm.open_session(
            True, c["cid"], ch, max_inflight=c["max_inflight"]
        )
        session.upgrade_qos = c["upgrade"]
        for s in c["subs"]:
            opts = SubOpts(
                qos=s["qos"], retain_as_published=s["rap"],
                no_local=s["no_local"], subid=s["subid"],
            )
            session.subscribe(s["flt"], opts)
            b.subscribe(c["cid"], s["flt"], opts)
        chans[c["cid"]] = ch
    counts = []
    ts = 1.0e9  # fixed stamps: identical expiry math across runs
    for win in windows:
        msgs = [
            Message(
                topic=w["topic"], qos=w["qos"], retain=w["retain"],
                payload=w["payload"], from_client=w["from"],
                timestamp=ts,
            )
            for w in win
        ]
        counts.append(b.publish_many(msgs))
    wires = {cid: b"".join(ch.writes) for cid, ch in chans.items()}
    sent = {
        k: b.metrics.val(k)
        for k in ("messages.sent", "messages.qos0.sent",
                  "messages.qos1.sent", "messages.qos2.sent",
                  "packets.publish.sent", "messages.delivered")
    }
    inflights = {
        c["cid"]: sorted(
            (pid, e.qos) for pid, e in b.cm.lookup(c["cid"]).inflight.items()
        )
        for c in clients
    }
    return counts, wires, sent, inflights, {c["cid"]: c for c in clients}


@pytest.mark.skipif(_native is None, reason="native dispatchasm unavailable")
@pytest.mark.parametrize("seed", [1, 2, 7, 23])
def test_native_and_fallback_wire_is_bit_identical(seed, monkeypatch):
    """Property test: random qos/version/RAP/subid/no_local/
    upgrade_qos/inflight-pressure mixes through full broker windows —
    the native assembler and the per-delivery Python loop must produce
    the SAME per-connection byte stream, delivery counts, per-qos sent
    metrics, and inflight windows."""
    clients, windows = _build_world(seed)
    native = _run_world(clients, windows)
    _force_fallback(monkeypatch)
    fallback = _run_world(clients, windows)
    assert native[0] == fallback[0]  # delivery counts
    for cid in native[1]:
        assert native[1][cid] == fallback[1][cid], cid
    assert native[2] == fallback[2]  # per-qos sent metrics
    assert native[3] == fallback[3]  # (pid, qos) inflight windows
    # and the native byte stream decodes end-to-end through the codec
    for cid, wire in native[1].items():
        parser = C.StreamParser(version=native[4][cid]["version"])
        for pkt in parser.feed(wire):
            assert pkt.type == C.PUBLISH


@pytest.mark.skipif(_native is None, reason="native dispatchasm unavailable")
def test_native_path_actually_engages():
    """Guard against silently testing fallback-vs-fallback: a plain
    window must take the native path (assemble stage recorded, run
    arriving as ONE Raw blob)."""
    b = _broker()
    ch = WireChannel(b)
    session, _ = b.cm.open_session(True, "c1", ch)
    session.subscribe("t/#", SubOpts(qos=1))
    b.subscribe("c1", "t/#", SubOpts(qos=1))
    raws = []
    orig = ch._send

    def send(pkts):
        raws.extend(p for p in pkts if isinstance(p, C.Raw))
        orig(pkts)

    ch._send = send
    counts = b.publish_many(
        [Message(topic=f"t/{i}", qos=1) for i in range(8)]
    )
    assert counts == [1] * 8
    assert len(raws) == 1 and raws[0].n_packets == 8
    (win,) = b.profiler.windows(1)
    assert "assemble" in win["stages_us"]
    assert b.profiler.summary()["assemble"]["count"] >= 1
    # the blob decodes to the eight QoS1 publishes with fresh pids
    parser = C.StreamParser(version=C.MQTT_V5)
    pkts = list(parser.feed(b"".join(ch.writes)))
    assert [p.packet_id for p in pkts] == list(range(1, 9))


def test_missing_so_full_fallback(monkeypatch):
    """Force the ctypes load to fail: dispatch stays green on the
    per-delivery loop (the acceptance criterion's deleted-.so run)."""
    _force_fallback(monkeypatch)
    assert dispatchasm.load() is None
    b = _broker()
    ch = WireChannel(b)
    session, _ = b.cm.open_session(True, "c1", ch)
    session.subscribe("t/#", SubOpts(qos=1))
    b.subscribe("c1", "t/#", SubOpts(qos=1))
    assert b.publish_many(
        [Message(topic=f"t/{i}", qos=1) for i in range(4)]
    ) == [1] * 4
    assert len(ch.writes) == 1  # still ONE corked write per window
    parser = C.StreamParser(version=C.MQTT_V5)
    assert [p.packet_id for p in parser.feed(ch.writes[0])] == [1, 2, 3, 4]


# ------------------------------------------- block session bookkeeping


def test_alloc_packet_ids_matches_sequential_semantics():
    """The block allocator must equal n sequential `_alloc_packet_id`
    calls (with interleaved inserts) for wraparound and in-use skips."""
    rng = random.Random(3)
    for _ in range(50):
        s_blk = Session("blk")
        s_seq = Session("seq")
        start = rng.choice([0, 1, 17, 65530, 65533, 65534])
        s_blk._next_pid = s_seq._next_pid = start
        in_use = rng.sample(range(1, 66), rng.randint(0, 8))
        for pid in in_use:
            s_blk.inflight.insert(pid, "x")
            s_seq.inflight.insert(pid, "x")
        n = rng.randint(1, 6)
        got = s_blk.alloc_packet_ids(n)
        want = []
        for _ in range(n):
            pid = s_seq._alloc_packet_id()
            s_seq.inflight.insert(pid, "y")  # sequential interleave
            want.append(pid)
        assert got == want, (start, in_use, n)
        assert s_blk._next_pid == s_seq._next_pid


def test_alloc_packet_ids_wraparound():
    s = Session("w")
    s._next_pid = 65533
    assert s.alloc_packet_ids(4) == [65534, 65535, 1, 2]


def test_alloc_packet_ids_skips_block_internal_ids():
    """Ids granted earlier in the same block are in use even though
    their inflight inserts land only after the whole allocation."""
    s = Session("b")
    s._next_pid = 65534
    s.inflight.insert(1, "x")
    assert s.alloc_packet_ids(3) == [65535, 2, 3]


def test_alloc_packet_ids_exhaustion():
    s = Session("full", max_inflight=0)
    for pid in range(1, 65536):
        s.inflight.insert(pid, "x")
    with pytest.raises(RuntimeError):
        s.alloc_packet_ids(1)


def test_inflight_insert_run():
    inf = Inflight(8)
    inf.insert_run([3, 1, 2], ["a", "b", "c"])
    assert [k for k, _ in inf.items()] == [3, 1, 2]  # order preserved
    assert inf.get(1) == "b"
    with pytest.raises(KeyError):
        inf.insert_run([5, 3], ["d", "e"])  # duplicate detected
    assert inf.get(5) == "d"  # entries before the dup landed (as with
    # sequential insert calls)


# ------------------------------------- shared detached-window mqueue bake


def _detached(b, cid, **kw):
    session, _ = b.cm.open_session(False, cid, object(), **kw)
    b.cm.disconnect(cid, b.cm.channel(cid))
    return session


def test_detached_window_shares_one_bake():
    """One queued copy per (msg, qos, subopts-signature) shared across
    every detached session in the window."""
    b = _broker()
    sessions = []
    for cid in ("d1", "d2", "d3"):
        s = _detached(b, cid, expiry_interval=300.0)
        s.subscribe("t", SubOpts(qos=1))
        b.subscribe(cid, "t", SubOpts(qos=1))
        sessions.append(s)
    assert b.publish(Message(topic="t", qos=1, payload=b"p")) == 3
    baked = [s.mqueue.pop() for s in sessions]
    assert baked[0] is baked[1] is baked[2]  # ONE bake for the window
    assert baked[0].qos == 1 and baked[0].payload == b"p"


def test_detached_bake_signature_separates_variants():
    """Different effective qos / RAP / subid must NOT share a bake."""
    b = _broker()
    s1 = _detached(b, "d1", expiry_interval=300.0)
    s1.subscribe("t", SubOpts(qos=1, retain_as_published=True))
    b.subscribe("d1", "t", SubOpts(qos=1, retain_as_published=True))
    s2 = _detached(b, "d2", expiry_interval=300.0)
    s2.subscribe("t", SubOpts(qos=2, subid=7))
    b.subscribe("d2", "t", SubOpts(qos=2, subid=7))
    assert b.publish(
        Message(topic="t", qos=2, retain=True, payload=b"p")
    ) == 2
    m1, m2 = s1.mqueue.pop(), s2.mqueue.pop()
    assert m1 is not m2
    assert (m1.qos, m1.retain) == (1, True)
    assert m2.qos == 2 and not m2.retain
    assert m2.properties["subscription_identifier"] == [7]


def test_detached_shared_bake_queue_full_accounting():
    """queue_full drops stay per-session even with a shared bake."""
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    cfg.mqtt.max_mqueue_len = 2
    b = Broker(config=cfg)
    s = _detached(b, "d1", expiry_interval=300.0)
    s.subscribe("t", SubOpts(qos=1))
    b.subscribe("d1", "t", SubOpts(qos=1))
    counts = b.publish_many(
        [Message(topic="t", qos=1, payload=bytes([i])) for i in range(4)]
    )
    assert counts == [1, 1, 1, 1]  # queued counts as delivered-to-session
    assert len(s.mqueue) == 2
    assert b.metrics.val("delivery.dropped.queue_full") == 2
    # survivors are the newest two (drop-oldest policy)
    assert [m.payload for m in s.mqueue] == [b"\x02", b"\x03"]


def test_detached_shared_bake_replication_payload_unchanged():
    """`replicate_queued` must carry the same wire dicts as the
    per-client bake did (one entry per session, identical content)."""
    b = _broker()
    calls = []

    class Ext:
        def match_remote(self, topics):
            return [set() for _ in topics]

        def replicate_queued(self, cid, wires):
            calls.append((cid, wires))

        def forward(self, msg, nodes):
            pass

    b.external = Ext()
    for cid in ("d1", "d2"):
        s = _detached(b, cid, expiry_interval=300.0)
        s.subscribe("t", SubOpts(qos=1))
        b.subscribe(cid, "t", SubOpts(qos=1))
    b.publish(Message(topic="t", qos=1, payload=b"z"))
    assert sorted(c for c, _ in calls) == ["d1", "d2"]
    (w1,), (w2,) = (w for _, w in calls)
    assert w1 == w2
    assert w1["topic"] == "t" and w1["qos"] == 1


# ----------------------------------------- window-batched delivered sink


def test_delivered_batch_sink_fires_once_per_window():
    b = _broker()
    for cid in ("c1", "c2"):
        ch = WireChannel(b)
        s, _ = b.cm.open_session(True, cid, ch)
        s.subscribe("t/#", SubOpts(qos=0))
        b.subscribe(cid, "t/#", SubOpts(qos=0))
    batches = []
    b.delivered_batch_sinks.append(lambda runs: batches.append(runs))
    hook_calls = []
    b.hooks.add(
        "message.delivered",
        lambda cid, ds: hook_calls.append((cid, len(ds))),
    )
    b.publish_many([Message(topic=f"t/{i}") for i in range(5)])
    # ONE sink call for the whole window, carrying both clients' runs
    assert len(batches) == 1
    assert sorted((c, len(d)) for c, d in batches[0]) == [
        ("c1", 5), ("c2", 5)
    ]
    # the in-process hook keeps its per-(window, client) signature
    assert sorted(hook_calls) == [("c1", 5), ("c2", 5)]


def test_exhook_client_registers_window_sink():
    pytest.importorskip("grpc")
    from emqx_tpu.exhook.client import ExhookClient

    b = _broker()
    client = ExhookClient(b, "t", "127.0.0.1:1")  # nothing listening
    client._channel = object()  # _register needs no live channel
    client._register(["message.delivered", "session.created"])
    assert client._delivered_window_sink in b.delivered_batch_sinks
    # no per-client hook registered for message.delivered
    assert not any(
        cb.fn is client._delivered_window_sink
        for cb in b.hooks.callbacks("message.delivered")
    )
    assert "message.delivered" in [n for n, _ in client._registered]
    client._channel = None
    client.stop()
    assert client._delivered_window_sink not in b.delivered_batch_sinks
    assert b.hooks.callbacks("session.created") == []
