"""The point-to-point deployment (`benchmark/configs/p2p-1k.json`) at a
small size on the CPU: one exact topic a pair, QoS1 on both legs, the
shipped session settings (`max_inflight` 32, `max_mqueue_len` 1,000).

- windows of 1, 7, 512 and 513 publishes through `_dispatch_columns`
  with the decisions on the device (`decide_force = "dev"`) against
  `_dispatch_scalar`, byte for byte, and both against a dictionary
  reference written here: each delivery to its one subscriber at QoS1,
  packet ids in publish order, the in-flight window and the queue
  behind it as long as the reference says; a publisher whose topic
  nobody holds is counted dropped, not delivered;
- the same over a socket (`BrokerServer`): that publisher's QoS1
  publishes are acknowledged all the same;
- `LoopClock.ingress`'s split by packet type: a read of PUBLISH
  packets alone, of acknowledgements alone, a mixed one, for one
  clock read a socket read.
"""

import asyncio
import os
import sys

import pytest

from emqx_tpu import observability
from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.channel import CONNECTED, Channel
from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.codec import mqtt as C
from emqx_tpu.config import BrokerConfig, ListenerConfig
from emqx_tpu.message import Message
from emqx_tpu.observability import LoopClock

from mqtt_client import TestClient

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)
sys.path.insert(0, BENCH)

import traffic  # noqa: E402

PAIRS = 7            # subscriber j holds fanout/t<j>, publisher j sends on it
PUBLISHERS = 8       # the last publisher's topic has no subscriber
ACKED = 10           # PUBACKs a subscriber returns between the two windows
SEQ = slice(traffic.SEQ_AT, traffic.SEQ_AT + traffic.SEQ_W)


def run(coro):
    return asyncio.run(coro)


def topic_of(seq):
    return f"fanout/t{seq % PUBLISHERS}"


# ------------------------------------------------ the plain reference

def reference(first, second, acked, max_inflight):
    """What each subscriber is owed by two windows of publishes, how
    many PUBACKs it returns between them (at most ``acked``, of what
    the first window put on its wire), and how much of what it is owed
    the session's window has let onto the wire by the end."""
    owner = {f"fanout/t{j}": f"sub{j}" for j in range(PAIRS)}
    owed = {cid: [] for cid in owner.values()}
    acks, dropped = {}, 0
    for seqs in (first, second):
        for seq in seqs:
            cid = owner.get(topic_of(seq))
            if cid is None:
                dropped += 1
            else:
                owed[cid].append(seq)
        for cid, mine in owed.items():
            acks.setdefault(cid, min(acked, len(mine), max_inflight))
    wire = {cid: mine[:max_inflight + acks[cid]]
            for cid, mine in owed.items()}
    return owed, wire, acks, dropped


# ------------------------------------------------- the dispatch paths

class Subscriber:
    """A served `Channel` whose writes are kept as bytes."""

    def __init__(self, broker, cid, flt):
        self.wire = bytearray()
        self.channel = Channel(
            broker, close=lambda reason: None,
            send=lambda pkts: self.wire.extend(b"".join(
                C.serialize(p, C.MQTT_V5) for p in pkts)),
        )
        for pkt in (
            C.Connect(client_id=cid, proto_ver=C.MQTT_V5),
            C.Subscribe(packet_id=1, subscriptions=[
                C.Subscription(flt, qos=1)]),
        ):
            self.channel.handle_in(pkt)
        assert self.channel.state == CONNECTED
        self.wire.clear()  # CONNACK, SUBACK

    def publishes(self):
        return list(C.StreamParser(version=C.MQTT_V5).feed(bytes(self.wire)))


def windows(n):
    return range(n), range(n, n + 7)


def dispatch(n, mode):
    """A window of ``n`` publishes, ``ACKED`` PUBACKs from every
    subscriber, then a window of 7: what each path left behind."""
    cfg = BrokerConfig()
    cfg.engine.use_device = False
    b = Broker(config=cfg)
    b._decide_columns = mode != "scalar"
    if mode != "scalar":
        b.router.engine.decide_force = mode
    subs = {f"sub{j}": Subscriber(b, f"sub{j}", f"fanout/t{j}")
            for j in range(PAIRS)}
    counts = []
    for seqs in windows(n):
        counts += b.publish_many([
            Message(topic=topic_of(s), qos=1, payload=traffic.payload_of(s),
                    from_client=f"pub{s % PUBLISHERS}", timestamp=1.0e9)
            for s in seqs
        ])
        if seqs.start == 0:
            for sub in subs.values():
                for pkt in sub.publishes()[:ACKED]:
                    sub.channel.handle_in(C.Puback(packet_id=pkt.packet_id))
    return {
        "counts": counts,
        "wires": {cid: bytes(s.wire) for cid, s in subs.items()},
        "subs": subs,
        "inflight": {
            cid: [pid for pid, _ in s.channel.session.inflight.items()]
            for cid, s in subs.items()},
        "queued": {cid: len(s.channel.session.mqueue)
                   for cid, s in subs.items()},
        "metrics": {k: b.metrics.val(k) for k in (
            "messages.dropped", "messages.dropped.no_subscribers",
            "messages.qos1.sent", "messages.delivered", "messages.acked",
            "delivery.dropped", "delivery.dropped.queue_full")},
        "stats": b.router.engine.stats(),
        "max_inflight": cfg.mqtt.max_inflight,
    }


@pytest.mark.parametrize("n", [1, 7, 512, 513])
def test_window_equals_scalar_and_the_reference(n):
    dev, scalar = dispatch(n, "dev"), dispatch(n, "scalar")
    assert dev["stats"]["decide_dev_windows"] == 2
    assert dev["stats"]["decide_host_windows"] == 0
    assert scalar["stats"]["decide_dev_windows"] == 0
    for key in ("counts", "wires", "inflight", "queued", "metrics"):
        assert dev[key] == scalar[key], key
    owed, wire, acks, dropped = reference(
        *windows(n), ACKED, dev["max_inflight"])
    # a delivery a publish that has a subscriber, none for the other,
    # which is counted dropped (the second window always holds one)
    assert dev["counts"] == [
        int(topic_of(s) != f"fanout/t{PAIRS}") for s in range(n + 7)
    ]
    assert dev["metrics"]["messages.dropped.no_subscribers"] == dropped > 0
    assert dev["metrics"]["messages.dropped"] == dropped
    assert dev["metrics"]["messages.acked"] == sum(acks.values())
    assert dev["metrics"]["delivery.dropped"] == 0
    for cid, sub in dev["subs"].items():
        pkts = sub.publishes()
        assert all(p.type == C.PUBLISH and p.qos == 1 for p in pkts)
        assert all(p.topic == f"fanout/t{cid[3:]}" for p in pkts)
        # the publisher's stream in its order, packet ids in that order
        assert [int(p.payload[SEQ]) for p in pkts] == wire[cid]
        assert [p.packet_id for p in pkts] == list(
            range(1, len(wire[cid]) + 1))
        assert dev["inflight"][cid] == list(
            range(acks[cid] + 1, len(wire[cid]) + 1))
        assert dev["queued"][cid] == len(owed[cid]) - len(wire[cid])
    if n >= 512:
        # the shipped window is full and the queue behind it holds
        assert all(len(v) == 32 for v in dev["inflight"].values())
        assert all(q > 0 for q in dev["queued"].values())


# ------------------------------------------------------ over a socket

async def serve_pairs(publishes_each):
    cfg = BrokerConfig()
    cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
    cfg.engine.use_device = True
    srv = BrokerServer(cfg)
    await srv.start()
    port = srv.listeners[0].port
    subs = [TestClient(port, f"sub{j}") for j in range(PAIRS)]
    pubs = [TestClient(port, f"pub{k}") for k in range(PUBLISHERS)]
    got = [[] for _ in subs]
    try:
        for j, c in enumerate(subs):
            await c.connect()
            ack = await c.subscribe(f"fanout/t{j}", qos=1)
            assert list(ack.reason_codes) == [1]
        for p in pubs:
            await p.connect()
        lc = srv.broker.profiler.loop
        srv.broker.profiler.reset()
        base = {f: getattr(lc, f) for f in LoopClock.FIELDS}
        acks = []
        for r in range(publishes_each):
            for k, p in enumerate(pubs):
                seq = r * PUBLISHERS + k
                p.writer.write(C.serialize(C.Publish(
                    topic=topic_of(seq), qos=1, packet_id=r + 1,
                    payload=traffic.payload_of(seq)), C.MQTT_V5))
            for p in pubs:
                acks.append(await p.expect(C.PUBACK, timeout=30))
            for j, c in enumerate(subs):
                pkt = await c.expect(C.PUBLISH, timeout=30)
                got[j].append((pkt.qos, int(pkt.payload[SEQ])))
                await c.send(C.Puback(packet_id=pkt.packet_id))
        await asyncio.sleep(0.2)
        # a read that holds a PUBACK and a PUBLISH is neither's
        c = subs[0]
        c.writer.write(
            C.serialize(C.Puback(packet_id=60000), C.MQTT_V5)
            + C.serialize(C.Publish(topic="fanout/none", qos=0,
                                    payload=b"x"), C.MQTT_V5))
        await c.writer.drain()
        await asyncio.sleep(0.2)
        grown = {f: getattr(lc, f) - base[f] for f in LoopClock.FIELDS}
        dropped = srv.broker.metrics.val("messages.dropped.no_subscribers")
        stats = srv.broker.router.engine.stats()
    finally:
        for c in subs + pubs:
            if c.writer is not None:
                c.writer.close()
        await srv.stop()
    return got, acks, grown, dropped, stats


def test_served_pairs_and_the_publisher_nobody_hears():
    rounds = 6
    got, acks, grown, dropped, stats = run(serve_pairs(rounds))
    # every publish acknowledged, the orphan's too; each subscriber got
    # its publisher's stream at QoS1 in order; the orphan's publishes
    # (and the one QoS0 stray) are counted dropped
    assert len(acks) == rounds * PUBLISHERS
    for j, mine in enumerate(got):
        assert mine == [(1, r * PUBLISHERS + j) for r in range(rounds)]
    assert dropped >= rounds + 1
    assert stats["decide_dev_windows"] > 0
    assert stats["decide_host_windows"] == 0
    # the loop's clock by packet type: a publisher connection's reads
    # hold PUBLISH alone, a subscriber's PUBACK alone
    assert grown["ingress_publishes"] == rounds * PUBLISHERS + 1
    assert grown["ingress_acks"] == rounds * PAIRS + 1
    assert 0 < grown["ingress_publish_reads"] <= rounds * PUBLISHERS
    assert 0 < grown["ingress_ack_reads"] <= rounds * PAIRS
    assert (grown["ingress_publish_reads"] + grown["ingress_ack_reads"]
            < grown["ingress_reads"])
    assert grown["ingress_publish_s"] > 0 and grown["ingress_ack_s"] > 0
    assert (grown["ingress_publish_s"] + grown["ingress_ack_s"]
            < grown["ingress_s"])


# ------------------------------------------------ the split, by itself

READS = {
    # name: (packets, publishes, acks, acks_run) -> which part grows
    "publishes_only": ((3, 3, 0, 0), "publish"),
    "one_publish": ((1, 1, 0, 0), "publish"),
    "acks_only": ((5, 0, 5, 0), "ack"),
    "an_ack_run": ((18, 0, 18, 18), "ack"),
    "publish_and_ack": ((2, 1, 1, 0), None),
    "ack_and_pingreq": ((2, 0, 1, 0), None),
    "connect_alone": ((1, 0, 0, 0), None),
    "publish_and_subscribe": ((2, 1, 0, 0), None),
    "a_partial_frame": ((0, 0, 0, 0), None),
}


@pytest.mark.parametrize("name", sorted(READS))
def test_ingress_split_by_packet_type(name, monkeypatch):
    (packets, publishes, acks, acks_run), part = READS[name]
    ticks = []
    real = observability.time.perf_counter

    def counted():
        ticks.append(1)
        return real()

    lc = LoopClock()
    t0 = real() - 250e-6
    monkeypatch.setattr(observability.time, "perf_counter", counted)
    lc.ingress(t0, packets, publishes, acks, acks_run)
    monkeypatch.undo()
    assert len(ticks) == 1  # the split reads no clock of its own
    assert lc.ingress_reads == 1 and lc.ingress_s >= 250e-6
    assert (lc.ingress_publishes, lc.ingress_acks,
            lc.ingress_acks_run) == (publishes, acks, acks_run)
    want = {"publish": (lc.ingress_s, 1, 0.0, 0),
            "ack": (0.0, 0, lc.ingress_s, 1),
            None: (0.0, 0, 0.0, 0)}[part]
    assert (lc.ingress_publish_s, lc.ingress_publish_reads,
            lc.ingress_ack_s, lc.ingress_ack_reads) == want


def test_ingress_split_reaches_the_ring_and_adds_up():
    prof = observability.Profiler(ring_size=16)
    lc = prof.loop
    real = observability.time.perf_counter
    for (packets, publishes, acks, acks_run), _ in READS.values():
        lc.ingress(real() - 100e-6, packets, publishes, acks, acks_run)
    prof.commit(prof.begin(1))
    (win,) = prof.windows(1)
    assert win["loop_ingress_reads"] == len(READS)
    assert win["loop_ingress_publish_reads"] == 2
    assert win["loop_ingress_ack_reads"] == 2
    assert win["loop_ingress_publish_us"] >= 200
    assert win["loop_ingress_ack_us"] >= 200
    assert (win["loop_ingress_publish_us"] + win["loop_ingress_ack_us"]
            <= win["loop_ingress_us"] - 500 + 0.3)
    # what the two per-packet metrics divide by is every packet of its
    # type, a mixed read's too: the parts never overstate a packet
    assert win["loop_ingress_publishes"] == 6
    assert win["loop_ingress_acks"] == 25
