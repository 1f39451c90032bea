"""Ack runs against the scalar path they replace: the same seeded byte
stream, cut into the same socket reads, is fed to one served channel
through ``StreamParser(ack_runs=True)`` (an `AckRun` a run, one
`Session.puback_run`) and to another packet by packet through the
scalar `handle_in`.  Everything either leaves behind has to be equal:
the bytes written to the socket, the inflight window in order, the
queue, the packet-id cursor, every counter, the hook's calls."""

import random
import struct

import pytest

from emqx_tpu.broker.broker import Broker
from emqx_tpu.broker.channel import CONNECTED, CONNECTING, Channel
from emqx_tpu.codec import mqtt as C
from emqx_tpu.config import BrokerConfig
from emqx_tpu.message import Message
from emqx_tpu.metrics import METRICS

CLIENT = "acker"


def ack4(pid):
    """The minimal PUBACK, hand-made: ``40 02 hi lo``."""
    return bytes((0x40, 0x02, pid >> 8, pid & 0xFF))


class Served:
    """A real `Channel` behind a capturing transport, read the way
    `Connection.run` reads: feed, `handle_in` each thing the parser
    yields, stop when the channel closed."""

    def __init__(self, ack_runs, version, max_inflight, hook):
        cfg = BrokerConfig()
        cfg.mqtt.max_inflight = max_inflight
        cfg.mqtt.max_mqueue_len = 10000
        self.broker = Broker(cfg)
        self.version = version
        self.wire = bytearray()
        self.writes = 0
        self.closed = None
        self.acked = []
        self.yielded = []
        if hook:
            self.broker.hooks.add(
                "message.acked",
                lambda clientid, pid: self.acked.append((clientid, pid)),
            )
        self.channel = Channel(self.broker, send=self._send,
                               close=self._close)
        self.parser = C.StreamParser(ack_runs=ack_runs)

    def _send(self, pkts):
        self.writes += 1
        self.wire += b"".join(
            C.serialize(p, self.channel.version) for p in pkts
        )

    def _close(self, reason):
        self.closed = reason

    def read(self, data):
        for pkt in self.parser.feed(data):
            self.yielded.append(type(pkt).__name__)
            self.channel.handle_in(pkt)
            if self.closed is not None:
                break

    def connect(self):
        sub = C.Subscribe(packet_id=1, subscriptions=[
            C.Subscription("q1/#", qos=1), C.Subscription("q2/#", qos=2),
            C.Subscription("q0/#", qos=0),
        ])
        self.read(C.serialize(C.Connect(client_id=CLIENT,
                                        proto_ver=self.version),
                              self.version)
                  + C.serialize(sub, self.version))
        assert self.channel.state == CONNECTED

    def state(self):
        s = self.channel.session
        m = self.broker.metrics
        return {
            "wire": bytes(self.wire),
            "closed": self.closed,
            "inflight": [
                (pid, e.phase, e.qos,
                 None if e.msg is None else (e.msg.topic, e.msg.payload))
                for pid, e in (s.inflight.items() if s else [])
            ],
            "mqueue": [
                (band, [(q.topic, q.payload, q.qos) for q in dq])
                for band, dq in sorted(s.mqueue._bands.items())
            ] if s else None,
            "next_pid": s._next_pid if s else None,
            "out_parked": s.out_parked if s else None,
            "metrics": {n: m.val(n) for n in METRICS},
            "extra": dict(m._extra),
            "acked": list(self.acked),
        }


def segment(rng, ref, version, seq):
    """One stretch of client bytes, drawn against the scalar side's
    window as it stands: four-byte PUBACKs of ids known, unknown,
    repeated, of QoS2 entries and of ids a follow-up may get; between
    them whatever ends a run."""
    s = ref.channel.session
    q1 = [pid for pid, e in s.inflight.items() if e.qos == 1]
    q2 = [pid for pid, e in s.inflight.items() if e.qos == 2]
    rng.shuffle(q1)
    frames = []
    used = []
    for _ in range(rng.randint(1, 40)):
        roll = rng.random()
        if roll < 0.50 and q1:
            pid = q1.pop() if rng.random() < 0.5 else q1.pop(0)
            used.append(pid)
            frames.append(ack4(pid))
        elif roll < 0.58 and used:
            frames.append(ack4(rng.choice(used)))  # repeated
        elif roll < 0.66:
            frames.append(ack4(rng.randint(1, 65535)))  # mostly unknown
        elif roll < 0.72 and q2:
            frames.append(ack4(rng.choice(q2)))  # a QoS2 entry: not known
        elif roll < 0.80:
            # an id a follow-up of this very stretch may be given
            frames.append(ack4((s._next_pid + rng.randint(0, 5)) % 65535 + 1))
        elif roll < 0.84 and version == C.MQTT_V5 and q1:
            pid = q1.pop()
            used.append(pid)
            frames.append(bytes((0x40, 0x03, pid >> 8, pid & 0xFF, 0x00)))
        elif roll < 0.88 and q2:
            pid = q2.pop()
            frames.append(C.serialize(C.Pubrec(packet_id=pid), version))
            if rng.random() < 0.7:
                frames.append(C.serialize(C.Pubcomp(packet_id=pid), version))
        elif roll < 0.92:
            frames.append(C.serialize(C.Pingreq(), version))
        else:
            seq[0] += 1
            qos = rng.choice((0, 1))
            frames.append(C.serialize(C.Publish(
                topic=rng.choice(("q1/in", "q0/in", "nobody/in")),
                payload=b"in%d" % seq[0], qos=qos,
                packet_id=seq[0] % 60000 + 1 if qos else None,
            ), version))
    return frames


def cut(rng, frames):
    """Socket reads of the frames' bytes: cuts at frame boundaries,
    inside frames, and at each offset inside a four-byte ack."""
    data = b"".join(frames)
    marks = set()
    pos = 0
    for f in frames:
        if rng.random() < 0.15:
            marks.add(pos)
        if len(f) == 4 and rng.random() < 0.2:
            marks.add(pos + rng.randint(1, 3))
        elif rng.random() < 0.1:
            marks.add(pos + rng.randrange(len(f)))
        pos += len(f)
    edges = [0] + sorted(m for m in marks if 0 < m < len(data)) + [len(data)]
    return [data[a:b] for a, b in zip(edges, edges[1:]) if b > a]


@pytest.mark.parametrize("version", [C.MQTT_V4, C.MQTT_V5],
                         ids=["v4", "v5"])
@pytest.mark.parametrize("hook", [False, True], ids=["nohook", "hook"])
@pytest.mark.parametrize("max_inflight", [4096, 5],
                         ids=["mqueue_empty", "mqueue_backlog"])
@pytest.mark.parametrize("seed", [11, 2400000931, 3000000933])
def test_runs_leave_what_the_scalar_path_leaves(seed, max_inflight, hook,
                                                version):
    rng = random.Random(seed)
    runs = Served(True, version, max_inflight, hook)
    ref = Served(False, version, max_inflight, hook)
    for side in (runs, ref):
        side.connect()
    seq = [0]
    n_reads = 0
    for _ in range(30):
        # deliveries owed to the client, the same on both sides
        for _ in range(rng.randint(0, 14)):
            seq[0] += 1
            qos = rng.choice((1, 1, 1, 2, 0))
            for side in (runs, ref):
                side.broker.publish(Message(
                    topic=f"q{qos}/out", payload=b"out%d" % seq[0], qos=qos,
                ))
        if rng.random() < 0.4 and len(ref.channel.session.inflight):
            # the cursor as a wrap of the 65,535 ids leaves it: behind
            # ids still in flight, which `_alloc_packet_id` has to skip
            pid = rng.choice([p for p, _ in ref.channel.session.inflight.items()])
            behind = (pid - rng.randint(1, 3)) % 65535
            for side in (runs, ref):
                side.channel.session._next_pid = behind
        for data in cut(rng, segment(rng, ref, version, seq)):
            runs.read(data)
            ref.read(data)
            n_reads += 1
            assert runs.state() == ref.state(), (seed, n_reads)
    assert runs.closed is None
    # the comparison compared something: acks were known and unknown,
    # runs did cross as runs, and the scalar side never saw one
    m = ref.broker.metrics
    assert m.val("messages.acked") > 20
    assert m.val("packets.puback.received") > m.val("messages.acked") + 20
    assert "AckRun" in runs.yielded and "AckRun" not in ref.yielded
    assert runs.yielded.count("Puback") < ref.yielded.count("Puback")
    # a run's follow-ups leave in one write, the scalar side's ack by ack
    assert runs.writes <= ref.writes
    if max_inflight == 5:
        assert m.val("packets.publish.sent") > 100 and runs.writes < ref.writes
    if hook:
        # (PUBCOMP counts as acked too, and runs no hook)
        assert 20 < len(ref.acked) <= m.val("messages.acked")


@pytest.mark.parametrize("cursor,ids", [
    (5, [6, 7, 8, 9, 10]),
    # the cursor behind the window, as after a wrap: the first
    # follow-up gets the id its ack freed, so the run's second ack of 3
    # is known, and deleting the run's ids first would hand out 1..5
    (0, [3, 6, 7, 8, 9, 10]),
])
def test_a_backlogged_run_gives_the_ids_the_scalar_walk_gives(cursor, ids):
    """Behind a full window of five, acks release queued deliveries:
    the packet ids those get skip what is still in flight, so the run
    walks id by id there, and its follow-ups leave in one write."""
    sides = [Served(flag, C.MQTT_V4, 5, False) for flag in (True, False)]
    for side in sides:
        side.connect()
        for i in range(12):
            side.broker.publish(
                Message(topic="q1/out", payload=b"%d" % i, qos=1))
        side.wire.clear()
        side.writes = 0
        side.channel.session._next_pid = cursor
        side.read(b"".join(ack4(p) for p in (3, 1, 9, 3, 5, 2, 4)))
    runs, ref = sides
    assert runs.state() == ref.state()
    sent = list(C.StreamParser(version=C.MQTT_V4).feed(bytes(runs.wire)))
    assert [p.packet_id for p in sent] == ids
    assert [p.payload for p in sent] == [b"%d" % i
                                         for i in range(5, 5 + len(ids))]
    assert runs.writes == 1 and ref.writes == len(ids)
    assert len(runs.channel.session.mqueue) == 7 - len(ids)


@pytest.mark.parametrize("ack_runs", [True, False], ids=["runs", "scalar"])
def test_acks_before_connect_are_a_protocol_error(ack_runs):
    side = Served(ack_runs, C.MQTT_V4, 32, False)
    side.read(ack4(1) + ack4(2) + ack4(3))
    assert side.closed == "protocol_error"
    # the first packet is the error; what follows it is never handled
    assert side.broker.metrics.val("packets.received") == 1
    assert side.broker.metrics.val("packets.puback.received") == 0


@pytest.mark.parametrize("n,closed", [(5, None),
                                      (70, "connect_backlog_overflow")])
def test_a_run_while_connect_resolves_joins_the_backlog_as_packets(n, closed):
    sides = [Served(flag, C.MQTT_V5, 32, False) for flag in (True, False)]
    for side in sides:
        assert side.channel.state == CONNECTING
        side.channel._pending_connect = object()  # CONNECT still resolving
        side.read(b"".join(ack4(i + 1) for i in range(n)))
        backlog = side.channel._connect_backlog
        assert len(backlog) == min(n, 64)
        assert all(isinstance(p, C.Puback) for p in backlog)
        assert [p.packet_id for p in backlog] == list(range(1, len(backlog) + 1))
        assert side.closed == closed
    runs, ref = sides
    assert runs.yielded == ["AckRun"] and ref.yielded == ["Puback"] * min(n, 65)
    assert ({k: runs.broker.metrics.val(k) for k in METRICS}
            == {k: ref.broker.metrics.val(k) for k in METRICS})


# ------------------------------------------------------------ the codec


def test_feed_without_the_option_still_yields_pubacks():
    data = b"".join(ack4(i) for i in (1, 2, 3))
    got = list(C.StreamParser(version=C.MQTT_V4).feed(data))
    assert [type(p) for p in got] == [C.Puback] * 3
    assert [p.packet_id for p in got] == [1, 2, 3]


def test_a_run_ends_at_whatever_is_not_a_minimal_puback():
    v5 = C.MQTT_V5
    stream = [
        (ack4(1) + ack4(0xFFFF) + ack4(0x4002), ("run", (1, 0xFFFF, 0x4002))),
        (bytes((0x40, 0x03, 0, 9, 0x10)), ("Puback", 9)),  # reason code
        (ack4(7), ("run", (7,))),  # a run of one is a run
        (C.serialize(C.Pubrec(packet_id=4), v5), ("Pubrec", 4)),
        (C.serialize(C.Pubrel(packet_id=4), v5), ("Pubrel", 4)),
        (C.serialize(C.Pubcomp(packet_id=4), v5), ("Pubcomp", 4)),
        (ack4(2) + ack4(3), ("run", (2, 3))),
        (C.serialize(C.Pingreq(), v5), ("Pingreq", None)),
        (bytes((0x40, 0x04, 0, 5, 0x00, 0x00)), ("Puback", 5)),  # properties
        (ack4(8) + ack4(8), ("run", (8, 8))),
    ]
    got = []
    for pkt in C.StreamParser(version=v5, ack_runs=True).feed(
            b"".join(b for b, _ in stream)):
        if isinstance(pkt, C.AckRun):
            assert pkt.type == C.ACK_RUN
            assert [p.packet_id for p in pkt.packets()] == list(pkt.packet_ids)
            got.append(("run", tuple(pkt.packet_ids)))
        else:
            got.append((type(pkt).__name__, getattr(pkt, "packet_id", None)))
    assert got == [want for _, want in stream]


@pytest.mark.parametrize("k", [1, 2, 18, 255, 256, 257, 600, 4000])
def test_run_lengths_across_the_scan_chunk(k):
    ids = [(i * 7919) % 65535 + 1 for i in range(k)]
    tail = C.serialize(C.Pingreq(), C.MQTT_V4)
    run, ping = C.StreamParser(ack_runs=True).feed(
        struct.pack(">" + "BBH" * k, *(v for i in ids for v in (0x40, 2, i)))
        + tail)
    assert list(run.packet_ids) == ids and isinstance(ping, C.Pingreq)


@pytest.mark.parametrize("seed", range(6))
def test_a_partial_frame_stays_buffered_whatever_the_cut(seed):
    """The ids come out once each and in order however the bytes are
    cut into reads, a run's last frame split at every offset."""
    rng = random.Random(seed)
    ids = [rng.randint(1, 65535) for _ in range(200)]
    data = b"".join(ack4(i) for i in ids)
    parser = C.StreamParser(ack_runs=True)
    got, pos = [], 0
    while pos < len(data):
        step = rng.choice((1, 2, 3, 4, 5, 6, 7, 9, 13, 41))
        for pkt in parser.feed(data[pos:pos + step]):
            assert isinstance(pkt, C.AckRun) and len(pkt.packet_ids) >= 1
            got += pkt.packet_ids
        pos += step
    assert got == ids


def test_malformed_ack_shapes_fail_as_they_did():
    for bad in (bytes((0x41, 0x02, 0, 1)),      # flags on a PUBACK
                bytes((0x40, 0x01, 0)),         # no room for an id
                ack4(1) + bytes((0x42, 0x02, 0, 2))):
        for ack_runs in (True, False):
            with pytest.raises(C.MqttError):
                list(C.StreamParser(ack_runs=ack_runs).feed(bad))
    # a packet size limit under four bytes refuses the frame either way
    for ack_runs in (True, False):
        with pytest.raises(C.MqttError):
            list(C.StreamParser(max_packet_size=3,
                                ack_runs=ack_runs).feed(ack4(1)))


@pytest.mark.parametrize("seed", range(8))
def test_fuzzed_bytes_parse_alike_with_and_without_runs(seed):
    """Random bytes salted with ack-shaped fragments, cut into random
    reads: with a run expanded into its packets both parsers yield the
    same packets, and fail at the same point with the same error."""
    rng = random.Random(seed)
    salt = [b"\x40\x02", b"\x40\x02\x00\x01", b"\x40", b"\x40\x03\x00\x01\x00",
            b"\x50\x02\x00\x07", b"\xc0\x00", b"\x40\x02\x40\x02"]
    for _ in range(60):
        data = b"".join(
            rng.choice(salt) if rng.random() < 0.8
            else bytes(rng.randrange(256) for _ in range(rng.randint(1, 6)))
            for _ in range(rng.randint(1, 50))
        )
        cuts = sorted(rng.sample(range(len(data) + 1),
                                 min(len(data) + 1, rng.randint(0, 6))))
        reads = [data[a:b] for a, b in zip([0] + cuts, cuts + [len(data)])]
        seen = []
        for ack_runs in (True, False):
            parser = C.StreamParser(ack_runs=ack_runs)
            got = []
            try:
                for chunk in reads:
                    for pkt in parser.feed(chunk):
                        got += pkt.packets() if isinstance(pkt, C.AckRun) \
                            else [pkt]
            except C.MqttError as e:
                got.append(("error", str(e), e.reason_code))
            seen.append(got)
        assert seen[0] == seen[1], data.hex()
