"""`benchmark/loadgen.py` carries its own MQTT 5 codec so that the
program's codec cannot move the yardstick.  Held here against
`emqx_tpu.codec` in both directions, every packet type it uses, so the
independent copy is not a private dialect."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import loadgen as L  # noqa: E402

from emqx_tpu.codec import mqtt as C  # noqa: E402

V5 = C.MQTT_V5


def parse_one(data: bytes):
    pkts = list(C.StreamParser(version=V5).feed(data))
    assert len(pkts) == 1
    return pkts[0]


def test_connect_is_read_by_the_program():
    pkt = parse_one(L.connect("sub17"))
    assert pkt.type == C.CONNECT and pkt.client_id == "sub17"
    assert pkt.proto_ver == V5 and pkt.keepalive == 0 and pkt.clean_start


@pytest.mark.parametrize("qos", [0, 1])
def test_subscribe_is_read_by_the_program(qos):
    pkt = parse_one(L.subscribe(7, ["a/+/b", "c/#"], qos))
    assert pkt.type == C.SUBSCRIBE and pkt.packet_id == 7
    assert [(s.topic_filter, s.qos) for s in pkt.subscriptions] == [
        ("a/+/b", qos), ("c/#", qos)
    ]


@pytest.mark.parametrize("filters", [["a/+/b"], ["vehicles/v7/sensors/#",
                                                   "site/+/floor/f3/#"]])
def test_unsubscribe_is_read_by_the_program(filters):
    pkt = parse_one(L.unsubscribe(65535, filters))
    assert pkt.type == C.UNSUBSCRIBE and pkt.packet_id == 65535
    assert pkt.topic_filters == filters


@pytest.mark.parametrize("filters", [["a/+/b"], ["c/#", "d/x"]])
def test_program_unsubscribe_is_the_generators_bytes(filters):
    wire = C.serialize(C.Unsubscribe(packet_id=300, topic_filters=filters),
                       V5)
    assert wire == L.unsubscribe(300, filters)


@pytest.mark.parametrize("codes", [[0], [0, 0x11]])
def test_program_unsuback_is_read_by_the_generator(codes):
    wire = C.serialize(C.Unsuback(packet_id=4097, reason_codes=codes), V5)
    (first, p, end), = L.split(wire)[0]
    assert first >> 4 == L.UNSUBACK
    assert L.parse_suback(wire, p, end) == (4097, codes)


@pytest.mark.parametrize("seq", [0, 12345, 2 ** 31 + 5])
def test_publish_is_read_by_the_program(seq):
    payload = L.payload_of(seq)
    pkt = parse_one(L.publish("vehicles/v1/sensors/temp", 1, 513, payload))
    assert pkt.type == C.PUBLISH and pkt.qos == 1 and pkt.packet_id == 513
    assert pkt.topic == "vehicles/v1/sensors/temp"
    assert bytes(pkt.payload) == payload
    assert int(payload[L.SEQ_AT:L.SEQ_AT + L.SEQ_W]) == seq


def test_publish_head_plus_tail_is_the_whole_packet():
    payload = L.payload_of(9)
    head = L.publish_head("t/x", 1, len(payload))
    assert head + (5).to_bytes(2, "big") + b"\x00" + payload \
        == L.publish("t/x", 1, 5, payload)


def test_puback_and_disconnect_are_read_by_the_program():
    pkt = parse_one(L.puback(65535))
    assert pkt.type == C.PUBACK and pkt.packet_id == 65535
    assert parse_one(L.disconnect()).type == C.DISCONNECT


@pytest.mark.parametrize("qos", [0, 1])
def test_program_publish_is_read_by_the_generator(qos):
    payload = L.payload_of(77)
    wire = C.serialize(C.Publish(
        topic="site/s1/floor/f2/a", payload=payload, qos=qos,
        packet_id=9 if qos else None,
    ), V5)
    pkts, rest = L.split(wire + wire[:3])
    assert len(pkts) == 1 and rest == wire[:3]
    first, p, end = pkts[0]
    topic, q, dup, pid, body = L.parse_publish(wire, first, p, end)
    assert (topic, q, dup, body) == (b"site/s1/floor/f2/a", qos, 0, payload)
    assert pid == (9 if qos else 0)


def test_program_acks_are_read_by_the_generator():
    wire = C.serialize(C.Suback(packet_id=1, reason_codes=[0, 1, 1]), V5)
    (first, p, end), = L.split(wire)[0]
    assert first >> 4 == L.SUBACK
    assert L.parse_suback(wire, p, end) == (1, [0, 1, 1])
    wire = C.serialize(C.Puback(packet_id=300), V5)
    (first, p, end), = L.split(wire)[0]
    assert first >> 4 == L.PUBACK and wire[p] << 8 | wire[p + 1] == 300
    wire = C.serialize(C.Connack(reason_code=0), V5)
    (first, p, end), = L.split(wire)[0]
    assert first >> 4 == L.CONNACK and wire[p + 1] == 0


def test_hand_made_spec_bytes():
    # MQTT 5 section 3.4: PUBACK, remaining length 2, packet id 0x0102
    assert L.puback(0x0102) == bytes([0x40, 0x02, 0x01, 0x02])
    # section 3.3: PUBLISH QoS1 "a" id 1, no properties, payload "x"
    assert L.publish("a", 1, 1, b"x") == bytes(
        [0x32, 0x07, 0x00, 0x01, 0x61, 0x00, 0x01, 0x00, 0x78]
    )
    assert L.varint(321) == bytes([0xC1, 0x02])
    # section 3.10: UNSUBSCRIBE "a" id 1, no properties
    assert L.unsubscribe(1, ["a"]) == bytes(
        [0xA2, 0x06, 0x00, 0x01, 0x00, 0x00, 0x01, 0x61]
    )
    # section 3.11: UNSUBACK id 1, no properties, success; both codecs
    # read the same answer from it
    unsuback = bytes([0xB0, 0x04, 0x00, 0x01, 0x00, 0x00])
    pkt = parse_one(unsuback)
    assert pkt.type == C.UNSUBACK and pkt.packet_id == 1
    assert pkt.reason_codes == [0]
    (first, p, end), = L.split(unsuback)[0]
    assert first >> 4 == L.UNSUBACK
    assert L.parse_suback(unsuback, p, end) == (1, [0])


def test_split_keeps_an_unfinished_tail():
    a, b = L.puback(1), L.publish("t", 0, 0, b"p" * 200)
    data = a + b
    for cut in range(1, len(data)):
        pkts, rest = L.split(data[:cut])
        more, rest2 = L.split(rest + data[cut:])
        assert len(pkts) + len(more) == 2 and rest2 == b""
