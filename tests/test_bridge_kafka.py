"""Kafka producer bridge (emqx_tpu/kafka.py) against an in-repo fake
Kafka broker speaking the real wire protocol (Metadata v1 + Produce v3
with magic-2 record batches) — the reference's flagship integration
(/root/reference/apps/emqx_bridge_kafka/src/emqx_bridge_kafka.erl)
proven at the resource/buffer-worker depth: batching, partitioning,
retriable-error recovery, and backpressure."""

import asyncio
import struct

from emqx_tpu.kafka import (
    KafkaClient,
    KafkaProducerResource,
    crc32c,
    decode_batch_record_count,
    encode_record_batch,
    murmur2,
)
from emqx_tpu.resources import BufferWorker


def run(coro):
    return asyncio.run(coro)


def _string(s):
    if s is None:
        return struct.pack(">h", -1)
    b = s.encode()
    return struct.pack(">h", len(b)) + b


class FakeKafka:
    """Minimal broker: leader of every partition of every topic.
    Knobs: ``fail_partition`` (error code, n_times) injection and a
    ``stall_produce`` event to wedge produce handling."""

    def __init__(self, n_partitions=2):
        self.n_partitions = n_partitions
        self.server = None
        self.port = 0
        self.records = {}  # (topic, partition) -> [batch bytes]
        self.produce_count = 0
        self.fail = {}  # partition -> [error_code, remaining]
        self.stalled = False

    async def start(self):
        self.server = await asyncio.start_server(
            self._conn, "127.0.0.1", 0
        )
        self.port = self.server.sockets[0].getsockname()[1]

    async def stop(self):
        self.server.close()
        await self.server.wait_closed()

    def total_records(self):
        return sum(
            decode_batch_record_count(b)
            for batches in self.records.values()
            for b in batches
        )

    async def _conn(self, r, w):
        try:
            while True:
                raw = await r.readexactly(4)
                (size,) = struct.unpack(">i", raw)
                req = await r.readexactly(size)
                api, ver, corr = struct.unpack_from(">hhi", req, 0)
                off = 8
                (cl,) = struct.unpack_from(">h", req, off)
                off += 2 + max(cl, 0)
                if api == 3:
                    resp = self._metadata(req, off)
                elif api == 0:
                    if self.stalled:
                        await asyncio.sleep(30)
                        continue
                    resp = self._produce(req, off)
                else:
                    continue
                payload = struct.pack(">i", corr) + resp
                w.write(struct.pack(">i", len(payload)) + payload)
                await w.drain()
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            w.close()

    def _metadata(self, req, off):
        (n,) = struct.unpack_from(">i", req, off)
        off += 4
        topics = []
        for _ in range(n):
            (ln,) = struct.unpack_from(">h", req, off)
            off += 2
            topics.append(req[off:off + ln].decode())
            off += ln
        out = bytearray()
        out += struct.pack(">i", 1)  # one broker: us
        out += struct.pack(">i", 0) + _string("127.0.0.1")
        out += struct.pack(">i", self.port) + _string(None)
        out += struct.pack(">i", 0)  # controller
        out += struct.pack(">i", len(topics))
        for t in topics:
            out += struct.pack(">h", 0) + _string(t) + b"\x00"
            out += struct.pack(">i", self.n_partitions)
            for p in range(self.n_partitions):
                out += struct.pack(">h", 0)   # partition error
                out += struct.pack(">i", p)   # partition id
                out += struct.pack(">i", 0)   # leader = broker 0
                out += struct.pack(">ii", 1, 0)  # replicas [0]
                out += struct.pack(">ii", 1, 0)  # isr [0]
        return bytes(out)

    def _produce(self, req, off):
        self.produce_count += 1
        (tx,) = struct.unpack_from(">h", req, off)
        off += 2 + max(tx, 0)
        _acks, _tmo = struct.unpack_from(">hi", req, off)
        off += 6
        (n_topics,) = struct.unpack_from(">i", req, off)
        off += 4
        results = []
        for _ in range(n_topics):
            (ln,) = struct.unpack_from(">h", req, off)
            off += 2
            topic = req[off:off + ln].decode()
            off += ln
            (n_parts,) = struct.unpack_from(">i", req, off)
            off += 4
            parts = []
            for _ in range(n_parts):
                (pid,) = struct.unpack_from(">i", req, off)
                off += 4
                (blen,) = struct.unpack_from(">i", req, off)
                off += 4
                batch = req[off:off + blen]
                off += blen
                err = 0
                inj = self.fail.get(pid)
                if inj and inj[1] > 0:
                    err, inj[1] = inj[0], inj[1] - 1
                else:
                    self.records.setdefault(
                        (topic, pid), []
                    ).append(batch)
                parts.append((pid, err))
            results.append((topic, parts))
        out = bytearray()
        out += struct.pack(">i", len(results))
        for topic, parts in results:
            out += _string(topic)
            out += struct.pack(">i", len(parts))
            for pid, err in parts:
                out += struct.pack(">ihqq", pid, err, 0, -1)
        out += struct.pack(">i", 0)  # throttle
        return bytes(out)


# ----------------------------------------------------------- unit bits

def test_crc32c_vectors():
    # RFC 3720 test vectors
    assert crc32c(b"") == 0
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(bytes(32)) == 0x8A9136AA


def test_record_batch_shape():
    batch = encode_record_batch([(b"k1", b"v1"), (None, b"v2")])
    assert decode_batch_record_count(batch) == 2
    # crc covers attributes..end and must verify
    crc_off = 8 + 4 + 4 + 1
    (crc,) = struct.unpack_from(">I", batch, crc_off)
    assert crc == crc32c(batch[crc_off + 4:])
    # magic 2
    assert batch[8 + 4 + 4] == 2


def test_murmur2_is_stable_and_spreads():
    vals = {murmur2(f"key-{i}".encode()) % 8 for i in range(64)}
    assert len(vals) >= 4  # spreads over partitions
    assert murmur2(b"abc") == murmur2(b"abc")


# ------------------------------------------------------------- e2e path

def test_produce_end_to_end_with_keys():
    async def t():
        fk = FakeKafka(n_partitions=3)
        await fk.start()
        res = KafkaProducerResource(
            [("127.0.0.1", fk.port)], topic="mqtt-data"
        )
        worker = BufferWorker(res, health_interval=0.2)
        await worker.start()
        assert worker.status == "connected"
        for i in range(100):
            # half keyed (stable partition), half round-robin
            if i % 2:
                worker.enqueue((f"dev-{i % 5}", f"payload-{i}"))
            else:
                worker.enqueue(f"payload-{i}")
        deadline = asyncio.get_event_loop().time() + 5
        while asyncio.get_event_loop().time() < deadline:
            if fk.total_records() >= 100:
                break
            await asyncio.sleep(0.05)
        assert fk.total_records() == 100
        assert res.stats["produced"] == 100
        # all records of one key land in ONE partition
        key_part = murmur2(b"dev-1") % 3
        assert ("mqtt-data", key_part) in fk.records
        await worker.stop()
        await fk.stop()

    run(t())


def test_retriable_partition_error_recovers_without_loss():
    async def t():
        fk = FakeKafka(n_partitions=2)
        await fk.start()
        fk.fail[0] = [6, 2]  # NOT_LEADER twice for partition 0
        res = KafkaProducerResource(
            [("127.0.0.1", fk.port)], topic="t"
        )
        worker = BufferWorker(res, health_interval=0.1)
        await worker.start()
        for i in range(40):
            worker.enqueue((f"k{i % 8}", f"m{i}"))
        deadline = asyncio.get_event_loop().time() + 8
        while asyncio.get_event_loop().time() < deadline:
            if fk.total_records() >= 40:
                break
            await asyncio.sleep(0.05)
        # exactly-once per record at the fake: no loss, no duplicates
        assert fk.total_records() == 40
        assert res.stats["partition_retries"] > 0
        assert res.stats["abandoned"] == 0
        await worker.stop()
        await fk.stop()

    run(t())


def test_backpressure_bounded_buffer_drops_oldest():
    async def t():
        fk = FakeKafka(n_partitions=1)
        await fk.start()
        res = KafkaProducerResource([("127.0.0.1", fk.port)], topic="t")
        worker = BufferWorker(res, max_buffer=50, health_interval=0.2)
        await worker.start()
        fk.stalled = True  # sink wedged: buffer takes the pressure
        await asyncio.sleep(0.1)
        for i in range(300):
            worker.enqueue(f"m{i}")
        assert len(worker) <= 51  # bounded (one may be in flight)
        assert worker.stats["dropped"] >= 240
        fk.stalled = False
        # the stalled produce's connection is wedged ~30s; the worker's
        # retry path reconnects and drains the surviving tail
        deadline = asyncio.get_event_loop().time() + 10
        while asyncio.get_event_loop().time() < deadline:
            if fk.total_records() >= 40:
                break
            await asyncio.sleep(0.1)
        assert fk.total_records() >= 40
        await worker.stop()
        await fk.stop()

    run(t())


def test_rule_action_into_kafka():
    """Full path: MQTT publish -> rule SELECT -> SinkAction -> buffer
    worker -> Kafka record on the fake broker."""

    async def t():
        from emqx_tpu.broker.broker import Broker
        from emqx_tpu.config import BrokerConfig
        from emqx_tpu.message import Message
        from emqx_tpu.rules.engine import SinkAction

        fk = FakeKafka(n_partitions=2)
        await fk.start()
        broker = Broker(BrokerConfig())
        res = KafkaProducerResource(
            [("127.0.0.1", fk.port)], topic="rules-out"
        )
        await broker.resources.create("kafka0", res)
        broker.rules.add_rule(
            "r1",
            'SELECT payload, topic FROM "sensors/#"',
            [SinkAction(resource_id="kafka0")],
        )
        broker.publish(Message(topic="sensors/1/temp", payload=b"21.5"))
        deadline = asyncio.get_event_loop().time() + 5
        while asyncio.get_event_loop().time() < deadline:
            if fk.total_records() >= 1:
                break
            await asyncio.sleep(0.05)
        assert fk.total_records() == 1
        blob = b"".join(
            b for bs in fk.records.values() for b in bs
        )
        assert b"21.5" in blob and b"sensors/1/temp" in blob
        await broker.resources.stop_all()
        await fk.stop()

    run(t())


def test_config_declared_kafka_sink_boots():
    """cfg.sinks entry of type kafka starts with the broker server and
    is addressable from rules by id (the emqx_bridge boot path)."""

    async def t():
        from emqx_tpu.broker.listener import BrokerServer
        from emqx_tpu.config import BrokerConfig, ListenerConfig
        from emqx_tpu.message import Message
        from emqx_tpu.rules.engine import SinkAction

        fk = FakeKafka(n_partitions=1)
        await fk.start()
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        cfg.sinks = [{
            "id": "kbridge",
            "type": "kafka",
            "bootstrap": [["127.0.0.1", fk.port]],
            "topic": "boot-out",
        }]
        srv = BrokerServer(cfg)
        await srv.start()
        assert srv.broker.resources.get("kbridge") is not None
        srv.broker.rules.add_rule(
            "r1", 'SELECT payload FROM "b/#"',
            [SinkAction(resource_id="kbridge")],
        )
        srv.broker.publish(Message(topic="b/1", payload=b"hello"))
        deadline = asyncio.get_event_loop().time() + 5
        while asyncio.get_event_loop().time() < deadline:
            if fk.total_records() >= 1:
                break
            await asyncio.sleep(0.05)
        assert fk.total_records() == 1
        await srv.stop()
        await fk.stop()

    run(t())


def test_requests_pipeline_on_one_connection():
    """PR 3 burn-down: requests no longer serialize on a lock held
    across the full round-trip.  The server here collects TWO complete
    requests before answering either (impossible under the old lock —
    the second frame was only written after the first response), then
    answers in REVERSE order to prove responses demultiplex by
    correlation id, not arrival order."""

    async def t():
        conns = []

        async def handler(r, w):
            conns.append(w)
            corrs = []
            for _ in range(2):
                raw = await r.readexactly(4)
                (size,) = struct.unpack(">i", raw)
                req = await r.readexactly(size)
                _api, _ver, corr = struct.unpack_from(">hhi", req, 0)
                corrs.append(corr)
            for corr in reversed(corrs):
                payload = struct.pack(">ii", corr, corr)
                w.write(struct.pack(">i", len(payload)) + payload)
            await w.drain()

        server = await asyncio.start_server(handler, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        client = KafkaClient("127.0.0.1", port)
        r1, r2 = await asyncio.wait_for(
            asyncio.gather(
                client.request(0, 0, b""), client.request(0, 0, b"")
            ),
            5.0,
        )
        # each caller got ITS body back despite reversed responses
        assert struct.unpack(">i", r1)[0] == 1
        assert struct.unpack(">i", r2)[0] == 2
        assert len(conns) == 1  # both rode one pipelined connection
        client.close()
        # Python 3.12's wait_closed() waits for every accepted
        # connection: close the server's half, or it never returns
        for w in conns:
            w.close()
        server.close()
        await server.wait_closed()

    run(t())


def test_connection_loss_fails_pending_requests():
    """A dead connection must fail every in-flight future (the reader
    pump's teardown), not leave callers hanging until their timeout."""

    async def t():
        async def handler(r, w):
            await r.readexactly(4)  # swallow, never answer
            await r.read()  # until the client is gone, then close our
            w.close()  # half: 3.12's wait_closed() waits for it

        server = await asyncio.start_server(handler, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        client = KafkaClient("127.0.0.1", port)
        stuck = asyncio.ensure_future(
            client.request(0, 0, b"", timeout=30.0)
        )
        await asyncio.sleep(0.05)
        assert not stuck.done()
        client._w.close()  # connection dies under the pending request
        try:
            await asyncio.wait_for(stuck, 5.0)
            assert False, "expected the pending request to fail"
        except ConnectionError:
            pass
        client.close()
        server.close()
        await server.wait_closed()

    run(t())


def test_client_redials_after_connection_loss():
    """The reader pump tears the transport down with itself: after a
    server-side close, the NEXT request must re-dial and succeed
    instead of registering in an unpumped map and hanging."""

    async def t():
        fk = FakeKafka(n_partitions=1)
        await fk.start()
        client = KafkaClient("127.0.0.1", fk.port)
        assert (await client.metadata(["t"]))["topics"]["t"] == {0: 0}
        # kill the live connection server-side and let the pump die
        first_w = client._w
        fk.server.close()
        first_w.close()  # first: 3.12's wait_closed() waits for it
        await fk.server.wait_closed()
        await asyncio.sleep(0.05)
        assert not client.connected  # pump teardown closed the writer
        await fk.start()  # server back (new port)
        client.port = fk.port
        md = await asyncio.wait_for(client.metadata(["t"]), 5.0)
        assert md["topics"]["t"] == {0: 0}
        client.close()
        await fk.stop()

    run(t())
