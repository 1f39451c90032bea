"""The fan-out deployment (`benchmark/configs/exact-1k-fanout.json`)
at a small size on the CPU: 40 subscribers on 4 exact topics, windows
of 1-64 publishes.

- the served path (`BrokerServer` + loopback clients) against the
  benchmark's plain reference (`benchmark/referee.py`): deliveries,
  QoS and order exact;
- `decide_batch` through `_decide_device` against `decide_batch_host`,
  bit for bit, at fan-out shapes (ten rows a message), padding
  included, one case a shape;
- after `BrokerServer.start()`, windows of every size raise no XLA
  compile request, with no automaton and with a small fleet table.
"""

import asyncio
import os
import sys

import numpy as np
import pytest

from emqx_tpu import engine as engine_mod
from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.codec import mqtt as C
from emqx_tpu.config import BrokerConfig, ListenerConfig
from emqx_tpu.engine import MatchEngine
from emqx_tpu.ops import match_kernel

from mqtt_client import TestClient

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)
sys.path.insert(0, BENCH)

import referee  # noqa: E402
import traffic  # noqa: E402

SUBSCRIBERS, TOPICS, PUBLISHERS = 40, 4, 4
BURSTS = (1, 2, 5, 16, 33, 64, 3, 64, 1)


def run(coro):
    return asyncio.run(coro)


# ------------------------------------------------- decide, bit for bit

def fanout_window(rng, messages, per=10, r=64):
    """A window of ``messages`` publishes at ``per`` deliveries each,
    the columns sorted by subscriber as `expand_window` leaves them."""
    cols = (
        rng.integers(0, 3, r).astype(np.int8), rng.random(r) < 0.3,
        rng.random(r) < 0.4, rng.random(r) < 0.2,
    )
    n = per * messages
    midx = np.repeat(np.arange(messages), per)
    crows = rng.integers(0, SUBSCRIBERS, n)
    order = np.argsort(crows, kind="stable")
    return cols, (
        rng.integers(0, r, n)[order], crows[order], midx[order],
        rng.integers(0, 3, messages).astype(np.int8),
        rng.random(messages) < 0.5,
        rng.integers(-1, SUBSCRIBERS, messages).astype(np.int32),
    )


@pytest.mark.parametrize("warm_batch", [16, 64, 512])
@pytest.mark.parametrize("messages", [1, 2, 7, 16, 17, 64, 103, 205, 512])
def test_decide_device_equals_host_at_fanout_shapes(messages, warm_batch):
    rng = np.random.default_rng(1000 * warm_batch + messages)
    cols, window = fanout_window(rng, messages)
    eng = MatchEngine(use_device=True)
    eng._warm_batch = warm_batch  # what `warmup(batch_max)` leaves
    info = {}
    dev = eng._decide_device(cols, 1, *window, info)
    host = match_kernel.decide_batch_host(*cols, *window)
    assert dev.dtype == host.dtype and np.array_equal(dev, host)
    # the bucket: rows to a power of two from 1,024, the message
    # columns to the warmed width, the attribute columns to a rung
    rows, padded = info["rows"]
    assert rows == 10 * messages
    assert padded == max(1024, 1 << (rows - 1).bit_length())
    assert eng._dec_cols_cache[2] == 4096
    assert info["upload"][1] > 0 and info["device_wait"][1] > 0
    assert info["upload"][0] + info["upload"][1] <= info["device_wait"][0]


def test_decide_attribute_columns_climb_rungs():
    """Past 4,096 subscription rows the columns are padded to the next
    rung (x4), which was queued for compiling when the rung filled."""
    rng = np.random.default_rng(5)
    cols, window = fanout_window(rng, 8, r=4096)
    eng = MatchEngine(use_device=True)
    dev = eng._decide_device(cols, 1, *window)
    assert np.array_equal(
        dev, match_kernel.decide_batch_host(*cols, *window)
    )
    assert eng._dec_cols_cache[2] == 4096
    assert eng._dec_rungs == {4096, 16384}
    cols, window = fanout_window(rng, 8, r=8192)
    dev = eng._decide_device(cols, 2, *window)
    assert np.array_equal(
        dev, match_kernel.decide_batch_host(*cols, *window)
    )
    assert eng._dec_cols_cache[2] == 16384
    t = eng._dec_warm_thread
    if t is not None:
        t.join(60)
    assert not eng._decide_shapes()


# ------------------------------------------------------ the served path

class Subscriber:
    def __init__(self, port, cid, flt, qos):
        self.client = TestClient(port, cid)
        self.flt, self.qos = flt, qos
        self.seqs, self.qos_seen = [], 0

    async def start(self):
        await self.client.connect()
        ack = await self.client.subscribe(self.flt, qos=self.qos)
        assert list(ack.reason_codes) == [self.qos]
        self.task = asyncio.get_running_loop().create_task(self.pump())

    async def pump(self):
        lo, hi = traffic.SEQ_AT, traffic.SEQ_AT + traffic.SEQ_W
        while True:
            pkt = await self.client.recv_publish(timeout=3600)
            self.seqs.append(int(pkt.payload[lo:hi]))
            self.qos_seen |= 1 << pkt.qos


async def serve_fanout(seed, fleet_table=0, compile_log=None):
    """Bursts of 1-64 QoS1 publishes from ``PUBLISHERS`` connections
    (publish ``seq`` on connection ``seq % PUBLISHERS`` and topic
    ``pool[seq % TOPICS]``, the benchmark's rule) to 40 subscribers.
    Returns what `referee.judge` needs, and the ring."""
    cfg = BrokerConfig()
    cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
    cfg.engine.use_device = True
    cfg.mqtt.max_inflight = 4096
    cfg.mqtt.max_mqueue_len = 0
    cfg.profiler.ring_size = 4096
    srv = BrokerServer(cfg)
    eng = srv.broker.router.engine
    if fleet_table:
        pairs, _ = traffic.table_fleet_families(fleet_table, 8)
        eng.insert_many(pairs)
    await srv.start()
    mark = compile_log.mark() if compile_log is not None else 0
    port = srv.listeners[0].port
    subs = traffic.live_exact_fanout(SUBSCRIBERS, TOPICS)
    pool = traffic.topic_pool(
        {"generator": "exact_topics", "pool": TOPICS}, None, seed, PUBLISHERS
    )
    rng = np.random.default_rng(seed)
    clients = [Subscriber(port, cid, flts[0], qos)
               for cid, flts, qos in subs]
    pubs = [TestClient(port, f"pub{k}") for k in range(PUBLISHERS)]
    try:
        for c in clients:
            await c.start()
        for p in pubs:
            await p.connect()
        seq, sent, acked = 0, [], []
        for burst in rng.permutation(BURSTS):
            wire = [bytearray() for _ in pubs]
            pids = [[] for _ in pubs]
            for _ in range(int(burst)):
                k = seq % PUBLISHERS
                pid = seq // PUBLISHERS % 65535 + 1
                wire[k] += C.serialize(C.Publish(
                    topic=pool[seq % len(pool)], qos=1, packet_id=pid,
                    payload=traffic.payload_of(seq),
                ), C.MQTT_V5)
                pids[k].append((pid, seq))
                sent.append(seq)
                seq += 1
            for p, w in zip(pubs, wire):
                p.writer.write(bytes(w))
            for p, mine in zip(pubs, pids):
                for pid, s in mine:
                    ack = await p.expect(C.PUBACK, timeout=60)
                    assert ack.packet_id == pid
                    acked.append(s)
        want = len(sent) * SUBSCRIBERS // TOPICS
        for _ in range(600):
            if sum(len(c.seqs) for c in clients) >= want:
                break
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.3)  # anything nobody expects still arrives
        stats = eng.stats()
        ring = srv.broker.profiler.windows(4096)
        compiles = (compile_log.since(mark)
                    if compile_log is not None else None)
    finally:
        for c in clients:
            c.task.cancel()
            await c.client.close()
        for p in pubs:
            await p.close()
        await srv.stop()
    exp = referee.Expected(pool, subs, 0, np.asarray(sent))
    numbers, failed = referee.judge(
        exp, PUBLISHERS, np.asarray(acked), [c.seqs for c in clients],
        [c.qos_seen for c in clients], np.zeros(0, np.int32),
        np.zeros(0, np.int64), {},
    )
    return numbers, failed, ring, stats, compiles, len(sent)


@pytest.mark.parametrize("seed", [11, 3000000026, 77])
def test_served_fanout_equals_the_reference(seed):
    numbers, failed, ring, stats, _, n_sent = run(serve_fanout(seed))
    assert [(n, v) for n, v, _lim in numbers if v] == []
    assert len(failed) == 0
    wins = [w for w in ring if w["n_msgs"]]
    assert sum(w["n_msgs"] for w in wins) == n_sent == sum(BURSTS)
    assert 1 <= min(w["n_msgs"] for w in wins)
    assert max(w["n_msgs"] for w in wins) <= 64
    # no automaton: the host's exact index matched, the device decided
    # every window that delivered, ten rows a publish in a bucket
    assert all(w["path"] == "host" for w in wins)
    assert stats["decide_host_windows"] == 0
    assert stats["decide_dev_windows"] == len(wins)
    for w in wins:
        assert w["n_deliveries"] == w["decide_rows"] == 10 * w["n_msgs"]
        assert w["decide_rows_padded"] == 1024
        # one run a subscriber of the window's topics: the sockets the
        # window wrote to
        assert w["n_clients"] == 10 * min(w["n_msgs"], TOPICS)
        st = w["stages_us"]
        assert 0 < st["decide_upload"] and 0 < st["decide_device_wait"]
        assert st["decide_upload"] + st["decide_device_wait"] \
            <= st["decide"] + 1.0


# ------------------------------------------- nothing compiles in traffic

@pytest.fixture
def compile_log():
    import jax

    import run as harness

    # start() has to compile for itself, whatever ran before in this
    # process
    jax.clear_caches()
    engine_mod._DEC_WARMED.clear()
    return harness.CompileLog()


@pytest.mark.parametrize("fleet_table", [0, 5000],
                         ids=["no_automaton", "fleet_table"])
def test_no_compile_request_after_start(fleet_table, compile_log):
    numbers, failed, ring, stats, compiles, _ = run(
        serve_fanout(5, fleet_table, compile_log)
    )
    assert [(n, v) for n, v, _lim in numbers if v] == []
    assert stats["decide_dev_windows"] > 0
    assert {w["path"] for w in ring if w["n_msgs"]} == (
        {"dev"} if fleet_table else {"host"}
    )
    # start() did compile decide_batch, and the traffic nothing
    assert any("decide_batch" in r[0] for r in compile_log.requests)
    assert compiles["requests"] == 0, compiles


# ------------------------------------- the collector on a stalled loop

def stalled_collector(arrivals_after_stall):
    """Two publishes queued, then the loop held for 30 ms (as a
    predecessor's dispatch holds it) while the window's 1 ms deadline
    runs out; ``arrivals_after_stall`` more publishes land four loop
    turns after the stall ends (the timeout has been seen by then; a
    readable socket's publishes take two or three).  Returns
    the sizes of the windows the collector made."""
    import time

    from emqx_tpu.broker.broker import Broker, PublishBatcher
    from emqx_tpu.message import Message

    async def t():
        broker = Broker(BrokerConfig())
        broker._loop = loop = asyncio.get_running_loop()
        sizes = []
        real = broker.publish_match_submit

        def submit(live, congested=False, rec=None):
            sizes.append(len(live))
            return real(live, congested, rec)

        broker.publish_match_submit = submit
        batcher = PublishBatcher(broker, window=0.001)

        def msg(i):
            return Message(topic=f"fanout/t{i % 4}", payload=b"x", qos=1)

        def land():
            for i in range(arrivals_after_stall):
                batcher.publish_nowait(msg(i), source="late")

        def later(turns):
            if turns:
                loop.call_soon(later, turns - 1)
            else:
                land()

        def stall():
            time.sleep(0.03)
            later(4)

        batcher.publish_nowait(msg(0), source="a")
        batcher.publish_nowait(msg(1), source="b")
        await batcher.start()
        loop.call_soon(stall)
        for _ in range(200):
            await asyncio.sleep(0.005)
            if sum(sizes) == 2 + arrivals_after_stall:
                break
        await batcher.stop()
        return sizes

    return run(t())


@pytest.mark.parametrize("late", [0, 1, 40, 600])
def test_a_stalled_deadline_does_not_split_a_burst(late):
    sizes = stalled_collector(late)
    assert sum(sizes) == 2 + late
    # what landed right after the stall rides the window that was
    # open through it, up to the window limit; with nothing landing
    # the window closes after its bounded turns
    assert sizes[0] == min(2 + late, 512)


def test_decide_warm_thread_ends_where_the_device_path_is_off():
    """A warm-up that has shapes left but may not compile them (the
    breaker opened meanwhile) ends; it does not spin."""
    eng = MatchEngine(use_device=True)
    eng._dec_rungs = eng._dec_rungs | {1 << 30}  # never compiled
    eng._brk_open = True
    assert eng._decide_shapes()
    eng._kick_decide_warm()
    t = eng._dec_warm_thread
    if t is not None:
        t.join(10)
        assert not t.is_alive()
    assert eng._dec_warm_thread is None
