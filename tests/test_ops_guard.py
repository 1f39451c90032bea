"""Operational guards: banned CONNECT, flapping ban, alarms over $SYS
and REST, slow-subscription tracking (emqx_banned / emqx_flapping /
emqx_alarm / emqx_slow_subs parity)."""

import asyncio
import tempfile

# auto-cleaned parent for per-test mgmt stores (finalized at interpreter exit)
_MGMT_TMP = tempfile.TemporaryDirectory(prefix="emqx-mgmt-")

import aiohttp
import pytest

from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.config import BrokerConfig, ListenerConfig
from emqx_tpu.ops_guard import SlowSubs
from api_helper import auth_session
from mqtt_client import TestClient


def run(coro):
    return asyncio.run(coro)


def make_server(**kw):
    cfg = BrokerConfig()
    cfg.listeners = [ListenerConfig(port=0)]
    cfg.api.enable = True
    cfg.api.data_dir = tempfile.mkdtemp(dir=_MGMT_TMP.name)
    cfg.api.port = 0
    for k, v in kw.items():
        setattr(cfg, k, v)
    return BrokerServer(cfg)


def test_banned_client_rejected_at_connect():
    async def t():
        srv = make_server()
        await srv.start()
        port = srv.listeners[0].port
        srv.broker.banned.ban("clientid", "evil", reason="test")
        c = TestClient(port, "evil")
        ack = await c.connect()
        assert ack.reason_code == 0x8A  # banned
        await c.close()
        # expiry frees the ban
        srv.broker.banned.ban("clientid", "brief", seconds=-1)
        c2 = TestClient(port, "brief")
        ack2 = await c2.connect()
        assert ack2.reason_code == 0
        await c2.disconnect()
        await srv.stop()

    run(t())


def test_flapping_client_gets_banned():
    async def t():
        from emqx_tpu.config import FlappingConfig

        srv = make_server(
            flapping=FlappingConfig(max_count=3, window=10.0, ban_time=60.0)
        )
        await srv.start()
        port = srv.listeners[0].port
        for _ in range(3):
            c = TestClient(port, "flappy")
            await c.connect()
            await c.disconnect()
            await asyncio.sleep(0.02)
        c = TestClient(port, "flappy")
        ack = await c.connect()
        assert ack.reason_code == 0x8A  # banned for flapping
        await c.close()
        assert any(
            a.name.startswith("flapping/") for a in srv.broker.alarms.active()
        )
        await srv.stop()

    run(t())


def test_alarms_rest_and_sys():
    async def t():
        srv = make_server()
        await srv.start()
        port = srv.listeners[0].port
        mon = TestClient(port, "mon")
        await mon.connect()
        await mon.subscribe("$SYS/#")

        srv.broker.alarms.activate(
            "high_mem", details={"pct": 93}, message="memory high"
        )
        pkt = await mon.recv_publish()
        assert pkt.topic.endswith("/alarms/activate")
        assert b"high_mem" in pkt.payload

        http, api = await auth_session(srv)
        async with http:
            async with http.get(api + "/api/v5/alarms") as r:
                data = await r.json()
            assert data["data"][0]["name"] == "high_mem"
            async with http.delete(api + "/api/v5/alarms") as r:
                assert r.status == 204
            async with http.get(api + "/api/v5/alarms") as r:
                assert (await r.json())["data"] == []
            async with http.get(
                api + "/api/v5/alarms?activated=false"
            ) as r:
                hist = await r.json()
            assert hist["data"][0]["name"] == "high_mem"

        await mon.disconnect()
        await srv.stop()

    run(t())


def test_banned_rest_crud():
    async def t():
        srv = make_server()
        await srv.start()
        http, api = await auth_session(srv)
        async with http:
            async with http.post(
                api + "/api/v5/banned",
                json={"as": "peerhost", "who": "10.0.0.9", "seconds": 60},
            ) as r:
                assert r.status == 201
            async with http.get(api + "/api/v5/banned") as r:
                data = await r.json()
            assert data["data"][0]["who"] == "10.0.0.9"
            async with http.delete(
                api + "/api/v5/banned/peerhost/10.0.0.9"
            ) as r:
                assert r.status == 204
        await srv.stop()

    run(t())


def test_slow_subs_topk():
    ss = SlowSubs(top_k=2, threshold_ms=10.0)
    ss.record("a", "t/1", 5.0)  # below threshold: ignored
    ss.record("b", "t/2", 50.0)
    ss.record("c", "t/3", 500.0)
    ss.record("d", "t/4", 100.0)  # evicts the 50ms entry
    top = ss.top()
    assert [e["clientid"] for e in top] == ["c", "d"]
    assert top[0]["latency_ms"] == 500.0


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_slow_subs_slowest_is_what_a_record_a_delivery_leaves(seed):
    """`slowest` picks, from a window's latencies, the positions whose
    records leave the board a record of EVERY position would have:
    over boards empty, part full and full, with many equal latencies
    (one message's deliveries) at the cut."""
    import numpy as np

    rng = np.random.default_rng(seed)
    one, all_ = (SlowSubs(top_k=5, threshold_ms=10.0) for _ in range(2))
    for w in range(6):
        # few distinct values: ties everywhere, some under the threshold
        lat = rng.choice(
            rng.uniform(0.0, 40.0 + 20.0 * (w % 3), 4 + seed), 60
        )
        picked = one.slowest(lat).tolist()
        assert picked == sorted(picked) and len(picked) <= 5
        for t in picked:
            one.record(f"c{w}.{t}", "t", float(lat[t]))
        for t in range(len(lat)):
            all_.record(f"c{w}.{t}", "t", float(lat[t]))
        assert [(e["clientid"], e["latency_ms"]) for e in one.top()] == [
            (e["clientid"], e["latency_ms"]) for e in all_.top()
        ]
    assert len(one.top()) == 5


def test_hierarchical_limiter_levels():
    """The tightest level bounds the connection: listener-aggregate
    and zone buckets throttle even when the per-connection bucket is
    unlimited (emqx_limiter's hierarchy, flattened)."""
    from emqx_tpu.limiter import ConnectionLimiter, HierarchicalLimiter

    listener_shared = ConnectionLimiter(messages_rate=10, messages_burst=10)
    conn_a = HierarchicalLimiter(None, listener_shared, None)
    conn_b = HierarchicalLimiter(
        ConnectionLimiter(messages_rate=1000), listener_shared, None
    )
    # the two connections drain the SHARED bucket together
    assert conn_a.consume(0, 5) == 0.0
    assert conn_b.consume(0, 5) == 0.0
    delay = conn_a.consume(0, 5)
    assert delay > 0.0  # shared bucket exhausted => pause owed
    # a zone bucket above both wins when tighter
    zone = ConnectionLimiter(bytes_rate=100, bytes_burst=100)
    c = HierarchicalLimiter(
        ConnectionLimiter(bytes_rate=10**9), None, zone
    )
    assert c.consume(100, 0) == 0.0
    assert c.consume(100, 0) > 0.0


def test_shared_bucket_debt_accumulates_across_consumers():
    """Aggregate enforcement: N connections hammering one SHARED
    bucket must queue behind its rate — the debt (and so the owed
    pause) keeps growing instead of saturating at one burst, which
    would let the combined rate scale with N."""
    from emqx_tpu.limiter import ConnectionLimiter

    shared = ConnectionLimiter(
        messages_rate=10, messages_burst=10, shared=True
    )
    delays = [shared.consume(0, 1) for _ in range(50)]
    # first burst-worth admitted free, then the wait grows linearly:
    # the 50th consumer owes ~(50-10)/10 = 4s, far beyond one burst
    assert delays[9] == 0.0
    assert delays[-1] > 3.0
    assert delays[-1] > delays[20] > delays[11]
    # a PRIVATE bucket keeps the one-burst debt cap (bounded pause)
    private = ConnectionLimiter(messages_rate=10, messages_burst=10)
    for _ in range(50):
        capped = private.consume(0, 1)
    assert capped <= 1.0 + 1e-6


def test_listener_hierarchy_over_socket():
    """End to end: a listener-aggregate message cap throttles two
    clients' combined publish rate via read-pausing."""
    import time as _time

    from emqx_tpu.broker.listener import BrokerServer
    from emqx_tpu.config import BrokerConfig, ListenerConfig
    from mqtt_client import TestClient

    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(
            port=0, max_messages_rate=50, max_bytes_rate=0,
        )]
        srv = BrokerServer(cfg)
        await srv.start()
        port = srv.listeners[0].port
        c1 = TestClient(port, "l1")
        c2 = TestClient(port, "l2")
        await c1.connect()
        await c2.connect()
        t0 = _time.perf_counter()
        # 120 msgs over a 50/s shared cap (burst 50) => >= ~1.3s
        for i in range(60):
            await c1.publish("t/a", b"x", qos=1, timeout=10)
            await c2.publish("t/b", b"x", qos=1, timeout=10)
        elapsed = _time.perf_counter() - t0
        assert elapsed >= 1.0, f"shared cap not enforced ({elapsed:.2f}s)"
        await c1.close()
        await c2.close()
        await srv.stop()

    run(t())


def test_sysmon_samples_and_alarms():
    """emqx_os_mon / emqx_vm_mon role: gauges always land in stats;
    watermark breaches raise alarms with cpu hysteresis."""
    from emqx_tpu.broker.broker import Broker
    from emqx_tpu.config import BrokerConfig
    from emqx_tpu.sysmon import SysMonitor

    broker = Broker(BrokerConfig())
    mon = SysMonitor(broker, interval=0.0,
                     sysmem_high_watermark=2.0,  # never fires
                     procmem_high_watermark=2.0,
                     cpu_high_watermark=1e9,
                     cpu_low_watermark=1e9 - 1)
    out = mon.sample()
    stats = broker.stats.all()
    assert "vm.mem.rss_bytes" in stats and stats["vm.mem.rss_bytes"] > 0
    assert "os.cpu.load1_per_core_x1000" in stats
    assert not any(a.name == "high_sysmem"
                   for a in broker.alarms.active())

    # force every watermark under the observed readings: alarms fire
    mon2 = SysMonitor(broker, interval=0.0,
                      sysmem_high_watermark=0.0,
                      procmem_high_watermark=0.0,
                      cpu_high_watermark=-1.0,
                      cpu_low_watermark=-2.0)
    mon2.sample()
    names = {a.name for a in broker.alarms.active()}
    assert {"high_sysmem", "high_procmem", "high_cpu"} <= names

    # hysteresis: readings between low and high KEEP the cpu alarm
    mon3 = SysMonitor(broker, interval=0.0,
                      sysmem_high_watermark=2.0,
                      procmem_high_watermark=2.0,
                      cpu_high_watermark=1e9,
                      cpu_low_watermark=-1.0)
    mon3.sample()
    names = {a.name for a in broker.alarms.active()}
    assert "high_sysmem" not in names  # cleared (above-threshold gone)
    assert "high_cpu" in names         # still above LOW: alarm holds

    # dropping under the low watermark finally clears it
    mon4 = SysMonitor(broker, interval=0.0,
                      sysmem_high_watermark=2.0,
                      procmem_high_watermark=2.0,
                      cpu_high_watermark=1e9,
                      cpu_low_watermark=1e9 - 1)
    mon4.sample()
    assert "high_cpu" not in {a.name for a in broker.alarms.active()}
