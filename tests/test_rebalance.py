"""Node evacuation: bounded-rate eviction with cross-node session
migration (emqx_node_rebalance / emqx_eviction_agent parity)."""

import asyncio
import tempfile

# auto-cleaned parent for per-test mgmt stores (finalized at interpreter exit)
_MGMT_TMP = tempfile.TemporaryDirectory(prefix="emqx-mgmt-")

from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.cluster import ClusterNode
from emqx_tpu.codec import mqtt as C
from emqx_tpu.config import BrokerConfig, ListenerConfig
from mqtt_client import TestClient

FAST = dict(heartbeat_interval=0.05, down_after=0.25, flush_interval=0.002)


def run(coro):
    return asyncio.run(coro)


def test_evacuation_drains_and_signals_clients():
    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        cfg.api.enable = True
        cfg.api.data_dir = tempfile.mkdtemp(dir=_MGMT_TMP.name)
        cfg.api.port = 0
        srv = BrokerServer(cfg)
        await srv.start()
        port = srv.listeners[0].port

        clients = [TestClient(port, f"ev-{i}") for i in range(6)]
        for c in clients:
            await c.connect(
                clean_start=False,
                properties={"session_expiry_interval": 600},
            )
        await srv.broker.eviction.start_evacuation(conn_evict_rate=100)
        # v5 clients get USE_ANOTHER_SERVER before the close
        pkt = await clients[0].recv(timeout=3)
        assert pkt is not None and pkt.type == C.DISCONNECT
        assert pkt.reason_code == 0x9C
        for _ in range(100):
            if srv.broker.eviction.info()["status"] == "evacuated":
                break
            await asyncio.sleep(0.05)
        info = srv.broker.eviction.info()
        assert info["status"] == "evacuated" and info["evicted"] == 6
        # persistent sessions survive detached (takeover-able)
        assert srv.broker.cm.lookup("ev-0") is not None
        assert not srv.broker.cm.connected("ev-0")
        for c in clients:
            await c.close()
        await srv.stop()

    run(t())


def test_plan_rebalance_donors_and_recipients():
    from emqx_tpu.rebalance import plan_rebalance

    plan = plan_rebalance({"a": 90, "b": 10, "c": 20})
    assert plan["avg"] == 40
    assert plan["donors"] == {"a": 50}
    assert plan["recipients"] == ["b", "c"]
    # balanced cluster -> no donors
    assert plan_rebalance({"a": 10, "b": 10})["donors"] == {}
    assert plan_rebalance({})["donors"] == {}
    # threshold guards small skews
    assert plan_rebalance({"a": 11, "b": 10}, threshold=1.2)["donors"] == {}


def test_cluster_rebalance_sheds_overloaded_node():
    async def t():
        async def start_node(name, seeds=()):
            cfg = BrokerConfig()
            cfg.listeners = [ListenerConfig(port=0)]
            srv = BrokerServer(cfg)
            await srv.start()
            node = ClusterNode(name, srv.broker, **FAST)
            await node.start(seeds=list(seeds))
            return srv, node

        srv_a, a = await start_node("a")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)])
        await asyncio.sleep(0.3)

        # 8 connections on A, none on B: A is the donor
        clients = [TestClient(srv_a.listeners[0].port, f"rb-{i}")
                   for i in range(8)]
        for c in clients:
            await c.connect()

        plan = await srv_a.broker.rebalance.start(
            conn_evict_rate=100, rel_conn_threshold=1.05
        )
        assert plan["donors"].get("a", 0) >= 3  # shed down toward avg=4
        assert "b" in plan["recipients"]

        for _ in range(100):
            info = srv_a.broker.rebalance.info()
            if info["status"] == "balanced":
                break
            await asyncio.sleep(0.05)
        live = sum(1 for c in srv_a.broker.cm.clients()
                   if srv_a.broker.cm.connected(c))
        assert live <= 8 - plan["donors"]["a"]

        for c in clients:
            await c.close()
        await b.stop()
        await srv_b.stop()
        await a.stop()
        await srv_a.stop()

    run(t())


def test_rebalance_remote_donor_shed_via_cast():
    """The coordinator on a balanced node still drives a remote donor."""

    async def t():
        async def start_node(name, seeds=()):
            cfg = BrokerConfig()
            cfg.listeners = [ListenerConfig(port=0)]
            srv = BrokerServer(cfg)
            await srv.start()
            node = ClusterNode(name, srv.broker, **FAST)
            await node.start(seeds=list(seeds))
            return srv, node

        srv_a, a = await start_node("a")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)])
        await asyncio.sleep(0.3)

        clients = [TestClient(srv_a.listeners[0].port, f"rr-{i}")
                   for i in range(6)]
        for c in clients:
            await c.connect()

        # start from B (a recipient): it must tell A to shed remotely
        plan = await srv_b.broker.rebalance.start(
            conn_evict_rate=100, rel_conn_threshold=1.05
        )
        assert plan["donors"].get("a", 0) >= 2

        for _ in range(100):
            live = sum(1 for c in srv_a.broker.cm.clients()
                       if srv_a.broker.cm.connected(c))
            if live <= 6 - plan["donors"]["a"]:
                break
            await asyncio.sleep(0.05)
        live = sum(1 for c in srv_a.broker.cm.clients()
                   if srv_a.broker.cm.connected(c))
        assert live <= 6 - plan["donors"]["a"]

        for c in clients:
            await c.close()
        await b.stop()
        await srv_b.stop()
        await a.stop()
        await srv_a.stop()

    run(t())


def test_purge_drops_detached_sessions_only():
    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        port = srv.listeners[0].port

        # three persistent sessions; two go detached, one stays live
        clients = [TestClient(port, f"pg-{i}") for i in range(3)]
        for c in clients:
            await c.connect(
                clean_start=False,
                properties={"session_expiry_interval": 600},
            )
        await clients[0].disconnect()
        await clients[1].disconnect()
        await asyncio.sleep(0.05)
        assert not srv.broker.cm.connected("pg-0")
        assert srv.broker.cm.lookup("pg-0") is not None

        await srv.broker.purger.start_purge(purge_rate=100)
        for _ in range(100):
            if srv.broker.purger.info()["status"] == "purged":
                break
            await asyncio.sleep(0.05)
        info = srv.broker.purger.info()
        assert info["status"] == "purged" and info["purged"] == 2
        assert srv.broker.cm.lookup("pg-0") is None
        assert srv.broker.cm.lookup("pg-1") is None
        # the live client is untouched
        assert srv.broker.cm.connected("pg-2")
        await clients[2].disconnect()
        for c in clients:
            await c.close()
        await srv.stop()

    run(t())


def test_purge_refused_while_evacuating():
    async def t():
        import pytest

        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        c = TestClient(srv.listeners[0].port, "busy")
        await c.connect(
            clean_start=False,
            properties={"session_expiry_interval": 600},
        )
        await srv.broker.eviction.start_evacuation(conn_evict_rate=1)
        with pytest.raises(RuntimeError):
            await srv.broker.purger.start_purge()
        await srv.broker.eviction.stop_evacuation()
        await c.close()
        await srv.stop()

    run(t())


def test_eviction_refused_while_purging():
    """The exclusion is bidirectional: a running purge blocks
    evacuation/shed (which would park sessions the purge destroys)."""

    async def t():
        import pytest

        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        # a detached session keeps the purge loop alive
        c = TestClient(srv.listeners[0].port, "pp")
        await c.connect(
            clean_start=False,
            properties={"session_expiry_interval": 600},
        )
        await c.disconnect()
        await asyncio.sleep(0.05)
        srv.broker.purger.status = "purging"  # freeze mid-purge
        with pytest.raises(RuntimeError):
            await srv.broker.eviction.start_evacuation()
        srv.broker.rebalance.start_shed(5, 10)
        assert not srv.broker.rebalance.shedding
        srv.broker.purger.status = "disabled"
        await c.close()
        await srv.stop()

    run(t())


def test_rebalance_stop_reaches_remote_donors():
    async def t():
        async def start_node(name, seeds=()):
            cfg = BrokerConfig()
            cfg.listeners = [ListenerConfig(port=0)]
            srv = BrokerServer(cfg)
            await srv.start()
            node = ClusterNode(name, srv.broker, **FAST)
            await node.start(seeds=list(seeds))
            return srv, node

        srv_a, a = await start_node("a")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)])
        await asyncio.sleep(0.3)

        clients = [TestClient(srv_a.listeners[0].port, f"rs-{i}")
                   for i in range(6)]
        for c in clients:
            await c.connect()

        # coordinate from B with a slow rate so the shed is still
        # running on A when the stop arrives
        plan = await srv_b.broker.rebalance.start(
            conn_evict_rate=1, rel_conn_threshold=1.05
        )
        assert plan["donors"].get("a", 0) >= 2
        for _ in range(50):
            if srv_a.broker.rebalance.shedding:
                break
            await asyncio.sleep(0.05)
        assert srv_a.broker.rebalance.shedding

        await srv_b.broker.rebalance.stop()
        for _ in range(50):
            if not srv_a.broker.rebalance.shedding:
                break
            await asyncio.sleep(0.05)
        assert not srv_a.broker.rebalance.shedding
        assert srv_a.broker.rebalance.status == "idle"

        for c in clients:
            await c.close()
        await b.stop()
        await srv_b.stop()
        await a.stop()
        await srv_a.stop()

    run(t())


def test_cluster_purge_fans_out():
    async def t():
        async def start_node(name, seeds=()):
            cfg = BrokerConfig()
            cfg.listeners = [ListenerConfig(port=0)]
            srv = BrokerServer(cfg)
            await srv.start()
            node = ClusterNode(name, srv.broker, **FAST)
            await node.start(seeds=list(seeds))
            return srv, node

        srv_a, a = await start_node("a")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)])
        await asyncio.sleep(0.3)

        c = TestClient(srv_b.listeners[0].port, "pg-remote")
        await c.connect(
            clean_start=False,
            properties={"session_expiry_interval": 600},
        )
        await c.disconnect()
        await asyncio.sleep(0.05)
        assert srv_b.broker.cm.lookup("pg-remote") is not None

        # the fan-out path the REST handler uses: cast to peers
        await srv_a.broker.purger.start_purge(100)
        for peer in a.peers_alive():
            await a.transport.cast(
                peer, {"type": "session_purge", "rate": 100}
            )
        for _ in range(100):
            if srv_b.broker.purger.info()["status"] == "purged":
                break
            await asyncio.sleep(0.05)
        assert srv_b.broker.cm.lookup("pg-remote") is None
        assert srv_b.broker.purger.info()["status"] == "purged"

        await c.close()
        await b.stop()
        await srv_b.stop()
        await a.stop()
        await srv_a.stop()

    run(t())


def test_evacuated_client_migrates_to_peer():
    async def t():
        async def start_node(name, seeds=()):
            cfg = BrokerConfig()
            cfg.listeners = [ListenerConfig(port=0)]
            srv = BrokerServer(cfg)
            await srv.start()
            node = ClusterNode(name, srv.broker, **FAST)
            await node.start(seeds=list(seeds))
            return srv, node

        srv_a, a = await start_node("a")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)])
        await asyncio.sleep(0.3)

        c = TestClient(srv_a.listeners[0].port, "mover")
        await c.connect(
            clean_start=False,
            properties={"session_expiry_interval": 600},
        )
        await c.subscribe("m/#", qos=1)
        await srv_a.broker.eviction.start_evacuation(conn_evict_rate=100)
        for _ in range(300):  # up to 15 s: a limit, not a pace
            if not srv_a.broker.cm.connected("mover"):
                break
            await asyncio.sleep(0.05)
        assert not srv_a.broker.cm.connected("mover")

        # the client follows USE_ANOTHER_SERVER to node B: takeover
        c2 = TestClient(srv_b.listeners[0].port, "mover")
        ack = await c2.connect(
            clean_start=False,
            properties={"session_expiry_interval": 600},
        )
        assert ack.session_present  # migrated with subscriptions
        pub = TestClient(srv_b.listeners[0].port, "pub")
        await pub.connect()
        await pub.publish("m/1", b"hello", qos=1)
        pkt = await c2.recv_publish()
        assert pkt.payload == b"hello"
        await pub.disconnect()
        await c2.disconnect()
        await c.close()
        await b.stop()
        await srv_b.stop()
        await a.stop()
        await srv_a.stop()

    run(t())
