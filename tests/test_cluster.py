"""Cluster-layer tests: multi-node brokers in one process over loopback
TCP — the `emqx_cth_cluster` pattern (peer nodes on the same host,
/root/reference/apps/emqx/test/emqx_cth_cluster.erl:44,334-349) without
spawning OS processes (pytest drives its own event loop)."""

import asyncio

from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.cluster import ClusterNode
from emqx_tpu.config import BrokerConfig
from emqx_tpu.message import Message
from emqx_tpu.codec import mqtt as C
from mqtt_client import TestClient


FAST = dict(heartbeat_interval=0.05, down_after=0.25, flush_interval=0.002)


def run(coro):
    return asyncio.run(coro)


async def start_node(name, seeds=(), **kw):
    cfg = BrokerConfig()
    cfg.listeners[0].port = 0
    srv = BrokerServer(cfg)
    await srv.start()
    node = ClusterNode(name, srv.broker, **{**FAST, **kw})
    await node.start(seeds=list(seeds))
    return srv, node


async def stop_node(srv, node):
    await node.stop()
    await srv.stop()


async def settle(t=0.05):
    await asyncio.sleep(t)


def test_cross_node_pubsub():
    async def t():
        s1, n1 = await start_node("n1")
        s2, n2 = await start_node("n2", seeds=[("n1", "127.0.0.1", n1.port)])
        try:
            sub = TestClient(s1.listeners[0].port, "subA")
            await sub.connect()
            await sub.subscribe("fleet/+/temp", qos=1)
            await settle()  # route delta flush -> n2 replica

            assert n2.routes.nodes_for("fleet/+/temp") == {"n1"}

            pub = TestClient(s2.listeners[0].port, "pubB")
            await pub.connect()
            await pub.publish("fleet/v1/temp", b"22C", qos=1)
            msg = await sub.recv_publish(timeout=5)
            assert msg.topic == "fleet/v1/temp" and msg.payload == b"22C"
            await sub.disconnect()
            await pub.disconnect()
        finally:
            await stop_node(s2, n2)
            await stop_node(s1, n1)

    run(t())


def test_route_replication_and_removal():
    async def t():
        s1, n1 = await start_node("n1")
        s2, n2 = await start_node("n2", seeds=[("n1", "127.0.0.1", n1.port)])
        try:
            c = TestClient(s1.listeners[0].port, "c1")
            await c.connect()
            await c.subscribe("a/b", qos=0)
            await c.subscribe("x/#", qos=0)
            await settle()
            assert n2.routes.nodes_for("a/b") == {"n1"}
            assert n2.routes.nodes_for("x/#") == {"n1"}

            await c.unsubscribe("a/b")
            await settle()
            assert n2.routes.nodes_for("a/b") == set()
            assert n2.routes.nodes_for("x/#") == {"n1"}
            await c.disconnect()
            await settle()  # session cleanup drops the last route too
            assert n2.routes.nodes_for("x/#") == set()
        finally:
            await stop_node(s2, n2)
            await stop_node(s1, n1)

    run(t())


def test_late_join_gets_existing_routes():
    async def t():
        s1, n1 = await start_node("n1")
        try:
            c = TestClient(s1.listeners[0].port, "c1")
            await c.connect()
            await c.subscribe("warehouse/+/door", qos=0)
            await settle()

            s2, n2 = await start_node(
                "n2", seeds=[("n1", "127.0.0.1", n1.port)]
            )
            try:
                # the sync exchange, not delta broadcast, carried this
                assert n2.routes.nodes_for("warehouse/+/door") == {"n1"}

                pub = TestClient(s2.listeners[0].port, "p1")
                await pub.connect()
                await pub.publish("warehouse/7/door", b"open", qos=0)
                msg = await c.recv_publish(timeout=5)
                assert msg.payload == b"open"
                await pub.disconnect()
            finally:
                await stop_node(s2, n2)
            await c.disconnect()
        finally:
            await stop_node(s1, n1)

    run(t())


def test_dead_node_routes_purged():
    async def t():
        s1, n1 = await start_node("n1")
        s2, n2 = await start_node("n2", seeds=[("n1", "127.0.0.1", n1.port)])
        n1.add_peer("n2", "127.0.0.1", n2.port)
        try:
            c2 = TestClient(s2.listeners[0].port, "c2")
            await c2.connect()
            await c2.subscribe("dead/+", qos=0)
            await settle()
            assert n1.routes.nodes_for("dead/+") == {"n2"}

            # kill n2 without cleanup: n1 must notice and purge
            await c2.close()
            await stop_node(s2, n2)
            for _ in range(40):
                if "n2" in n1._down:
                    break
                await asyncio.sleep(0.05)
            assert "n2" in n1._down
            assert n1.routes.nodes_for("dead/+") == set()
            # publishing on n1 no longer forwards (and does not error)
            s1.broker.publish_many([Message(topic="dead/x", payload=b"z")])
        finally:
            await stop_node(s1, n1)

    run(t())


def test_three_node_fanout():
    async def t():
        s1, n1 = await start_node("n1")
        seeds = [("n1", "127.0.0.1", n1.port)]
        s2, n2 = await start_node("n2", seeds=seeds)
        s3, n3 = await start_node(
            "n3", seeds=seeds + [("n2", "127.0.0.1", n2.port)]
        )
        n1.add_peer("n2", "127.0.0.1", n2.port)
        try:
            subs = []
            for srv, cid in ((s1, "sA"), (s2, "sB")):
                c = TestClient(srv.listeners[0].port, cid)
                await c.connect()
                await c.subscribe("news/#", qos=0)
                subs.append(c)
            await settle()

            pub = TestClient(s3.listeners[0].port, "p3")
            await pub.connect()
            await pub.publish("news/today", b"hi", qos=0)
            for c in subs:
                msg = await c.recv_publish(timeout=5)
                assert msg.payload == b"hi"
            await pub.disconnect()
            for c in subs:
                await c.disconnect()
        finally:
            await stop_node(s3, n3)
            await stop_node(s2, n2)
            await stop_node(s1, n1)

    run(t())


def test_forward_preserves_bytes_properties_and_skips_side_effects():
    """Code-review r2: bytes-valued MQTT 5 properties must survive the
    JSON transport, and a forwarded message must not re-run publish
    hooks/retain/rules on the receiving node."""

    async def t():
        s1, n1 = await start_node("n1")
        s2, n2 = await start_node("n2", seeds=[("n1", "127.0.0.1", n1.port)])
        try:
            hook_topics = []
            s1.broker.hooks.add(
                "message.publish", lambda m: hook_topics.append(m.topic) or m
            )
            sub = TestClient(s1.listeners[0].port, "subA")
            await sub.connect()
            await sub.subscribe("req/+", qos=1)
            await settle()

            pub = TestClient(s2.listeners[0].port, "pubB")
            await pub.connect()
            await pub.publish(
                "req/1",
                b"ask",
                qos=1,
                properties={
                    "correlation_data": b"\x00\x01\xff",
                    "response_topic": "resp/1",
                },
            )
            msg = await sub.recv_publish(timeout=5)
            assert msg.properties.get("correlation_data") == b"\x00\x01\xff"
            assert msg.properties.get("response_topic") == "resp/1"
            # publish hooks ran on the origin node only
            assert "req/1" not in hook_topics
            assert s1.broker.metrics.val("messages.forward.received") == 1
            await sub.disconnect()
            await pub.disconnect()
        finally:
            await stop_node(s2, n2)
            await stop_node(s1, n1)

    run(t())


def test_sync_snapshot_does_not_lose_racing_route_add():
    """A full-sync purge must not drop a route whose add cast raced past
    the snapshot on the other connection: the seq-guarded re-apply in
    _apply_snapshot keeps it."""

    async def t():
        srv_a, a = await start_node("a")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)])
        await settle(0.2)

        # simulate the race directly: B has applied an add from A at a
        # seq NEWER than the snapshot A would reply with
        await b._handle_route_ops(
            "a",
            {
                "node": "a",
                "epoch": a._epoch,
                "ops": [[a._op_seq + 1, "add", "raced/topic"]],
            },
        )
        assert "a" in b.routes.match_nodes(["raced/topic"])[0]
        # now a full sync with A's (older) snapshot runs: the purge must
        # re-apply the newer op from the log instead of dropping it
        await b._sync_with("a")
        assert "a" in b.routes.match_nodes(["raced/topic"])[0]
        # whereas an op INCLUDED in the snapshot window (seq <= snap) is
        # governed by the snapshot: a stale route is reconciled away
        await b._handle_route_ops(
            "a",
            {
                "node": "a",
                "epoch": a._epoch,
                "ops": [[a._op_seq, "add", "stale/topic"]]
                if a._op_seq > 0
                else [[0, "add", "stale/topic"]],
            },
        )
        if a._op_seq > 0:
            await b._sync_with("a")
            assert "a" not in b.routes.match_nodes(["stale/topic"])[0]

        await stop_node(srv_b, b)
        await stop_node(srv_a, a)

    run(t())


def test_sync_snapshot_never_unroutes_a_route_it_keeps():
    """A full sync that runs while windows are matched on executor
    threads: a route in both the table and the snapshot is matched at
    every step of the apply (no purge-then-re-add gap), a route the
    snapshot dropped goes, a new one comes."""

    async def t():
        srv_a, a = await start_node("a")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)])
        await settle(0.2)
        for flt in ("kept/#", "gone/#"):
            b.routes.add_route(flt, "a")
        steps = []
        add, delete = b.routes.add_route, b.routes.delete_route

        def seen(fn):
            def step(flt, node):
                out = fn(flt, node)
                steps.append(b.routes.match_nodes(["kept/x"])[0])
                return out
            return step

        b.routes.add_route = seen(add)
        b.routes.delete_route = seen(delete)
        b._apply_snapshot("a", ["kept/#", "new/#"], b._peer_seq.get("a", 0))
        assert steps and all("a" in nodes for nodes in steps)
        assert b.routes.routes_of("a") == {"kept/#", "new/#"}
        assert b.routes.match_nodes(["gone/x"])[0] == set()
        await stop_node(srv_b, b)
        await stop_node(srv_a, a)

    run(t())


def test_restart_epoch_resets_op_log():
    """A peer restart (new epoch) must invalidate the buffered op log so
    old-incarnation ops are not replayed over the new snapshot."""

    async def t():
        srv_a, a = await start_node("a")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)])
        await settle(0.2)
        await b._handle_route_ops(
            "a", {"node": "a", "epoch": 123, "ops": [[99, "add", "old/x"]]}
        )
        assert len(b._op_log["a"]) == 1
        # new epoch arrives: log resets, old op cannot resurrect
        b._check_epoch("a", 456)
        assert len(b._op_log["a"]) == 0
        b._apply_snapshot("a", [], 0)
        assert "a" not in b.routes.match_nodes(["old/x"])[0]
        await stop_node(srv_b, b)
        await stop_node(srv_a, a)

    run(t())


def test_restarted_node_advertises_boot_session_routes(tmp_path):
    """After a restart, a node's detached persistent-session filters
    must still be advertised as cluster routes so peers forward (and the
    home node persists) messages published in the restart→reconnect
    window."""

    async def t():
        # node A: durable broker; client subscribes and disconnects
        cfg = BrokerConfig()
        cfg.listeners[0].port = 0
        cfg.durable.enable = True
        cfg.durable.data_dir = str(tmp_path / "ds-a")
        srv_a = BrokerServer(cfg)
        await srv_a.start()
        c = TestClient(srv_a.listeners[0].port, "roamer")
        await c.connect(
            clean_start=False,
            properties={"session_expiry_interval": 3600},
        )
        await c.subscribe("fleet/+/pos", qos=1)
        await c.disconnect()
        await srv_a.stop()
        srv_a.broker.durable.close()

        # node A restarts (no client reconnect yet) and clusters with B
        cfg2 = BrokerConfig()
        cfg2.listeners[0].port = 0
        cfg2.durable.enable = True
        cfg2.durable.data_dir = str(tmp_path / "ds-a")
        srv_a2 = BrokerServer(cfg2)
        await srv_a2.start()
        node_a = ClusterNode("a", srv_a2.broker, **FAST)
        await node_a.start()
        srv_b, node_b = await start_node(
            "b", seeds=[("a", "127.0.0.1", node_a.port)]
        )
        await settle(0.3)

        # B sees A's boot-advertised route and forwards a publish
        assert "a" in node_b.routes.match_nodes(["fleet/7/pos"])[0]
        pub = TestClient(srv_b.listeners[0].port, "pub")
        await pub.connect()
        await pub.publish("fleet/7/pos", b"37.7,-122.4", qos=1)
        await pub.disconnect()
        await settle(0.2)

        # the reconnecting client replays the remote-origin message
        c2 = TestClient(srv_a2.listeners[0].port, "roamer")
        ack = await c2.connect(
            clean_start=False,
            properties={"session_expiry_interval": 3600},
        )
        assert ack.session_present
        pkt = await c2.recv_publish()
        assert pkt.topic == "fleet/7/pos"
        assert pkt.payload == b"37.7,-122.4"
        await c2.disconnect()

        await stop_node(srv_b, node_b)
        await node_a.stop()
        await srv_a2.stop()
        srv_a2.broker.durable.close()

    run(t())


def test_cross_node_session_takeover():
    """VERDICT r3 task 7: connect on A with QoS1 subs, disconnect,
    messages queue on A; reconnect on B with clean_start=false — the
    session (subs + queued messages) migrates and the client replays
    them on B (emqx_cm takeover semantics, emqx_cm.erl:276-317)."""

    async def t():
        srv_a, a = await start_node("a")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)])
        await settle(0.3)

        c = TestClient(srv_a.listeners[0].port, "roam-1")
        await c.connect(
            clean_start=False,
            properties={"session_expiry_interval": 3600},
        )
        await c.subscribe("inbox/roam-1/#", qos=1)
        await c.disconnect()
        await settle(0.1)

        # messages arrive while detached: they queue in A's session
        pub = TestClient(srv_b.listeners[0].port, "pubx")
        await pub.connect()
        await pub.publish("inbox/roam-1/m1", b"one", qos=1)
        await pub.publish("inbox/roam-1/m2", b"two", qos=1)
        await pub.disconnect()
        await settle(0.2)
        assert len(srv_a.broker.cm.lookup("roam-1").mqueue) == 2

        # reconnect on B: takeover migrates the session
        c2 = TestClient(srv_b.listeners[0].port, "roam-1")
        ack = await c2.connect(
            clean_start=False,
            properties={"session_expiry_interval": 3600},
        )
        assert ack.session_present
        got = {(await c2.recv_publish()).payload for _ in range(2)}
        assert got == {b"one", b"two"}
        # the session is gone from A and live on B
        assert srv_a.broker.cm.lookup("roam-1") is None
        assert srv_b.broker.cm.lookup("roam-1") is not None
        assert srv_a.broker.metrics.val("session.takenover") == 1

        # subscriptions moved too: a new publish on A routes to B
        await settle(0.2)
        pub2 = TestClient(srv_a.listeners[0].port, "puby")
        await pub2.connect()
        await pub2.publish("inbox/roam-1/m3", b"three", qos=1)
        pkt = await c2.recv_publish()
        assert pkt.payload == b"three"
        await pub2.disconnect()
        await c2.disconnect()
        await stop_node(srv_b, b)
        await stop_node(srv_a, a)

    run(t())


def test_takeover_of_live_connection_kicks_old_channel():
    """A still-connected session on A reconnecting via B must close A's
    channel with the takeover reason and keep exactly one live session."""

    async def t():
        srv_a, a = await start_node("a")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)])
        await settle(0.3)

        c1 = TestClient(srv_a.listeners[0].port, "dup-1")
        await c1.connect(
            clean_start=False,
            properties={"session_expiry_interval": 3600},
        )
        await c1.subscribe("d/#", qos=1)
        await settle(0.2)

        c2 = TestClient(srv_b.listeners[0].port, "dup-1")
        ack = await c2.connect(
            clean_start=False,
            properties={"session_expiry_interval": 3600},
        )
        assert ack.session_present  # session migrated from A
        await settle(0.2)
        assert srv_a.broker.cm.lookup("dup-1") is None
        # old connection got closed by the takeover
        pkt = await c1.recv(timeout=2.0)
        assert pkt is None or pkt.type == C.DISCONNECT
        await c2.disconnect()
        await stop_node(srv_b, b)
        await stop_node(srv_a, a)

    run(t())


def test_binary_wire_roundtrip():
    """Binary batch codec: bytes payloads, properties with bytes values
    (correlation_data), flags, and unicode topics all survive."""
    from emqx_tpu.cluster.wire import decode_messages, encode_messages

    msgs = [
        Message(
            topic="t/ü/1",
            payload=bytes(range(256)),
            qos=2,
            retain=True,
            from_client="c1",
            from_username="úser",
            properties={
                "correlation_data": b"\x00\xff",
                "user_property": [("k", "v")],
                "message_expiry_interval": 30,
            },
        ),
        Message(topic="t", payload=b"", qos=0, sys=True, dup=True),
    ]
    out = decode_messages(encode_messages(msgs))
    assert len(out) == 2
    a, b = out
    assert a.topic == "t/ü/1" and a.payload == bytes(range(256))
    assert a.qos == 2 and a.retain and a.from_username == "úser"
    assert a.properties["correlation_data"] == b"\x00\xff"
    assert a.properties["message_expiry_interval"] == 30
    assert b.sys and b.dup and b.payload == b""
    assert a.mid == msgs[0].mid


def test_forward_batching_coalesces_frames():
    """A burst of forwards to one peer leaves in (far) fewer frames than
    messages, and every message arrives."""

    async def t():
        # lww pinned: this test asserts the async cast_bin frame
        # coalescing; raft mode routes forwards through the
        # commit-confirmed forward_sync path instead
        srv_a, a = await start_node("a", consensus="lww")
        srv_b, b = await start_node(
            "b", seeds=[("a", "127.0.0.1", a.port)], consensus="lww"
        )
        await settle(0.3)

        sent_frames = [0]
        orig = a.transport.cast_bin

        async def counting(node, mtype, payload):
            if mtype == "forward_batch":
                sent_frames[0] += 1
            return await orig(node, mtype, payload)

        a.transport.cast_bin = counting

        sub = TestClient(srv_b.listeners[0].port, "s")
        await sub.connect()
        await sub.subscribe("burst/#", qos=0)
        await settle(0.2)

        pub = TestClient(srv_a.listeners[0].port, "p")
        await pub.connect()
        for i in range(200):
            await pub.send(
                C.Publish(topic=f"burst/{i}", payload=b"x", qos=0)
            )
        got = set()
        for _ in range(200):
            pkt = await sub.recv_publish()
            got.add(pkt.topic)
        assert got == {f"burst/{i}" for i in range(200)}
        assert 0 < sent_frames[0] < 50  # coalesced, not per-message
        await pub.disconnect()
        await sub.disconnect()
        await stop_node(srv_b, b)
        await stop_node(srv_a, a)

    run(t())


def test_clean_session_churn_does_not_leak_registry():
    """Zero-expiry sessions announce open AND close: churning clean
    clients must not grow the replicated client registry."""

    async def t():
        srv_a, a = await start_node("a")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)])
        await settle(0.3)
        for i in range(10):
            c = TestClient(srv_a.listeners[0].port, f"churn-{i}")
            await c.connect(clean_start=True)
            await c.disconnect()
        # the close announcements replicate within a heartbeat on a
        # quiet box; under the suite's six workers it can take longer
        for _ in range(100):
            await settle(0.05)
            if not any(cid.startswith("churn-")
                       for node in (a, b) for cid in node.clients):
                break
        assert not [
            cid for cid in a.clients if cid.startswith("churn-")
        ], a.clients
        assert not [
            cid for cid in b.clients if cid.startswith("churn-")
        ], b.clients
        await stop_node(srv_b, b)
        await stop_node(srv_a, a)

    run(t())


def test_clean_start_elsewhere_kicks_remote_duplicate():
    """Cluster-wide clientid uniqueness holds for clean_start=True too:
    the old node's live connection is kicked, no state transfers."""

    async def t():
        srv_a, a = await start_node("a")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)])
        await settle(0.3)
        c1 = TestClient(srv_a.listeners[0].port, "uniq-1")
        await c1.connect(
            clean_start=False,
            properties={"session_expiry_interval": 3600},
        )
        await settle(0.2)
        c2 = TestClient(srv_b.listeners[0].port, "uniq-1")
        ack = await c2.connect(clean_start=True)
        assert not ack.session_present
        await settle(0.3)
        assert srv_a.broker.cm.lookup("uniq-1") is None  # kicked
        assert srv_b.broker.cm.lookup("uniq-1") is not None
        await c2.disconnect()
        await stop_node(srv_b, b)
        await stop_node(srv_a, a)

    run(t())


def test_cluster_wide_config_update():
    """A config update on one node journals to every node (emqx_conf /
    emqx_cluster_rpc multicall semantics), including late joiners via
    sync catch-up.  lww pinned: this validates the journal layer,
    including a POST-COMMIT late joiner — raft mode freezes membership
    at bootstrap (raft-mode config propagation is covered by
    test_raft_cluster / test_raft_partition)."""

    async def t():
        srv_a, a = await start_node("a", consensus="lww")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)],
                                    consensus="lww")
        await settle(0.3)

        a.update_config("mqtt.max_inflight", 64)
        await settle(0.2)
        assert srv_a.broker.config.mqtt.max_inflight == 64
        assert srv_b.broker.config.mqtt.max_inflight == 64

        # a late joiner catches up from the journal at sync time
        srv_c, c = await start_node("c", seeds=[("a", "127.0.0.1", a.port)],
                                    consensus="lww")
        await settle(0.4)
        assert srv_c.broker.config.mqtt.max_inflight == 64

        # last-writer-wins across concurrent origins
        b.update_config("mqtt.max_inflight", 48)
        await settle(0.3)
        assert srv_a.broker.config.mqtt.max_inflight == 48
        assert srv_c.broker.config.mqtt.max_inflight == 48

        await stop_node(srv_c, c)
        await stop_node(srv_b, b)
        await stop_node(srv_a, a)

    run(t())


def test_session_survives_node_death_via_replication():
    """DS replication (simplified emqx_ds_builtin_raft): a persistent
    session's checkpoint and queued messages survive the death of the
    node that owned them — the client resumes on the buddy."""

    async def t():
        # lww pinned: buddy replication is the NON-raft DS path (raft
        # mode's quorum store is covered by test_raft_cluster)
        srv_a, a = await start_node("a", consensus="lww")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)],
                                    consensus="lww")
        await settle(0.3)

        c = TestClient(srv_a.listeners[0].port, "phoenix")
        await c.connect(
            clean_start=False,
            properties={"session_expiry_interval": 3600},
        )
        await c.subscribe("ash/#", qos=1)
        await c.disconnect()
        await settle(0.2)
        # the checkpoint was replicated to B (the only peer)
        assert b.replicas.info()["checkpoints"] == 1

        # messages published while detached queue on A AND replicate
        pub = TestClient(srv_b.listeners[0].port, "p")
        await pub.connect()
        await pub.publish("ash/1", b"rise", qos=1)
        await pub.disconnect()
        await settle(0.3)
        assert b.replicas.info()["buffered_messages"] >= 1

        # node A dies hard
        await stop_node(srv_a, a)
        await settle(0.5)  # B declares A down

        # the client lands on B: session restored from the replica
        c2 = TestClient(srv_b.listeners[0].port, "phoenix")
        ack = await c2.connect(
            clean_start=False,
            properties={"session_expiry_interval": 3600},
        )
        assert ack.session_present
        pkt = await c2.recv_publish()
        assert pkt.topic == "ash/1" and pkt.payload == b"rise"
        assert srv_b.broker.metrics.val("session.replica_restored") == 1

        # subscriptions came back too: new publishes deliver live
        pub2 = TestClient(srv_b.listeners[0].port, "p2")
        await pub2.connect()
        await pub2.publish("ash/2", b"again", qos=1)
        assert (await c2.recv_publish()).payload == b"again"
        await pub2.disconnect()
        await c2.disconnect()
        await stop_node(srv_b, b)

    run(t())


def test_replica_dropped_when_client_returns_to_owner():
    """A live reconnect on the owner invalidates the buddy's replica
    (the cadd registry op), preventing a later stale double-restore."""

    async def t():
        # lww pinned: replica-drop-on-cadd is the NON-raft DS path
        srv_a, a = await start_node("a", consensus="lww")
        srv_b, b = await start_node("b", seeds=[("a", "127.0.0.1", a.port)],
                                    consensus="lww")
        await settle(0.3)
        c = TestClient(srv_a.listeners[0].port, "rt")
        await c.connect(
            clean_start=False,
            properties={"session_expiry_interval": 600},
        )
        await c.subscribe("rt/#", qos=1)
        await c.disconnect()
        await settle(0.2)
        assert b.replicas.info()["checkpoints"] == 1
        # reconnect on A: the cadd op reaches B and clears the replica
        c2 = TestClient(srv_a.listeners[0].port, "rt")
        ack = await c2.connect(
            clean_start=False,
            properties={"session_expiry_interval": 600},
        )
        assert ack.session_present
        await settle(0.2)
        assert b.replicas.info()["checkpoints"] == 0
        await c2.disconnect()
        await stop_node(srv_b, b)
        await stop_node(srv_a, a)

    run(t())
