"""Chaos: cluster-plane fault injection through the failpoint seams.

A failpoint-driven partition (every frame crossing the leader dropped)
must produce a raft re-election on the surviving majority, commits
must keep succeeding there, and after the fault clears every node
converges on the committed history — no acknowledged write is lost.
A lossy+slow link (probabilistic drops, injected RPC latency) must
degrade throughput, never acknowledged durability."""

import asyncio
import tempfile

import pytest

from emqx_tpu import failpoints as fp
from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.cluster import ClusterNode
from emqx_tpu.config import BrokerConfig


FAST = dict(
    heartbeat_interval=0.05, down_after=0.4, flush_interval=0.002,
    consensus="raft", raft_fsync=False,
)


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.clear()
    yield
    fp.clear()


async def boot_cluster(n=3, prefix="chaos"):
    servers, nodes = [], []
    for i in range(n):
        cfg = BrokerConfig()
        cfg.listeners[0].port = 0
        srv = BrokerServer(cfg)
        await srv.start()
        node = ClusterNode(
            f"n{i}", srv.broker,
            raft_data_dir=tempfile.mkdtemp(prefix=f"{prefix}-n{i}-"),
            **FAST,
        )
        await node.transport.start()
        servers.append(srv)
        nodes.append(node)
    seeds = [(f"n{i}", "127.0.0.1", nodes[i].transport.port)
             for i in range(n)]
    for i, node in enumerate(nodes):
        await node.start(
            seeds=[s for j, s in enumerate(seeds) if j != i]
        )
    deadline = asyncio.get_event_loop().time() + 5
    while asyncio.get_event_loop().time() < deadline:
        if any(nd.raft_conf.role == "leader" for nd in nodes):
            break
        await asyncio.sleep(0.02)
    else:
        raise AssertionError("no raft_conf leader")
    return servers, nodes


async def shutdown(servers, nodes):
    for srv, node in zip(reversed(servers), reversed(nodes)):
        await node.stop()
        await srv.stop()


async def wait_leader_among(nodes, timeout=8.0):
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        for n in nodes:
            if n.raft_conf.role == "leader":
                return n
        await asyncio.sleep(0.05)
    raise AssertionError("no leader among survivors after injection")


def test_injected_partition_reelects_and_preserves_acked_writes():
    async def t():
        servers, nodes = await boot_cluster(3)
        try:
            # an acknowledged pre-fault write reaches everyone
            await nodes[0].update_config_async("mqtt.max_qos_allowed", 2)
            await asyncio.sleep(0.3)
            assert all(
                n.broker.config.mqtt.max_qos_allowed == 2 for n in nodes
            )

            old = next(n for n in nodes if n.raft_conf.role == "leader")
            rest = [n for n in nodes if n is not old]
            old_term = old.raft_conf.term
            # drop EVERY cluster frame crossing the leader, both
            # directions — a failpoint partition instead of the
            # transport.blocked test hook
            fp.configure("cluster.transport.send", "drop",
                         match=old.name)

            # the survivors re-elect through the injected partition
            leader = await wait_leader_among(rest)
            assert leader.raft_conf.term > old_term

            # ...and keep committing: this ack is a quorum promise
            await asyncio.wait_for(
                leader.update_config_async("mqtt.max_inflight", 7),
                timeout=10.0,
            )
            await asyncio.sleep(0.3)
            other = next(n for n in rest if n is not leader)
            assert other.broker.config.mqtt.max_inflight == 7

            # heal: the old leader adopts the committed history; both
            # acked writes survive on every node
            fp.clear("cluster.transport.send")
            deadline = asyncio.get_event_loop().time() + 12
            while asyncio.get_event_loop().time() < deadline:
                if old.broker.config.mqtt.max_inflight == 7:
                    break
                await asyncio.sleep(0.2)
            for n in nodes:
                assert n.broker.config.mqtt.max_inflight == 7
                assert n.broker.config.mqtt.max_qos_allowed == 2
        finally:
            await shutdown(servers, nodes)

    run(t())


def test_lossy_slow_link_commits_every_acknowledged_write():
    """25% frame loss (seeded) + 10ms injected latency on every raft
    RPC: slower consensus, but every acknowledged write is durable on
    a majority and converges everywhere once the chaos clears."""

    async def t():
        servers, nodes = await boot_cluster(3, prefix="lossy")
        try:
            fp.configure("cluster.transport.send", "drop",
                         prob=0.25, seed=20260803)
            fp.configure("cluster.raft.rpc", "delay", delay=0.01)

            acked = []
            for v in (3, 5, 9):
                await asyncio.wait_for(
                    nodes[0].update_config_async("mqtt.max_inflight", v),
                    timeout=15.0,
                )
                acked.append(v)
            assert acked == [3, 5, 9]

            fp.clear()
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline:
                if all(
                    n.broker.config.mqtt.max_inflight == 9
                    for n in nodes
                ):
                    break
                await asyncio.sleep(0.1)
            # the LAST acknowledged write is the converged state: no
            # acked write was lost or reordered away
            for n in nodes:
                assert n.broker.config.mqtt.max_inflight == 9
        finally:
            await shutdown(servers, nodes)

    run(t())


def test_raft_rpc_drop_forces_timeout_retry_path():
    """Dropping a bounded count of raft RPC replies exercises the
    submit retry loop without losing the proposal."""

    async def t():
        servers, nodes = await boot_cluster(3, prefix="rpcdrop")
        try:
            fp.configure("cluster.raft.rpc", "drop", times=4)
            await asyncio.wait_for(
                nodes[0].update_config_async("mqtt.max_awaiting_rel", 55),
                timeout=15.0,
            )
            assert [p for p in fp.list_points()][0]["fires"] >= 1
            # the proposal is committed once a majority has it; the
            # last follower applies it when its retried RPC lands
            deadline = asyncio.get_running_loop().time() + 15.0
            while any(
                n.broker.config.mqtt.max_awaiting_rel != 55 for n in nodes
            ):
                assert asyncio.get_running_loop().time() < deadline, [
                    n.broker.config.mqtt.max_awaiting_rel for n in nodes
                ]
                await asyncio.sleep(0.02)
        finally:
            await shutdown(servers, nodes)

    run(t())
