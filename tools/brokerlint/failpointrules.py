"""Failpoint-coverage rule (FP301).

ROADMAP used to carry a manual reminder that every new IO seam takes a
``failpoints.evaluate`` call; this rule is that reminder, enforced.
``SEAM_FUNCS`` declares the broker's real failure seams — the
functions where a fault injected in chaos runs exercises the SAME
recovery path a production fault would.  Each declared function must
contain a ``failpoints.evaluate``/``evaluate_async`` call, either
directly or through one same-module helper (``self._send_failpoint``
-style indirection resolves one level).

Growing the broker?  Add the new seam here AND in
``emqx_tpu.failpoints.SEAMS`` (the disabled-guard test iterates that
tuple), then give it a chaos test.
"""

from __future__ import annotations

import ast
from typing import List, NamedTuple, Sequence, Tuple

from .engine import ModuleContext, call_tail, is_failpoint_call


class Seam(NamedTuple):
    path_suffix: str   # module path suffix, posix ('cluster/transport.py')
    qualname: str      # dotted function name inside the module
    seam: str          # the failpoints.SEAMS name it must evaluate


# Kept in sync with emqx_tpu/failpoints.py SEAMS (tests/test_lint.py
# cross-checks the seam names against that tuple).
SEAM_FUNCS: Tuple[Seam, ...] = (
    Seam("emqx_tpu/engine.py", "MatchEngine._flat_dispatch",
         "engine.device_step"),
    Seam("emqx_tpu/engine.py", "MatchEngine._decide_device",
         "dispatch.decide.device"),
    Seam("emqx_tpu/engine.py", "MatchEngine._rules_device",
         "dispatch.rules.device"),
    Seam("emqx_tpu/cluster/transport.py", "NodeTransport.cast",
         "cluster.transport.send"),
    Seam("emqx_tpu/cluster/transport.py", "NodeTransport.cast_bin",
         "cluster.transport.send"),
    Seam("emqx_tpu/cluster/transport.py", "NodeTransport.call",
         "cluster.transport.send"),
    Seam("emqx_tpu/cluster/transport.py", "NodeTransport._on_conn",
         "cluster.transport.recv"),
    Seam("emqx_tpu/cluster/raft.py", "RaftNode._on_rpc",
         "cluster.raft.rpc"),
    Seam("emqx_tpu/ds/replication.py", "ReplicaStore.store_checkpoint",
         "ds.replication.store"),
    Seam("emqx_tpu/ds/replication.py", "ReplicaStore.append_messages",
         "ds.replication.store"),
    Seam("emqx_tpu/kafka.py", "KafkaClient.produce", "kafka.produce"),
    Seam("emqx_tpu/resources.py", "BufferWorker._run",
         "resource.buffer.query"),
    Seam("emqx_tpu/resources.py", "BufferWorker._flush_once",
         "resource.batch.flush"),
    Seam("emqx_tpu/bridge_mqtt.py", "MqttEgressResource.on_query_batch",
         "bridge.mqtt.send"),
    Seam("emqx_tpu/exhook/client.py", "ExhookClient._call",
         "exhook.call"),
    Seam("emqx_tpu/ds/beamformer.py", "Beamformer.poll",
         "ds.beamformer.poll"),
    Seam("emqx_tpu/cluster_link.py", "LinkServer._on_publish",
         "cluster.link.forward"),
    Seam("emqx_tpu/s3.py", "S3Client._request", "s3.request"),
    Seam("emqx_tpu/ds/persist.py", "DurableSessions._replay_read",
         "ds.replay.read"),
    Seam("emqx_tpu/ds/native.py", "DsLog.append", "ds.store.append"),
    Seam("emqx_tpu/ds/native.py", "DsLog.sync", "ds.store.sync"),
    Seam("emqx_tpu/ds/atomicio.py", "atomic_write_json",
         "ds.meta.write"),
    Seam("emqx_tpu/broker/resume.py", "ResumeScheduler._commit",
         "session.resume.commit"),
    Seam("emqx_tpu/cluster/quic_transport.py",
         "QuicPeerLink._transmit", "cluster.quic.send"),
    Seam("emqx_tpu/cluster/quic_transport.py",
         "QuicPeerLink._on_datagram", "cluster.quic.recv"),
    Seam("emqx_tpu/cluster/quic_transport.py",
         "QuicPeerEndpoint.transmit", "cluster.quic.send"),
    Seam("emqx_tpu/cluster/quic_transport.py",
         "QuicPeerEndpoint.on_datagram", "cluster.quic.recv"),
    Seam("emqx_tpu/cluster/node.py", "ClusterNode._send_fwd_ack",
         "cluster.forward.ack"),
    Seam("emqx_tpu/olp.py", "LoadMonitor.sample", "olp.sample"),
    Seam("emqx_tpu/olp.py", "LoadMonitor.shed", "olp.shed"),
    Seam("emqx_tpu/ds/journal.py", "MetaJournal.append",
         "ds.journal.append"),
    Seam("emqx_tpu/ds/native.py", "DsLog.gc", "ds.gc.reclaim"),
    Seam("emqx_tpu/broker/matchclient.py",
         "ServiceMatchEngine._ring_submit", "multicore.ring.submit"),
    Seam("emqx_tpu/broker/matchclient.py",
         "ServiceMatchEngine._ring_decide", "multicore.ring.submit"),
    Seam("emqx_tpu/broker/matchclient.py",
         "ServiceMatchEngine._ring_complete",
         "multicore.ring.complete"),
    Seam("emqx_tpu/broker/matchclient.py",
         "ServiceMatchEngine._reconnect_once",
         "multicore.service.restart"),
    Seam("emqx_tpu/broker/connection.py", "Connection._hand_over",
         "conn.sender.send"),
)


def _function_map(tree: ast.Module):
    """qualname -> FunctionDef/AsyncFunctionDef for the whole module."""
    out = {}

    def walk(node, prefix: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                walk(child, f"{prefix}{child.name}.")
            elif isinstance(child, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                out[f"{prefix}{child.name}"] = child
                walk(child, f"{prefix}{child.name}.")

    walk(tree, "")
    return out


def _evaluates_failpoint(fn, ctx: ModuleContext) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            if is_failpoint_call(node):
                return True
            # one level of same-module indirection:
            # `await self._send_failpoint(node)` counts when that
            # helper's body evaluates a failpoint
            if call_tail(node) in ctx.failpoint_methods:
                return True
    return False


def check(ctx: ModuleContext,
          seams: Sequence[Seam] = SEAM_FUNCS) -> None:
    relevant: List[Seam] = [
        s for s in seams if ctx.path.endswith(s.path_suffix)
    ]
    if not relevant:
        return
    fns = _function_map(ctx.tree)
    for s in relevant:
        fn = fns.get(s.qualname)
        if fn is None:
            ctx.report(
                ctx.tree, "FP301", s.qualname,
                f"declared failpoint seam function `{s.qualname}` not "
                f"found in {ctx.path} — update "
                f"tools/brokerlint/failpointrules.py:SEAM_FUNCS",
                detail=f"missing:{s.seam}",
            )
            continue
        if not _evaluates_failpoint(fn, ctx):
            ctx.report(
                fn, "FP301", s.qualname,
                f"IO seam `{s.qualname}` must evaluate failpoint "
                f"`{s.seam}` (failpoints.evaluate/_async) so chaos "
                f"runs can exercise its recovery path",
                detail=s.seam,
            )


__all__ = ["check", "Seam", "SEAM_FUNCS"]
