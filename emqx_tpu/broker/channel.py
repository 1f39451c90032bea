"""Per-connection protocol FSM.

Re-creates `emqx_channel` (/root/reference/apps/emqx/src/
emqx_channel.erl) as a pure-ish state machine: the CONNECT/auth flow
(:348-430), publish processing with QoS 0/1/2 acks (:615-631, 713-744),
subscribe/unsubscribe (:801-808), and the deliver side (:944-987).  IO
is injected: ``send(packets)`` writes to the transport, ``close(reason)``
tears it down; the asyncio connection drives timers.
"""

from __future__ import annotations

import asyncio
import logging
import secrets
import time
from typing import Dict, List, Optional, Tuple

from ..access import ClientInfo, PUBLISH, SUBSCRIBE
from ..aio import Gate
from ..codec import mqtt as C
from ..message import Message
from .. import topic as T
from .broker import Broker
from .resume import ResumeBusy
from .session import Session, SubOpts

log = logging.getLogger("emqx_tpu.channel")

# per-qos metric names, precomputed (an f-string per packet allocates
# on the hottest path)
_QOS_SENT = ("messages.qos0.sent", "messages.qos1.sent", "messages.qos2.sent")
_QOS_RECV = (
    "messages.qos0.received",
    "messages.qos1.received",
    "messages.qos2.received",
)

# channel states
CONNECTING = "connecting"
CONNECTED = "connected"
DISCONNECTED = "disconnected"

# v5 reason codes used here
RC_NORMAL = 0x00
RC_DISCONNECT_WITH_WILL = 0x04
RC_NO_MATCHING_SUBSCRIBERS = 0x10
RC_UNSPECIFIED = 0x80
RC_PROTOCOL_ERROR = 0x82
RC_NOT_AUTHORIZED = 0x87
RC_BAD_CLIENTID = 0x85
RC_BAD_AUTH = 0x86
RC_SERVER_BUSY = 0x89
RC_SESSION_TAKEN_OVER = 0x8E
RC_TOPIC_FILTER_INVALID = 0x8F
RC_TOPIC_NAME_INVALID = 0x90
RC_PACKET_ID_IN_USE = 0x91
RC_NO_SUBSCRIPTION_EXISTED = 0x11
RC_RECEIVE_MAX_EXCEEDED = 0x93
RC_TOPIC_ALIAS_INVALID = 0x94
RC_QUOTA_EXCEEDED = 0x97
RC_SHARED_SUB_UNSUPPORTED = 0x9E
RC_WILDCARD_SUB_UNSUPPORTED = 0xA2

# CONNACK codes for MQTT < 5 (emqx_reason_codes:connack_error)
_V3_CONNACK = {
    RC_BAD_CLIENTID: 2,
    RC_SERVER_BUSY: 3,
    RC_BAD_AUTH: 4,
    RC_NOT_AUTHORIZED: 5,
}


class Channel:
    # lazily-resolved metric slot tuples (shared: one registry per
    # process), so the per-packet hot path pays one lock per group
    # instead of one per counter
    _recv_slots = None
    _sent_slots = None
    _auth_ok = None
    _ack_slots = None

    def _ack_run_slots(self, m):
        slots = Channel._ack_slots
        if slots is None:
            rx = m.slots("packets.received", "packets.puback.received")
            acked = m.slots("messages.acked")
            slots = Channel._ack_slots = (rx, acked, rx + acked)
        return slots

    def _auth_ok_slots(self, m):
        ok = Channel._auth_ok
        if ok is None:
            ok = Channel._auth_ok = m.slots(
                "client.authorize", "authorization.allow"
            )
        return ok

    def __init__(
        self,
        broker: Broker,
        send,
        close,
        peer: str = "",
        mountpoint: Optional[str] = None,
    ) -> None:
        self.broker = broker
        self._send = send
        self._close = close
        self.state = CONNECTING
        self.version = C.MQTT_V5
        self.client: Optional[ClientInfo] = None
        self.session: Optional[Session] = None
        self.keepalive = 0.0
        self.peer = peer
        self.mountpoint = mountpoint
        self.will_msg: Optional[Message] = None
        self._alias_in: Dict[int, str] = {}
        self.last_rx = time.time()
        self.connected_at: Optional[float] = None
        self._closing = False
        self._pending_connect = None  # in-flight async-connect task
        self._connect_backlog: List[C.Packet] = []  # pipelined pre-CONNACK
        # ordered async-verdict continuation chain: tail task, ALL
        # live tasks (shutdown cancels every one, not just the tail),
        # depth for backpressure (the chain is upstream of the batcher
        # lanes, so the connection's read loop must pause on IT too)
        self._defer_tail = None
        self._defer_tasks: set = set()
        self._defer_depth = 0
        self._defer_drained: Optional[Gate] = None
        self.DEFER_HIGH = 256
        self.DEFER_LOW = 64
        # write coalescing: while corked (dispatch window / batched ack
        # resolution), outgoing packets buffer and flush as ONE
        # concatenated transport.write on uncork
        self._cork_depth = 0
        self._cork_buf: List[C.Packet] = []
        # wired by the owning Connection: () -> bytes buffered toward
        # this client, in the transport and with the sender thread
        # (the outbound high-watermark signal; None = transport can't
        # report, watermark inactive)
        self.transport_buffered = None

    def out_buffered(self) -> int:
        """Bytes buffered toward this client in the transport and
        with the native sender thread (the per-connection outbound
        high-watermark input; cork buffers flush within the same
        window, so these are the unbounded part a stalled subscriber
        grows)."""
        fn = self.transport_buffered
        if fn is None:
            return 0
        try:
            return fn()
        except Exception:
            return 0

    # ---------------------------------------------------------- util

    def cork(self) -> None:
        """Begin a write-coalescing scope: until the matching
        `uncork`, `send_packets` buffers instead of writing, so a
        dispatch window's deliveries (or a batch's acks) reach the
        transport as one concatenated write per connection.  Scopes
        are synchronous on the loop thread — nothing interleaves —
        and nest via a depth counter."""
        self._cork_depth += 1

    def uncork(self) -> None:
        if self._cork_depth:
            self._cork_depth -= 1
        if self._cork_depth == 0 and self._cork_buf:
            buf, self._cork_buf = self._cork_buf, []
            if not self._closing:
                self._send(buf)

    def _pub_sent_slots(self, m):
        sent = Channel._sent_slots
        if sent is None:
            sent = Channel._sent_slots = tuple(
                m.slots("messages.sent", q, "packets.publish.sent")
                for q in _QOS_SENT
            )
        return sent

    def send_packets(self, packets: List[C.Packet]) -> None:
        if packets and not self._closing:
            m = self.broker.metrics
            sent = self._pub_sent_slots(m)
            # count per qos first, then ONE locked bump per class —
            # a 256-subscriber fan-out was 768 lock acquisitions
            npub = [0, 0, 0]
            for p in packets:
                if p.type == C.PUBLISH:
                    npub[p.qos] += 1
            for q in (0, 1, 2):
                if npub[q]:
                    m.inc_slots(sent[q], npub[q])
            if self._cork_depth:
                self._cork_buf.extend(packets)
                return
            self._send(packets)

    def send_wire(self, data, npub: Tuple[int, int, int],
                  count: bool = True) -> bool:
        """One pre-assembled delivery run (the native window fast
        path): the same per-qos metric slots `send_packets` bumps,
        then ONE `Raw` blob into the corked buffer — per delivery the
        channel does no Python work at all.  ``count=False`` skips
        the metric bumps for callers that batch a whole WINDOW's
        sent counters into one flush (the splice-plan dispatch);
        returns False when the blob was dropped (closing channel) so
        those callers don't count bytes that never shipped."""
        if self._closing:
            return False
        total = npub[0] + npub[1] + npub[2]
        if count:
            m = self.broker.metrics
            sent = self._pub_sent_slots(m)
            for q in (0, 1, 2):
                if npub[q]:
                    m.inc_slots(sent[q], npub[q])
        pkt = C.Raw(data, self.version, total)
        if self._cork_depth:
            self._cork_buf.append(pkt)
            return True
        self._send([pkt])
        return True

    def close(self, reason: str) -> None:
        """CM-initiated close (takeover/kick): tell a v5 client why."""
        if self._closing:
            return
        if self.version == C.MQTT_V5 and self.state == CONNECTED:
            rc = {
                "takenover": RC_SESSION_TAKEN_OVER,
                "evacuated": 0x9C,  # use another server (rebalance)
                # olp L3 force-close of a slow subscriber: server busy
                # tells the client to back off, not that it misbehaved
                "olp_overloaded": RC_SERVER_BUSY,
            }.get(reason, RC_UNSPECIFIED)
            self._send([C.Disconnect(reason_code=rc)])
        if reason == "takenover":
            # session moves to the new channel; don't tear it down
            self.session = None
            self.will_msg = None
        self._shutdown(reason)

    def _shutdown(self, reason: str) -> None:
        self._closing = True
        self.state = DISCONNECTED
        self._cork_buf = []  # never flush past teardown
        # cancel the WHOLE deferred chain: cancelling only the tail
        # would leave every predecessor running verdict RPCs and
        # touching channel state long after the socket died
        for t in list(self._defer_tasks):
            t.cancel()
        self._defer_tasks.clear()
        self._defer_tail = None
        self._close(reason)

    @property
    def defer_saturated(self) -> bool:
        return self._defer_depth >= self.DEFER_HIGH

    async def wait_defer_drain(self) -> None:
        while self._defer_depth > self.DEFER_LOW and not self._closing:
            if self._defer_drained is None:
                self._defer_drained = Gate()
            self._defer_drained.clear()
            # depth transitions happen in done-callbacks on this same
            # loop: no await between the check and the wait, so no
            # lost wakeup
            if self._defer_depth <= self.DEFER_LOW:
                return
            await self._defer_drained.wait()

    def when_defer_drained(self, resume) -> None:
        """`wait_defer_drain` for a reader with no coroutine to park
        (`Connection.data_received`): ``resume()`` runs once the
        chain is down to ``DEFER_LOW``, at once if it is."""
        if self._defer_depth <= self.DEFER_LOW or self._closing:
            resume()
            return
        if self._defer_drained is None:
            self._defer_drained = Gate()
        self._defer_drained.clear()
        self._defer_drained.call(resume)

    def _defer(self, coro) -> None:
        """Chain an async continuation behind any previously deferred
        packet so per-connection packet ORDER survives the off-loop
        verdict wait (exhook authorize): each deferred handler runs
        only after its predecessor resolves."""
        prev = self._defer_tail
        self._defer_depth += 1

        async def run() -> None:
            if prev is not None:
                # wait() swallows the predecessor's failure/cancel (it
                # must never skip THIS packet) while still propagating
                # our own cancellation from _shutdown
                try:
                    await asyncio.wait({prev})
                except asyncio.CancelledError:
                    coro.close()  # un-started coroutine: no RuntimeWarning
                    raise
            try:
                await coro
            except asyncio.CancelledError:
                raise
            except Exception:
                log.exception("deferred packet handling failed")

        task = asyncio.get_running_loop().create_task(run())
        self._defer_tasks.add(task)

        def done(t, channel=self):
            channel._defer_tasks.discard(t)
            channel._defer_depth -= 1
            if channel._defer_tail is t:
                channel._defer_tail = None
            if (
                channel._defer_drained is not None
                and channel._defer_depth <= channel.DEFER_LOW
            ):
                channel._defer_drained.set()

        task.add_done_callback(done)
        self._defer_tail = task

    def _mount(self, topic: str) -> str:
        return self.mountpoint + topic if self.mountpoint else topic

    def _unmount(self, topic: str) -> str:
        if self.mountpoint and topic.startswith(self.mountpoint):
            return topic[len(self.mountpoint) :]
        return topic

    # ------------------------------------------------------ incoming

    def handle_in(self, pkt: C.Packet) -> None:
        """One parsed packet from the wire (emqx_channel:handle_in/2),
        or one `AckRun` of them."""
        self.last_rx = time.time()
        if pkt.type == C.ACK_RUN:
            self._handle_ack_run(pkt)
            return
        m = self.broker.metrics
        m.inc("packets.received")
        if self.state == CONNECTING:
            if self._pending_connect is not None:
                # CONNECT is resolving asynchronously (HTTP auth or
                # remote takeover).  Clients may legally pipeline
                # packets before CONNACK — buffer them (bounded) and
                # replay once connected; a second CONNECT is fatal.
                if pkt.type == C.CONNECT:
                    self._shutdown("protocol_error")  # [MQTT-3.1.0-2]
                elif len(self._connect_backlog) >= 64:
                    self._shutdown("connect_backlog_overflow")
                else:
                    self._connect_backlog.append(pkt)
                return
            if pkt.type != C.CONNECT:
                self._shutdown("protocol_error")
                return
            self._handle_connect(pkt)
            return
        t = pkt.type
        if t == C.CONNECT:
            self._disconnect_with(RC_PROTOCOL_ERROR)  # [MQTT-3.1.0-2]
        elif t == C.PUBLISH:
            self._handle_publish(pkt)
        elif t == C.PUBACK:
            m.inc("packets.puback.received")
            ok, out = self.session.puback(pkt.packet_id)
            if ok:
                m.inc("messages.acked")
                self.broker.hooks.run(
                    "message.acked", self.client.clientid, pkt.packet_id
                )
            self.send_packets(out)
        elif t == C.PUBREC:
            m.inc("packets.pubrec.received")
            ok, out = self.session.pubrec(pkt.packet_id)
            if out:
                m.inc("packets.pubrel.sent")
            self.send_packets(out)
        elif t == C.PUBREL:
            m.inc("packets.pubrel.received")
            found = self.session.pubrel(pkt.packet_id)
            rc = RC_NORMAL if found else RC_PACKET_ID_IN_USE + 1  # 0x92
            m.inc("packets.pubcomp.sent")
            self.send_packets(
                [C.Pubcomp(packet_id=pkt.packet_id,
                           reason_code=0 if found else 0x92)]
            )
        elif t == C.PUBCOMP:
            m.inc("packets.pubcomp.received")
            ok, out = self.session.pubcomp(pkt.packet_id)
            if ok:
                m.inc("messages.acked")
            self.send_packets(out)
        elif t == C.SUBSCRIBE:
            self._handle_subscribe(pkt)
        elif t == C.UNSUBSCRIBE:
            self._handle_unsubscribe(pkt)
        elif t == C.PINGREQ:
            m.inc("packets.pingreq.received")
            m.inc("packets.pingresp.sent")
            self.send_packets([C.Pingresp()])
        elif t == C.DISCONNECT:
            self._handle_disconnect(pkt)
        elif t == C.AUTH:
            m.inc("packets.auth.received")
            self._disconnect_with(RC_PROTOCOL_ERROR)  # no enhanced auth yet
        else:
            self._shutdown("protocol_error")

    def _handle_ack_run(self, run: C.AckRun) -> None:
        """A read's run of minimal PUBACKs (`StreamParser` with
        ``ack_runs``): every counter, the session and the hook end
        where `handle_in` a `Puback` would leave them, with one clock
        read, one locked bump and one `send_packets` for the run."""
        if self.state != CONNECTED:
            # the state guards are a packet's: before CONNECT the first
            # is the protocol error, while CONNECT resolves the run
            # joins the backlog as packets (and may overflow it)
            for pkt in run.packets():
                if self._closing:
                    break
                self.handle_in(pkt)
            return
        pids = run.packet_ids
        known, out = self.session.puback_run(pids)
        m = self.broker.metrics
        rx, acked, both = self._ack_run_slots(m)
        if len(known) == len(pids):
            m.inc_slots(both, len(pids))
        else:
            m.inc_slots(rx, len(pids))
            if known:
                m.inc_slots(acked, len(known))
        hooks = self.broker.hooks
        if known and hooks.has("message.acked"):
            clientid = self.client.clientid
            for pid in known:
                hooks.run("message.acked", clientid, pid)
        self.send_packets(out)

    # ------------------------------------------------------- connect

    def _handle_connect(self, pkt: C.Connect) -> None:
        m = self.broker.metrics
        m.inc("packets.connect.received")
        m.inc("client.connect")
        self.version = pkt.proto_ver
        self.broker.hooks.run("client.connect", pkt)
        mqtt = self.broker.config.mqtt

        clientid = pkt.client_id
        assigned = None
        if not clientid:
            if self.version < C.MQTT_V5 and not pkt.clean_start:
                self._connack_error(RC_BAD_CLIENTID)  # [MQTT-3.1.3-8]
                return
            clientid = assigned = "emqx_tpu_" + secrets.token_hex(8)
        if len(clientid) > mqtt.max_clientid_len:
            self._connack_error(RC_BAD_CLIENTID)
            return

        if (self.broker.eviction.status in ("evacuating", "evacuated")
                or self.broker.rebalance.shedding):
            # a draining node refuses new sessions so clients land on a
            # peer (the reference eviction agent's connect rejection);
            # a rebalance donor refuses too, else shed clients bounce
            # straight back through the load balancer
            m.inc("client.evacuation_refused")
            self._connack_error(RC_SERVER_BUSY if self.version < C.MQTT_V5
                                else 0x9C)
            return
        peerhost = self.peer.rsplit(":", 1)[0] if self.peer else ""
        if self.broker.banned.is_banned(
            clientid=clientid, username=pkt.username, peerhost=peerhost
        ):
            m.inc("client.banned")
            self._connack_error(0x8A)  # banned ([MQTT-3.2.2.2])
            return
        if self.broker.olp.refuse_connect():
            # olp ladder L2: CONNECT burst over the admission budget —
            # server-busy BEFORE auth/session work so refusal is the
            # cheapest path through the broker (counted + alarmed)
            self._connack_error(RC_SERVER_BUSY)
            return
        client = ClientInfo(
            clientid=clientid,
            username=pkt.username,
            password=pkt.password,
            peerhost=self.peer,
            mountpoint=self.mountpoint,
        )
        m.inc("client.authenticate")
        access = self.broker.access
        if access.has_async_authn or access.has_async_authz:
            # IO-backed providers (HTTP/DB) must not block the loop:
            # defer the rest of CONNECT until the chain resolves (and
            # the DB ACL prefetch lands — authorize() on the hot path
            # only reads the cache)
            import asyncio

            self._pending_connect = asyncio.get_running_loop().create_task(
                self._async_auth_connect(pkt, clientid, assigned, client)
            )
            return
        ok, client = access.authenticate(client)
        self._post_auth_connect(pkt, clientid, assigned, client, ok)

    async def _async_auth_connect(
        self, pkt, clientid, assigned, client
    ) -> None:
        try:
            access = self.broker.access
            if access.has_async_authn:
                ok, client = await access.authenticate_async(client)
            else:
                ok, client = access.authenticate(client)
            if ok:
                await access.prefetch_acl(client)
        except Exception:
            log.exception("async authentication failed for %s", clientid)
            ok = False
        self._pending_connect = None
        if self.state != CONNECTING:
            return
        self._post_auth_connect(pkt, clientid, assigned, client, ok)

    def _post_auth_connect(
        self, pkt, clientid, assigned, client, ok
    ) -> None:
        m = self.broker.metrics
        mqtt = self.broker.config.mqtt
        if not ok:
            m.inc("packets.publish.auth_error")
            self._connack_error(RC_BAD_AUTH)
            return
        if client.username is None:
            m.inc("client.auth.anonymous")
        client.password = None  # never retain credentials
        self.client = client

        expiry = float(
            pkt.properties.get("session_expiry_interval", 0)
            if self.version == C.MQTT_V5
            else (0 if pkt.clean_start else mqtt.session_expiry_interval)
        )
        receive_max = pkt.properties.get("receive_maximum")

        ext = self.broker.external
        if (
            ext is not None
            and pkt.clean_start
            and ext.remote_owner(clientid) is not None
        ):
            # clientid uniqueness is cluster-wide regardless of
            # clean_start: a duplicate live connection on another node
            # must be kicked (the reference discards the remote session
            # either way; no state transfer is wanted here)
            ext.discard_remote(clientid)
        durable = self.broker.durable
        if (
            not pkt.clean_start
            and ext is not None
            and self.broker.cm.lookup(clientid) is None
            and (
                # a live remote owner ALWAYS wins (its state is fresher
                # than any local disk checkpoint); otherwise only defer
                # when there is no local checkpoint to resume from
                ext.remote_owner(clientid) is not None
                or durable is None
                or not durable.has_checkpoint(clientid)
            )
        ):
            # the session may live elsewhere: a live peer (takeover) or
            # a replica of a dead node's session — fetch asynchronously
            # (the reference's cross-node takeover, emqx_cm.erl:314-317)
            # and finish the CONNECT when the lookup resolves
            import asyncio

            self._pending_connect = asyncio.get_running_loop().create_task(
                self._remote_connect(
                    pkt, clientid, assigned, client, expiry, receive_max
                )
            )
            return
        self._finish_connect(
            pkt, clientid, assigned, client, expiry, receive_max, None
        )

    async def _remote_connect(
        self, pkt, clientid, assigned, client, expiry, receive_max
    ) -> None:
        import asyncio

        # the takeover DESTROYS the session on the owning node, so the
        # fetched state must never be dropped: shield the RPC from our
        # own cancellation and re-home the state as a detached local
        # session if this connection dies mid-flight
        inner = asyncio.get_running_loop().create_task(
            self.broker.external.fetch_session(clientid)
        )

        def rescue(task: "asyncio.Task") -> None:
            if task.cancelled() or task.exception() is not None:
                return
            state = task.result()
            if state and self.broker.cm.lookup(clientid) is None:
                self.broker.adopt_orphan_session(clientid, state, expiry)

        try:
            state = await asyncio.shield(inner)
        except asyncio.CancelledError:
            inner.add_done_callback(rescue)
            raise
        except Exception:
            log.exception("remote takeover of %s failed", clientid)
            state = None
        self._pending_connect = None
        if self.state != CONNECTING:
            if state and self.broker.cm.lookup(clientid) is None:
                self.broker.adopt_orphan_session(clientid, state, expiry)
            return  # connection died while fetching
        self._finish_connect(
            pkt, clientid, assigned, client, expiry, receive_max, state
        )

    def _finish_connect(
        self, pkt, clientid, assigned, client, expiry, receive_max, imported
    ) -> None:
        m = self.broker.metrics
        mqtt = self.broker.config.mqtt
        if imported is not None and self.broker.durable is not None:
            # the fetched (takeover/replica) state supersedes any stale
            # local checkpoint — drop it or open_session would resurrect
            # the older state and discard the fresh import
            self.broker.durable.drop_checkpoint(clientid)
        try:
            session, present = self.broker.open_session(
                pkt.clean_start,
                clientid,
                self,
                expiry_interval=expiry,
                max_inflight=min(
                    mqtt.max_inflight, receive_max or mqtt.max_inflight
                ),
            )
        except ResumeBusy:
            # resume admission saturated (mass-reconnect storm): the
            # client backs off and retries instead of the broker
            # buffering another session's replay state
            self._connack_error(RC_SERVER_BUSY)
            return
        self.session = session
        if imported is not None and not present:
            self.broker.import_session(session, imported)
            present = True  # the client's session DID survive — elsewhere
        self.broker.cancel_will(clientid)  # reconnect cancels a delayed will
        if present:
            m.inc("session.resumed")
            self.broker.hooks.run("session.resumed", clientid)
            # re-register subscriptions in case the router was cleaned
            for flt, opts in session.subscriptions.items():
                self.broker.router.subscribe(clientid, flt, opts)

        if pkt.will is not None:
            self.will_msg = Message(
                topic=self._mount(pkt.will.topic),
                payload=pkt.will.payload,
                qos=min(pkt.will.qos, mqtt.max_qos_allowed),
                retain=pkt.will.retain,
                from_client=clientid,
                from_username=client.username,
                properties=dict(pkt.will.properties),
            )

        self.keepalive = float(
            mqtt.server_keepalive
            if (mqtt.server_keepalive and self.version == C.MQTT_V5)
            else pkt.keepalive
        )

        props: C.Properties = {}
        if self.version == C.MQTT_V5:
            if assigned is not None:
                props["assigned_client_identifier"] = assigned
            if mqtt.server_keepalive:
                props["server_keep_alive"] = mqtt.server_keepalive
            if mqtt.max_qos_allowed < 2:
                props["maximum_qos"] = mqtt.max_qos_allowed
            if not mqtt.retain_available:
                props["retain_available"] = 0
            if not mqtt.wildcard_subscription:
                props["wildcard_subscription_available"] = 0
            if not mqtt.shared_subscription:
                props["shared_subscription_available"] = 0
            props["topic_alias_maximum"] = mqtt.max_topic_alias
            props["receive_maximum"] = mqtt.max_inflight
            props["session_expiry_interval"] = int(expiry)
            props["maximum_packet_size"] = mqtt.max_packet_size
            # subscription ids ARE supported (SubOpts.subid), so the
            # property is advertised only in the spec's negative form
            # when a deployment turns them off — currently always on

        self.state = CONNECTED
        self.connected_at = time.time()
        m.inc("packets.connack.sent")
        m.inc("client.connack")
        m.inc("client.connected")
        self.broker.hooks.run("client.connected", client)
        self.send_packets(
            [C.Connack(session_present=present, reason_code=0,
                       properties=props)]
        )
        # server-side auto-subscribe (emqx_auto_subscribe): applied on
        # every connect through the SAME validation/mountpoint/authz
        # gauntlet a client SUBSCRIBE passes; re-subscribing is a no-op
        for entry in self.broker.config.auto_subscribe:
            flt = (
                entry["topic"]
                .replace("%c", clientid)
                .replace("%u", client.username or "")
            )
            try:
                T.validate_filter(flt)
            except ValueError:
                log.warning("invalid auto_subscribe filter %r", flt)
                continue
            full = self._mount(flt)
            if not self.broker.access.authorize(client, SUBSCRIBE, full):
                continue
            opts = SubOpts(qos=int(entry.get("qos", 0)))
            is_new = session.subscribe(full, opts)
            self.broker.subscribe(clientid, full, opts, is_new_sub=is_new)
        if present:
            self.send_packets(session.resume())
        # replay packets the client pipelined while CONNECT resolved
        backlog, self._connect_backlog = self._connect_backlog, []
        for pending in backlog:
            if self.state != CONNECTED:
                break
            self.handle_in(pending)

    def _connack_error(self, rc: int) -> None:
        code = rc if self.version == C.MQTT_V5 else _V3_CONNACK.get(rc, 3)
        self.broker.metrics.inc("packets.connack.sent")
        self._send([C.Connack(session_present=False, reason_code=code)])
        self._shutdown("connack_error")

    # ------------------------------------------------------- publish

    def _resolve_alias(self, pkt: C.Publish) -> Optional[str]:
        """MQTT 5 topic-alias resolution; None => protocol error."""
        alias = pkt.properties.get("topic_alias")
        if alias is None:
            return pkt.topic
        if (
            not isinstance(alias, int)
            or alias == 0
            or alias > self.broker.config.mqtt.max_topic_alias
        ):
            return None
        if pkt.topic:
            self._alias_in[alias] = pkt.topic
            return pkt.topic
        return self._alias_in.get(alias)

    def _handle_publish(self, pkt: C.Publish) -> None:
        # STICKY while the chain is non-empty: if the async-authorize
        # hook unloads mid-stream, later publishes must still queue
        # BEHIND the ones already deferred or they would overtake them
        # (per-publisher ordering, topic-alias state)
        if (self.broker.access.has_async_authz_hooks
                or self._defer_depth > 0):
            # IO-backed authorize (exhook): the verdict RPC must not
            # block the loop — defer this packet's handling into the
            # channel's ordered continuation chain
            try:
                asyncio.get_running_loop()
            except RuntimeError:
                pass  # no loop (unit tests): fall through, block
            else:
                self._defer(self._handle_publish_async(pkt))
                return
        full_topic = self._publish_validate(pkt)
        if full_topic is None:
            return
        ok = self.broker.access.authorize(self.client, PUBLISH, full_topic)
        self._publish_post_auth(pkt, full_topic, ok)

    async def _handle_publish_async(self, pkt: C.Publish) -> None:
        full_topic = self._publish_validate(pkt)
        if full_topic is None:
            return
        ok = await self.broker.access.authorize_async(
            self.client, PUBLISH, full_topic
        )
        if self._closing or self.state != CONNECTED:
            return  # channel died while the verdict was in flight
        self._publish_post_auth(pkt, full_topic, ok)

    def _publish_validate(self, pkt: C.Publish) -> Optional[str]:
        """Pre-authorize validation; returns the mounted topic, or
        None after responding/disconnecting."""
        m = self.broker.metrics
        recv = Channel._recv_slots
        if recv is None:
            recv = Channel._recv_slots = tuple(
                m.slots("packets.publish.received", "messages.received", q)
                for q in _QOS_RECV
            )
        m.inc_slots(recv[pkt.qos])

        topic = self._resolve_alias(pkt) if self.version == C.MQTT_V5 else pkt.topic
        if topic is None:
            self._disconnect_with(RC_TOPIC_ALIAS_INVALID)
            return None
        try:
            T.validate_name(topic)
        except ValueError:
            m.inc("packets.publish.error")
            self._disconnect_with(RC_TOPIC_NAME_INVALID)
            return None
        mqtt = self.broker.config.mqtt
        if pkt.qos > mqtt.max_qos_allowed:
            self._disconnect_with(0x9B)  # QoS not supported
            return None
        if pkt.retain and not mqtt.retain_available:
            self._disconnect_with(0x9A)  # retain not supported
            return None
        return self._mount(topic)

    def _publish_post_auth(
        self, pkt: C.Publish, full_topic: str, ok: bool
    ) -> None:
        m = self.broker.metrics
        if not ok:
            m.inc("client.authorize")
            m.inc("authorization.deny")
            m.inc("packets.publish.auth_error")
            self._publish_denied(pkt)
            return
        m.inc_slots(self._auth_ok_slots(m))

        olp = self.broker.olp
        if pkt.qos == 0 and olp.shed_ingress_qos0:
            # olp ladder L3: QoS0 drops at publish ingress — no route,
            # no persistence, no ack owed (QoS0 has none); counted and
            # carried on the overload alarm, never silent
            m.inc("messages.dropped")
            m.inc("messages.dropped.olp_shed")
            olp.shed("shed.publish_qos0")
            return

        props = {
            k: v for k, v in pkt.properties.items() if k != "topic_alias"
        }
        msg = Message(
            topic=full_topic,
            payload=pkt.payload,
            qos=pkt.qos,
            retain=pkt.retain,
            from_client=self.client.clientid,
            from_username=self.client.username,
            properties=props,
        )

        batcher = self.broker.batcher
        if pkt.qos == 0:
            if batcher is not None:
                batcher.publish_nowait(msg, source=self)  # fire-and-forget
            else:
                self.broker.publish(msg)
            return
        if pkt.qos == 1:
            if batcher is not None:
                # ack resolves from the batch future — the whole window
                # is one device step, PUBACKs stream out in batch order
                batcher.publish(msg, source=self).add_done_callback(
                    lambda f, pid=pkt.packet_id: self._publish_acked(
                        pid, 1, f
                    )
                )
            else:
                self._send_pub_ack(pkt.packet_id, 1, self.broker.publish(msg))
            return
        # QoS 2: route immediately, dedup on packet id until PUBREL
        st = self.session.awaiting_rel_add(pkt.packet_id)
        if st == "in_use":
            m.inc("packets.pubrec.sent")
            self.send_packets(
                [C.Pubrec(packet_id=pkt.packet_id, reason_code=0)]
            )
            return
        if st == "full":
            m.inc("messages.dropped")
            m.inc("messages.dropped.await_pubrel_timeout")
            self._disconnect_with(RC_RECEIVE_MAX_EXCEEDED)
            return
        if batcher is not None:
            batcher.publish(msg, source=self).add_done_callback(
                lambda f, pid=pkt.packet_id: self._publish_acked(pid, 2, f)
            )
        else:
            self._send_pub_ack(pkt.packet_id, 2, self.broker.publish(msg))

    def _publish_acked(self, packet_id: int, qos: int, fut) -> None:
        """Batch future resolved: emit the deferred PUBACK/PUBREC."""
        if fut.cancelled():
            return
        exc = fut.exception()
        if exc is not None:
            # routing failed: never ack a publish we did not route (the
            # client's retransmit gives it another chance).  For QoS 2
            # the packet id must leave awaiting_rel, or the dedup guard
            # would PUBREC the retransmit without ever routing it.
            if qos == 2 and self.session is not None:
                self.session.awaiting_rel.pop(packet_id, None)
            self.broker.metrics.inc("messages.publish.error")
            if self.state == CONNECTED:
                self._disconnect_with(0x80)  # unspecified error
            return
        self._send_pub_ack(packet_id, qos, fut.result())

    def _send_pub_ack(self, packet_id: int, qos: int, n: int) -> None:
        m = self.broker.metrics
        rc = (
            RC_NO_MATCHING_SUBSCRIBERS
            if (n == 0 and self.version == C.MQTT_V5)
            else 0
        )
        if qos == 1:
            m.inc("packets.puback.sent")
            self.send_packets([C.Puback(packet_id=packet_id, reason_code=rc)])
        else:
            m.inc("packets.pubrec.sent")
            self.send_packets([C.Pubrec(packet_id=packet_id, reason_code=rc)])

    def _publish_denied(self, pkt: C.Publish) -> None:
        """Unauthorized publish: drop or disconnect per config
        (authorization.deny_action)."""
        if self.broker.access.deny_action == "disconnect":
            self._disconnect_with(RC_NOT_AUTHORIZED)
            return
        if pkt.qos == 1:
            self.send_packets(
                [C.Puback(packet_id=pkt.packet_id,
                          reason_code=RC_NOT_AUTHORIZED)]
            )
        elif pkt.qos == 2:
            self.send_packets(
                [C.Pubrec(packet_id=pkt.packet_id,
                          reason_code=RC_NOT_AUTHORIZED)]
            )

    # ----------------------------------------------------- subscribe

    def _handle_subscribe(self, pkt: C.Subscribe) -> None:
        if (self.broker.access.has_async_authz_hooks
                or self._defer_depth > 0):  # sticky, as in publish
            try:
                asyncio.get_running_loop()
            except RuntimeError:
                pass  # no loop (unit tests): fall through, block
            else:
                self._defer(self._handle_subscribe_async(pkt))
                return
        self._subscribe_body(pkt, None)

    async def _handle_subscribe_async(self, pkt: C.Subscribe) -> None:
        """Precompute the per-filter authz verdicts off-loop, then run
        the synchronous subscribe body with them."""
        verdicts: List[Optional[bool]] = []
        for sub in pkt.subscriptions:
            real = self._sub_authz_topic(sub.topic_filter)
            if real is None:
                verdicts.append(None)  # validation fails in the body
            else:
                verdicts.append(
                    await self.broker.access.authorize_async(
                        self.client, SUBSCRIBE, real
                    )
                )
        if self._closing or self.state != CONNECTED:
            return
        self._subscribe_body(pkt, verdicts)

    def _sub_authz_topic(self, topic_filter: str) -> Optional[str]:
        """The mounted real topic a filter authorizes against (the
        derivation `_do_subscribe` performs before its authorize
        call); None when validation would reject the filter anyway."""
        flt = self.broker.rewrite.rewrite_sub(topic_filter)
        try:
            T.validate_filter(flt)
        except ValueError:
            return None
        if flt.startswith("$exclusive/"):
            flt = flt[len("$exclusive/"):]
            if not flt:
                return None
        shared = T.parse_share(flt)
        real = shared.topic if shared else flt
        return self._mount(real)

    def _subscribe_body(
        self, pkt: C.Subscribe, verdicts: Optional[List[Optional[bool]]]
    ) -> None:
        m = self.broker.metrics
        m.inc("packets.subscribe.received")
        mqtt = self.broker.config.mqtt
        subid = pkt.properties.get("subscription_identifier")
        if isinstance(subid, list):
            subid = subid[0] if subid else None
        rcs: List[int] = []
        retained_jobs: List[Tuple[Message, SubOpts]] = []
        for i, sub in enumerate(pkt.subscriptions):
            authz = verdicts[i] if verdicts is not None else None
            rc = self._do_subscribe(sub, subid, mqtt, retained_jobs,
                                    authz=authz)
            rcs.append(rc)
        if self.version != C.MQTT_V5:
            rcs = [rc if rc <= 2 else 0x80 for rc in rcs]
        m.inc("packets.suback.sent")
        self.send_packets([C.Suback(packet_id=pkt.packet_id, reason_codes=rcs)])
        if retained_jobs:
            self.send_packets(self.session.deliver(retained_jobs))

    def _do_subscribe(
        self,
        sub: C.Subscription,
        subid: Optional[int],
        mqtt,
        retained_jobs: List[Tuple[Message, SubOpts]],
        authz: Optional[bool] = None,
    ) -> int:
        flt = self.broker.rewrite.rewrite_sub(sub.topic_filter)
        try:
            T.validate_filter(flt)
        except ValueError:
            self.broker.metrics.inc("packets.subscribe.error")
            return RC_TOPIC_FILTER_INVALID
        exclusive = flt.startswith("$exclusive/")
        if exclusive:
            if not mqtt.exclusive_subscription:
                return RC_TOPIC_FILTER_INVALID
            flt = flt[len("$exclusive/"):]
            if not flt:
                return RC_TOPIC_FILTER_INVALID
            # the lock is acquired LAST, after every validation/authz
            # gate below — an error return must not leave a stale hold
        shared = T.parse_share(flt)
        if shared is not None and not mqtt.shared_subscription:
            return RC_SHARED_SUB_UNSUPPORTED
        real = shared.topic if shared else flt
        if T.is_wildcard(real) and not mqtt.wildcard_subscription:
            return RC_WILDCARD_SUB_UNSUPPORTED
        if T.levels(real) > mqtt.max_topic_levels:
            return RC_TOPIC_FILTER_INVALID
        full = self._mount(flt) if shared is None else flt
        self.broker.metrics.inc("client.authorize")
        allowed = (
            authz
            if authz is not None  # verdict precomputed off-loop
            else self.broker.access.authorize(
                self.client, SUBSCRIBE, self._mount(real)
            )
        )
        if not allowed:
            self.broker.metrics.inc("authorization.deny")
            self.broker.metrics.inc("packets.subscribe.auth_error")
            return RC_NOT_AUTHORIZED
        self.broker.metrics.inc("authorization.allow")

        granted = min(sub.qos, mqtt.max_qos_allowed)
        opts = SubOpts(
            qos=granted,
            no_local=sub.no_local,
            retain_as_published=sub.retain_as_published,
            retain_handling=sub.retain_handling,
            subid=subid,
        )
        if shared is not None and sub.no_local:
            return RC_PROTOCOL_ERROR  # [MQTT-3.8.3-4]
        hooked = self.broker.hooks.run_fold(
            "client.subscribe", (self.client, flt), opts
        )
        if hooked is None:
            return RC_NOT_AUTHORIZED
        opts = hooked
        if exclusive and not self.broker.exclusive.acquire(
            self.client.clientid, flt
        ):
            return 0x97  # quota exceeded: already held (reference rc)
        is_new = self.session.subscribe(full, opts)
        retained = self.broker.subscribe(
            self.client.clientid, full, opts, is_new_sub=is_new,
            defer_ok=True,  # this path DELIVERS the returned list
        )
        for rmsg in retained:
            # retained replay keeps the retain bit set [MQTT-3.3.1-8]
            ropts = SubOpts(
                qos=opts.qos,
                retain_as_published=True,
                subid=opts.subid,
            )
            retained_jobs.append((rmsg, ropts))
        return granted

    def _handle_unsubscribe(self, pkt: C.Unsubscribe) -> None:
        m = self.broker.metrics
        m.inc("packets.unsubscribe.received")
        rcs: List[int] = []
        for flt in pkt.topic_filters:
            flt = self.broker.rewrite.rewrite_sub(flt)
            if flt.startswith("$exclusive/"):
                flt = flt[len("$exclusive/"):]
                self.broker.exclusive.release(self.client.clientid, flt)
            full = self._mount(flt) if not T.parse_share(flt) else flt
            self.broker.hooks.run("client.unsubscribe", self.client, flt)
            had = self.session.unsubscribe(full) is not None
            if had:
                self.broker.unsubscribe(self.client.clientid, full)
            rcs.append(RC_NORMAL if had else RC_NO_SUBSCRIPTION_EXISTED)
        m.inc("packets.unsuback.sent")
        self.send_packets(
            [C.Unsuback(packet_id=pkt.packet_id, reason_codes=rcs)]
        )

    # ---------------------------------------------------- disconnect

    def _handle_disconnect(self, pkt: C.Disconnect) -> None:
        m = self.broker.metrics
        m.inc("packets.disconnect.received")
        if pkt.reason_code == RC_NORMAL:
            self.will_msg = None  # [MQTT-3.14.4-3]
        if self.version == C.MQTT_V5:
            expiry = pkt.properties.get("session_expiry_interval")
            if expiry is not None and self.session is not None:
                if self.session.expiry_interval == 0 and expiry > 0:
                    self._disconnect_with(RC_PROTOCOL_ERROR)
                    return
                self.session.expiry_interval = float(expiry)  # type: ignore[arg-type]
        self._shutdown("normal")

    def _disconnect_with(self, rc: int) -> None:
        if self.version == C.MQTT_V5 and self.state == CONNECTED:
            self.broker.metrics.inc("packets.disconnect.sent")
            self._send([C.Disconnect(reason_code=rc)])
        self._shutdown(f"rc_{rc:#04x}")

    # ------------------------------------------------------- timers

    def keepalive_expired(self, now: Optional[float] = None) -> bool:
        if self.keepalive <= 0 or self.state != CONNECTED:
            return False
        now = now if now is not None else time.time()
        mult = self.broker.config.mqtt.keepalive_multiplier
        return now - self.last_rx > self.keepalive * mult

    def retry_deliveries(self) -> None:
        if self.session is not None and self.state == CONNECTED:
            self.send_packets(self.session.retry())
            self.session.expire_awaiting_rel()
            wm = self.broker.config.mqtt.outbound_high_watermark
            if self.session.out_parked and (
                not wm or self.out_buffered() < wm
            ):
                # outbound-watermark backlog: the subscriber's buffer
                # recovered but it may owe NO ack that would trigger
                # the ack-driven dequeue — flush the parked queue (in
                # order) from the timer; `_dequeue` clears the flag
                # once the queue empties
                self.send_packets(self.session._dequeue())

    # ----------------------------------------------------- teardown

    def connection_lost(self, reason: str = "closed") -> None:
        """Socket gone (either direction).  Publishes the will, updates
        the CM, drops router state for non-persistent sessions."""
        if self.state == DISCONNECTED and self.session is None:
            return
        self.state = DISCONNECTED
        if self._pending_connect is not None:
            self._pending_connect.cancel()
            self._pending_connect = None
        m = self.broker.metrics
        if self.client is not None:
            m.inc("client.disconnected")
            if self.broker.flapping.on_disconnect(self.client.clientid):
                m.inc("client.flapping_banned")
                self.broker.alarms.activate(
                    f"flapping/{self.client.clientid}",
                    message="client banned for flapping",
                    ttl=self.broker.flapping.ban_time,
                )
            self.broker.hooks.run(
                "client.disconnected", self.client, reason
            )
        if self.will_msg is not None:
            will, self.will_msg = self.will_msg, None
            delay = float(will.properties.pop("will_delay_interval", 0) or 0)
            expiry = self.session.expiry_interval if self.session else 0.0
            if delay > 0 and expiry > 0:
                # fire at min(delay, session expiry) unless the client
                # reconnects first ([MQTT-3.1.2-8], [MQTT-3.1.3.2.2])
                self.broker.schedule_will(
                    self.client.clientid, will, min(delay, expiry)
                )
            else:
                self.broker.publish(will)
        if self.session is not None and self.client is not None:
            self.broker.cm.disconnect(self.client.clientid, self)
            self.broker.channel_disconnected(self.client.clientid)
            if self.session.expiry_interval <= 0:
                self.broker.session_terminated(
                    self.client.clientid, self.session
                )
                self.broker.hooks.run(
                    "session.terminated", self.client.clientid, reason
                )
            self.session = None
