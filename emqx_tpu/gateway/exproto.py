"""exproto gateway: protocol logic lives in an external gRPC service.

The `emqx_gateway_exproto` role (/root/reference/apps/emqx_gateway_exproto/
src/emqx_exproto_channel.erl event flow, priv/protos/exproto.proto
contract): we accept raw TCP connections, forward socket events to the
user's ``ConnectionUnaryHandler`` service (OnSocketCreated /
OnReceivedBytes / OnSocketClosed / OnTimerTimeout / OnReceivedMessages),
and serve ``ConnectionAdapter`` so that service can drive each
connection: send bytes, authenticate a clientid, subscribe/publish on
the broker core, start the keepalive timer, close the socket.

gRPC plumbing mirrors the exhook server: the committed message classes
generated from proto/exproto.proto (README, "Running", has the
command) + hand-wired generic method handlers (no grpc_tools codegen in
this environment); handler->broker calls marshal onto the asyncio loop
with ``call_soon_threadsafe``, and gateway->handler calls use
future-based stubs so the event loop never blocks on the handler
service."""

from __future__ import annotations

import asyncio
import itertools
import logging
import threading
import time
from concurrent import futures
from typing import Dict, List, Optional, Tuple

from ..access import PUBLISH as ACT_PUBLISH
from ..access import SUBSCRIBE as ACT_SUBSCRIBE
from ..access import ClientInfo
from ..codec import mqtt as C
from ..message import Message
from ..broker.session import SubOpts
from . import Gateway, GatewayChannel, GatewayFrame
from . import exproto_pb2 as pb

log = logging.getLogger("emqx_tpu.gateway.exproto")

ADAPTER_SERVICE = "emqx.exproto.v1.ConnectionAdapter"
HANDLER_SERVICE = "emqx.exproto.v1.ConnectionUnaryHandler"

SUCCESS = 0
UNKNOWN = 1
CONN_PROCESS_NOT_ALIVE = 2
REQUIRED_PARAMS_MISSED = 3
PERMISSION_DENY = 5


class _RawFrame(GatewayFrame):
    """Passthrough: the external handler owns all framing."""

    def parse(self, state, data: bytes):
        return [data], state

    def serialize(self, frame) -> bytes:
        return frame


class ExprotoChannel(GatewayChannel):
    """One raw TCP connection, driven by the external handler."""

    def __init__(self, gateway, write, close, peer) -> None:
        super().__init__(gateway, write, close, peer)
        self.conn_id = f"{gateway.node}:{next(gateway._conn_seq)}"
        self.client: Optional[ClientInfo] = None
        self.keepalive_s = 0.0
        self.last_rx = time.monotonic()
        self._keepalive_task: Optional[asyncio.Task] = None
        # per-connection handler-call chain: socket events must reach
        # the handler service in order (created -> bytes... -> closed),
        # and independent gRPC futures into its thread pool would race
        self._call_queue: List[Tuple[str, object]] = []
        self._call_inflight = False
        gateway.conns[self.conn_id] = self
        host, _, port = peer.rpartition(":")
        self.call_handler("OnSocketCreated", pb.SocketCreatedRequest(
            conn=self.conn_id,
            conninfo=pb.ConnInfo(
                socktype=pb.TCP,
                peername=pb.Address(
                    host=host,
                    # peer may be "?" when the socket reset before the
                    # peername could be read
                    port=int(port) if port.isdigit() else 0,
                ),
                sockname=pb.Address(host=gateway.bind, port=gateway.port),
            ),
        ))

    def call_handler(self, method: str, request) -> None:
        """Queue a handler call; at most one in flight per connection,
        issued in arrival order (all entry points run on the loop)."""
        self._call_queue.append((method, request))
        if not self._call_inflight:
            self._pump_calls()

    def _pump_calls(self) -> None:
        if not self._call_queue:
            self._call_inflight = False
            return
        self._call_inflight = True
        method, request = self._call_queue.pop(0)
        loop = self.gateway._loop

        def done(_f):
            if loop is not None and not loop.is_closed():
                loop.call_soon_threadsafe(self._pump_calls)

        self.gateway.call_handler(method, request, on_done=done)

    def handle_frame(self, frame: bytes) -> None:
        self.last_rx = time.monotonic()
        self.call_handler(
            "OnReceivedBytes",
            pb.ReceivedBytesRequest(conn=self.conn_id, bytes=frame),
        )

    def deliver(self, packets) -> None:
        # iterative settle: each puback can dequeue ANOTHER packet from
        # the session's backlog (recursing here would stack one frame
        # per queued message)
        pending = list(packets)
        while pending:
            batch, pending = pending, []
            msgs = [
                pb.Message(
                    node=self.gateway.node,
                    id=pkt.packet_id and str(pkt.packet_id) or "",
                    qos=pkt.qos,
                    topic=pkt.topic,
                    payload=bytes(pkt.payload),
                    timestamp=int(time.time() * 1000),
                )
                for pkt in batch
                if pkt.type == C.PUBLISH
            ]
            if not msgs:
                return
            self.call_handler(
                "OnReceivedMessages",
                pb.ReceivedMessagesRequest(conn=self.conn_id, messages=msgs),
            )
            # the handler owns its wire framing; broker-side QoS1
            # deliveries settle on handoff (the reference treats the
            # handler service as the terminal hop the same way)
            if self.session is not None:
                for pkt in batch:
                    if pkt.type == C.PUBLISH and pkt.packet_id:
                        _ok, follow = self.session.puback(pkt.packet_id)
                        if follow:
                            pending.extend(follow)

    def connection_lost(self, reason: str) -> None:
        if self._keepalive_task is not None:
            self._keepalive_task.cancel()
            self._keepalive_task = None
        self.gateway.conns.pop(self.conn_id, None)
        self.call_handler(
            "OnSocketClosed",
            pb.SocketClosedRequest(conn=self.conn_id, reason=reason),
        )
        super().connection_lost(reason)

    # ------------------------------------------------- adapter actions
    # (invoked on the event loop via the AdapterServer's marshalling)

    def adapter_authenticate(self, ci: "pb.ClientInfo",
                             password: str) -> Tuple[int, str]:
        clientid = ci.clientid
        if not clientid:
            return REQUIRED_PARAMS_MISSED, "clientid required"
        client = ClientInfo(
            clientid=clientid,
            username=ci.username or None,
            password=password.encode() or None,
            peerhost=self.peer,
            mountpoint=ci.mountpoint or None,
        )
        if self.broker.banned.is_banned(
            clientid=clientid, username=client.username,
            peerhost=self.peer.rsplit(":", 1)[0],
        ):
            return PERMISSION_DENY, "banned"
        ok, client = self.broker.access.authenticate(client)
        if not ok:
            return PERMISSION_DENY, "authentication failed"
        client.password = None
        self.client = client
        self.open_session(clientid, clean_start=True)
        return SUCCESS, ""

    def adapter_subscribe(self, topic: str, qos: int) -> Tuple[int, str]:
        if self.session is None:
            return CONN_PROCESS_NOT_ALIVE, "not authenticated"
        if not self.broker.access.authorize(
            self.client, ACT_SUBSCRIBE, topic
        ):
            return PERMISSION_DENY, "subscribe not authorized"
        opts = SubOpts(qos=min(max(qos, 0), 2))
        is_new = self.session.subscribe(topic, opts)
        self.broker.subscribe(self.clientid, topic, opts, is_new_sub=is_new)
        return SUCCESS, ""

    def adapter_unsubscribe(self, topic: str) -> Tuple[int, str]:
        if self.session is None:
            return CONN_PROCESS_NOT_ALIVE, "not authenticated"
        self.session.unsubscribe(topic)
        self.broker.unsubscribe(self.clientid, topic)
        return SUCCESS, ""

    def adapter_publish(self, topic: str, qos: int,
                        payload: bytes) -> Tuple[int, str]:
        if self.session is None:
            return CONN_PROCESS_NOT_ALIVE, "not authenticated"
        if not self.broker.access.authorize(self.client, ACT_PUBLISH, topic):
            return PERMISSION_DENY, "publish not authorized"
        self.broker_publish(Message(
            topic=topic, payload=payload, qos=min(max(qos, 0), 2),
            from_client=self.clientid,
            from_username=self.client.username if self.client else None,
        ))
        return SUCCESS, ""

    def adapter_start_timer(self, interval_s: int) -> Tuple[int, str]:
        self.keepalive_s = float(interval_s)
        if self._keepalive_task is not None:
            self._keepalive_task.cancel()
        if interval_s > 0:
            self._keepalive_task = asyncio.get_running_loop().create_task(
                self._keepalive_watch()
            )
        return SUCCESS, ""

    async def _keepalive_watch(self) -> None:
        while True:
            await asyncio.sleep(self.keepalive_s / 2)
            if time.monotonic() - self.last_rx > self.keepalive_s * 1.5:
                self.call_handler(
                    "OnTimerTimeout",
                    pb.TimerTimeoutRequest(conn=self.conn_id,
                                           type=pb.KEEPALIVE),
                )
                self.close("keepalive_timeout")
                return


class ExprotoGateway(Gateway):
    """TCP side + both gRPC halves of the exproto contract."""

    name = "exproto"
    frame_class = _RawFrame
    channel_class = ExprotoChannel

    def __init__(
        self,
        broker,
        bind: str = "0.0.0.0",
        port: int = 0,
        handler_address: str = "127.0.0.1:9100",
        adapter_bind: str = "127.0.0.1:0",
    ) -> None:
        super().__init__(broker, bind, port)
        import grpc

        self.node = broker.config.node_name
        self.conns: Dict[str, ExprotoChannel] = {}
        self._conn_seq = itertools.count(1)
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        # future-based stubs to the user's handler service
        self._grpc_channel = grpc.insecure_channel(handler_address)
        self._stubs = {
            name: self._grpc_channel.unary_unary(
                f"/{HANDLER_SERVICE}/{name}",
                request_serializer=req.SerializeToString,
                response_deserializer=pb.EmptySuccess.FromString,
            )
            for name, req in (
                ("OnSocketCreated", pb.SocketCreatedRequest),
                ("OnSocketClosed", pb.SocketClosedRequest),
                ("OnReceivedBytes", pb.ReceivedBytesRequest),
                ("OnTimerTimeout", pb.TimerTimeoutRequest),
                ("OnReceivedMessages", pb.ReceivedMessagesRequest),
            )
        }
        self._adapter = _AdapterServer(self, adapter_bind)

    @property
    def adapter_port(self) -> int:
        return self._adapter.port

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._adapter.start()
        await super().start()

    async def stop(self) -> None:
        await super().stop()
        self._adapter.stop()
        self._grpc_channel.close()

    def call_handler(self, method: str, request, on_done=None) -> None:
        """Unary call to the handler service (the future keeps the loop
        unblocked; failures are logged — the reference's handler pool
        behaves the same on a dead service).  ``on_done`` always fires
        (channels chain their per-connection call order on it)."""
        try:
            fut = self._stubs[method].future(request, timeout=10.0)
        except Exception:
            log.exception("exproto handler call %s failed to start", method)
            if on_done is not None:
                on_done(None)
            return

        def done(f):
            exc = f.exception()
            if exc is not None:
                log.warning("exproto handler %s failed: %s", method, exc)
                self.broker.metrics.inc("gateway.exproto.handler_error")
            if on_done is not None:
                on_done(f)

        fut.add_done_callback(done)


class _AdapterServer:
    """Serves ConnectionAdapter for the external handler service."""

    def __init__(self, gateway: ExprotoGateway, bind: str) -> None:
        import grpc

        self.gateway = gateway
        self._grpc = grpc.server(futures.ThreadPoolExecutor(max_workers=8))
        self._grpc.add_generic_rpc_handlers((
            grpc.method_handlers_generic_handler(
                ADAPTER_SERVICE, self._handlers()
            ),
        ))
        self.port = self._grpc.add_insecure_port(bind)

    def start(self) -> None:
        self._grpc.start()
        log.info("exproto ConnectionAdapter serving on port %d", self.port)

    def stop(self, grace: float = 0.5) -> None:
        self._grpc.stop(grace).wait()

    # ------------------------------------------------------- plumbing

    def _on_loop(self, fn) -> Tuple[int, str]:
        """Run ``fn`` on the gateway's event loop and wait for its
        (code, message) result — adapter RPCs arrive on gRPC worker
        threads, but all broker/channel state lives on the loop."""
        loop = self.gateway._loop
        if loop is None or loop.is_closed():
            return CONN_PROCESS_NOT_ALIVE, "gateway not running"
        done = threading.Event()
        box: List = [UNKNOWN, "internal"]

        def run():
            try:
                box[0], box[1] = fn()
            except Exception as exc:  # pragma: no cover - defensive
                log.exception("exproto adapter action failed")
                box[0], box[1] = UNKNOWN, str(exc)
            finally:
                done.set()

        loop.call_soon_threadsafe(run)
        if not done.wait(10.0):
            return UNKNOWN, "loop timeout"
        return box[0], box[1]

    def _conn(self, conn_id: str) -> Optional[ExprotoChannel]:
        return self.gateway.conns.get(conn_id)

    def _handlers(self):
        import grpc

        def unary(fn, req_cls):
            def call(request, context):
                try:
                    code, msg = fn(request)
                except Exception:
                    log.exception("exproto adapter %s failed", fn.__name__)
                    code, msg = UNKNOWN, "internal error"
                return pb.CodeResponse(code=code, message=msg)

            return grpc.unary_unary_rpc_method_handler(
                call,
                request_deserializer=req_cls.FromString,
                response_serializer=pb.CodeResponse.SerializeToString,
            )

        def with_conn(action):
            def fn(request):
                def on_loop():
                    chan = self._conn(request.conn)
                    if chan is None:
                        return CONN_PROCESS_NOT_ALIVE, "no such connection"
                    return action(chan, request)

                return self._on_loop(on_loop)

            return fn

        return {
            "Send": unary(
                with_conn(lambda ch, r: (ch.write(bytes(r.bytes)),
                                         (SUCCESS, ""))[1]),
                pb.SendBytesRequest,
            ),
            "Close": unary(
                with_conn(lambda ch, r: (ch.close("adapter_close"),
                                         (SUCCESS, ""))[1]),
                pb.CloseSocketRequest,
            ),
            "Authenticate": unary(
                with_conn(lambda ch, r: ch.adapter_authenticate(
                    r.clientinfo, r.password)),
                pb.AuthenticateRequest,
            ),
            "StartTimer": unary(
                with_conn(lambda ch, r: ch.adapter_start_timer(r.interval)),
                pb.TimerRequest,
            ),
            "Publish": unary(
                with_conn(lambda ch, r: ch.adapter_publish(
                    r.topic, r.qos, bytes(r.payload))),
                pb.PublishRequest,
            ),
            "Subscribe": unary(
                with_conn(lambda ch, r: ch.adapter_subscribe(
                    r.topic, r.qos)),
                pb.SubscribeRequest,
            ),
            "Unsubscribe": unary(
                with_conn(lambda ch, r: ch.adapter_unsubscribe(r.topic)),
                pb.UnsubscribeRequest,
            ),
            "RawPublish": unary(self._raw_publish, pb.RawPublishRequest),
        }

    def _raw_publish(self, request) -> Tuple[int, str]:
        def on_loop():
            self.gateway.broker.publish(Message(
                topic=request.topic,
                payload=bytes(request.payload),
                qos=min(max(request.qos, 0), 2),
                from_client="exproto",
            ))
            return SUCCESS, ""

        return self._on_loop(on_loop)
