"""Listener lifecycle + broker server entry point.

Re-creates `emqx_listeners` (/root/reference/apps/emqx/src/
emqx_listeners.erl:242,430-448): bind/unbind TCP listeners, cap
concurrent connections, hand accepted sockets to `Connection` loops.
``python -m emqx_tpu.broker`` boots a broker the way `bin/emqx
foreground` does.
"""

from __future__ import annotations

import asyncio
import gc
import logging
import os
import sys
from typing import Dict, List, Optional

from ..aio import cancel_and_wait
from ..config import BrokerConfig, ListenerConfig
from .broker import Broker
from .connection import Connection, ReadTurn

log = logging.getLogger("emqx_tpu.listener")


class Listener:
    """One bound socket accepting MQTT clients over tcp/ssl/ws/wss
    (the four transports emqx_listeners starts via esockd/cowboy,
    emqx_listeners.erl:430-447)."""

    # the listener types whose `Connection` is its transport's protocol
    DIRECT = ("tcp", "ssl")

    def __init__(self, broker: Broker, cfg: ListenerConfig) -> None:
        self.broker = broker
        self.cfg = cfg
        self._server: Optional[asyncio.AbstractServer] = None
        # what counts against `max_connections`: `_on_client` tasks,
        # or (a direct listener) the connections themselves
        self._conns: set = set()
        self._direct = False
        # a direct listener's reads, a turn
        self._turn = ReadTurn(broker.profiler.loop)
        # listener-aggregate buckets shared by ALL this listener's
        # connections (the hierarchical limiter's middle level)
        self._shared_limiter = None
        self._ssl_ctx = None
        self._crl_mtime = 0.0
        self._crl_next_update = None
        if cfg.max_messages_rate > 0 or cfg.max_bytes_rate > 0:
            from ..limiter import ConnectionLimiter

            self._shared_limiter = ConnectionLimiter(
                messages_rate=cfg.max_messages_rate,
                bytes_rate=cfg.max_bytes_rate,
                shared=True,
            )

    @property
    def port(self) -> int:
        """Actual bound port (useful when cfg.port == 0)."""
        if self._server is None or not self._server.sockets:
            return self.cfg.port
        return self._server.sockets[0].getsockname()[1]

    def _make_limiter(self):
        from ..limiter import ConnectionLimiter, HierarchicalLimiter

        conn = None
        if self.cfg.messages_rate > 0 or self.cfg.bytes_rate > 0:
            conn = ConnectionLimiter(
                messages_rate=self.cfg.messages_rate,
                bytes_rate=self.cfg.bytes_rate,
            )
        zone = getattr(self.broker, "zone_limiter", None)
        if self._shared_limiter is None and zone is None:
            return conn  # single level: no wrapper indirection
        return HierarchicalLimiter(conn, self._shared_limiter, zone)

    def _ssl_context(self):
        import ssl as ssl_mod

        ctx = ssl_mod.SSLContext(ssl_mod.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(self.cfg.certfile, self.cfg.keyfile)
        if self.cfg.cacertfile:
            ctx.load_verify_locations(self.cfg.cacertfile)
        if self.cfg.verify:
            ctx.verify_mode = ssl_mod.CERT_REQUIRED
        if self.cfg.crlfile:
            # revocation checking (the emqx_crl_cache role,
            # /root/reference/apps/emqx/src/emqx_crl_cache.erl): leaf
            # certs are checked against the CRL; the housekeeper
            # re-loads the file when it changes, so revocations take
            # effect on new handshakes without a listener restart
            if not self.cfg.verify:
                raise ValueError(
                    f"listener {self.cfg.name}: crlfile requires "
                    "verify=true (without a requested client cert "
                    "there is nothing to check revocation against)"
                )
            ctx.verify_flags |= ssl_mod.VERIFY_CRL_CHECK_LEAF
            ctx.load_verify_locations(self.cfg.crlfile)
            self._crl_mtime = os.stat(self.cfg.crlfile).st_mtime
            self._note_crl_expiry()
        self._ssl_ctx = ctx
        return ctx

    def _note_crl_expiry(self) -> None:
        """Track the CRL's nextUpdate: once it passes, OpenSSL fails
        EVERY handshake with CRL_HAS_EXPIRED — the operator needs a
        warning before that, since an untouched file never triggers
        the mtime-based reload."""
        self._crl_next_update = None
        try:
            from cryptography import x509

            with open(self.cfg.crlfile, "rb") as f:
                crl = x509.load_pem_x509_crl(f.read())
            self._crl_next_update = crl.next_update_utc
        except Exception:
            log.debug("CRL nextUpdate unreadable", exc_info=True)

    def maybe_reload_crl(self) -> bool:
        """Re-load the CRL file into the LIVE ssl context when its
        mtime changes (OpenSSL picks the freshest CRL per issuer, so
        additive loading rolls the list forward).  Returns True when a
        reload happened."""
        if self._ssl_ctx is None or not self.cfg.crlfile:
            return False
        if self._crl_next_update is not None:
            import datetime

            now = datetime.datetime.now(datetime.timezone.utc)
            if now > self._crl_next_update:
                log.warning(
                    "listener %s: CRL is past nextUpdate (%s) — "
                    "OpenSSL now rejects ALL client certs on this "
                    "listener until a fresh CRL is written",
                    self.cfg.name, self._crl_next_update,
                )
                self._crl_next_update = None  # warn once per expiry
        try:
            mtime = os.stat(self.cfg.crlfile).st_mtime
        except OSError:
            return False
        if mtime == self._crl_mtime:
            return False
        try:
            self._ssl_ctx.load_verify_locations(self.cfg.crlfile)
        except Exception:
            # mtime NOT advanced: the load retries every tick until
            # the operator writes a CRL OpenSSL accepts
            log.warning("listener %s: CRL reload failed",
                        self.cfg.name, exc_info=True)
            return False
        self._crl_mtime = mtime
        self._note_crl_expiry()
        log.info("listener %s: CRL reloaded", self.cfg.name)
        return True

    async def start(self) -> None:
        ssl_ctx = (
            self._ssl_context() if self.cfg.type in ("ssl", "wss") else None
        )
        # who reads a socket's bytes, chosen from what the listener
        # is: plain TCP and TLS -> the connection is the transport's
        # protocol and its reads are handled a loop turn; a WebSocket
        # (a stream over the reader, no transport) -> a reader, a
        # writer and the `Connection.run` coroutine
        self._direct = self.cfg.type in self.DIRECT
        if self._direct:
            self._server = await asyncio.get_running_loop().create_server(
                self._protocol, self.cfg.bind, self.cfg.port, ssl=ssl_ctx,
                reuse_port=self.cfg.reuse_port or None,
            )
        else:
            self._server = await asyncio.start_server(
                self._on_client, self.cfg.bind, self.cfg.port, ssl=ssl_ctx,
                reuse_port=self.cfg.reuse_port or None,
            )
        log.info(
            "listener %s (%s) started on %s:%d",
            self.cfg.name,
            self.cfg.type,
            self.cfg.bind,
            self.port,
        )

    async def stop(self) -> None:
        # cancel connection handlers BEFORE wait_closed: Python 3.12's
        # Server.wait_closed also waits for live handlers, so the old
        # order deadlocks while any client is still connected
        if self._server is not None:
            self._server.close()
        if self._direct:
            tasks = [
                conn.stop("server_stopped") for conn in list(self._conns)
            ]
        else:
            tasks = list(self._conns)
            for task in tasks:
                task.cancel()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)
        if self._server is not None:
            await self._server.wait_closed()
            self._server = None

    def _protocol(self) -> Connection:
        """A direct listener's protocol factory: asked an accepted
        socket, before its transport (and a TLS handshake) is made."""
        return Connection(
            self.broker,
            mountpoint=self.cfg.mountpoint,
            limiter=self._make_limiter(),
            admit=self._admit,
            on_lost=self._conns.discard,
            turn=self._turn,
        )

    def _admit(self, conn: Connection) -> bool:
        if len(self._conns) >= self.cfg.max_connections:
            return False
        self._conns.add(conn)
        return True

    async def _on_client(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        if len(self._conns) >= self.cfg.max_connections:
            writer.close()
            return
        # count the connection against the cap from accept time — a
        # slow (up to 10 s) WS handshake must not be a free pass
        task = asyncio.current_task()
        self._conns.add(task)
        try:
            if self.cfg.type in ("ws", "wss"):
                from .ws import WsError, WsServerStream, server_handshake

                try:
                    await asyncio.wait_for(
                        server_handshake(reader, writer), 10.0
                    )
                except (
                    WsError,
                    asyncio.TimeoutError,
                    asyncio.IncompleteReadError,
                    asyncio.LimitOverrunError,
                    ConnectionError,
                    ValueError,
                ):
                    writer.close()
                    return
                reader = writer = WsServerStream(
                    reader,
                    writer,
                    max_size=self.broker.config.mqtt.max_packet_size * 2,
                )
            conn = Connection(
                self.broker,
                reader,
                writer,
                mountpoint=self.cfg.mountpoint,
                limiter=self._make_limiter(),
            )
            await conn.run()
        finally:
            self._conns.discard(task)


class BrokerServer:
    """A broker plus its listeners — the unit `emqx_machine` boots."""

    # servers of this process between start() and stop(): the boot
    # heap stays frozen while there is one (`_freeze_boot_heap`)
    _serving = 0

    def __init__(self, config: Optional[BrokerConfig] = None) -> None:
        self.broker = Broker(config=config)
        self.listeners: List[Listener] = [
            Listener(self.broker, lc)
            for lc in self.broker.config.listeners
            if lc.enable and lc.type in ("tcp", "ssl", "ws", "wss")
        ]
        # QUIC listeners (UDP; the reference's MsQuic slot) start/stop
        # alongside but are not stream-socket Listeners
        self.quic_listeners: list = []
        for lc in self.broker.config.listeners:
            if lc.enable and lc.type == "quic":
                from .quic_listener import QuicListener

                self.quic_listeners.append(QuicListener(
                    self.broker,
                    bind=lc.bind,
                    port=lc.port,
                    certfile=lc.certfile,
                    keyfile=lc.keyfile,
                    mountpoint=lc.mountpoint,
                ))
        self._housekeeper: Optional[asyncio.Task] = None
        self.telemetry = None
        from ..sys_topics import SysTopics
        from ..sysmon import SysMonitor

        self.sys = SysTopics(self.broker)
        self.sysmon = SysMonitor(self.broker)
        self.api = None  # MgmtApi when config.api.enable
        self.cluster_links = None  # ClusterLinks when config.cluster_links
        self.otel = None  # OtelExporter when config.otel.enable
        self.exhook_clients: list = []  # ExhookClient per config.exhooks
        self.cluster_node = None  # ClusterNode when config.cluster
        self._froze = False  # this server is counted in `_serving`

    async def start(self) -> None:
        from .. import failpoints

        # arm any EMQX_FAILPOINTS chaos spec before traffic flows (a
        # no-op when the env var is unset — the production default)
        failpoints.load_env()
        self.broker._loop = asyncio.get_running_loop()
        # the turn clock, from where the served path starts: the loop
        # is whoever's (`asyncio.run`'s), so its selector is hooked,
        # not its class chosen; off again in `stop`
        turn_clock = self.broker.profiler.loop
        if turn_clock is not None:
            turn_clock.install(self.broker._loop)
        eng_cfg = self.broker.config.engine
        engine = self.broker.router.engine
        if engine.use_device is not False:
            # persistent XLA cache: automaton capacity-class compiles
            # happen once EVER, not once per process — a first-use
            # compile stalls concurrent matches for seconds
            from ..engine import enable_compile_cache

            enable_compile_cache()
            # compile BEFORE a listener accepts: the match kernels for
            # whatever table the boot restored and the rules kernel
            # for the loaded rules, at every window bucket up to
            # batch_max — paid inside the first dispatch windows these
            # compiles hold ordered dispatch for tens of seconds and
            # run queued windows past the breaker deadline.  Folds and
            # rebuilds after this warm the same buckets themselves.
            await self.broker._loop.run_in_executor(
                None, engine.warmup, eng_cfg.batch_max
            )
        # the native sender and reader threads, before a listener
        # accepts: every plain-TCP connection takes its slots as it is
        # made
        from ..ops import sockreader, sockwriter

        self.broker.sender = sockwriter.start(
            self.broker._loop, self.broker.profiler.loop
        )
        self.broker.reader = sockreader.start(
            self.broker._loop, self.broker.profiler.loop
        )
        if eng_cfg.batch_publish:
            from .broker import PublishBatcher

            self.broker.batcher = PublishBatcher(
                self.broker,
                window=eng_cfg.batch_window_ms / 1000.0,
                batch_max=eng_cfg.batch_max,
                pipeline_windows=eng_cfg.pipeline_windows,
            )
            await self.broker.batcher.start()
        if self.broker.resume is not None:
            # resume scheduler BEFORE listeners accept: the first
            # reconnect of a mass-reconnect storm must already route
            # through admission control, not the synchronous fallback
            await self.broker.resume.start()
        # the olp ladder's L2 clamp scales the SHARED (aggregate)
        # buckets — listener level + node/zone level; per-connection
        # private buckets stay untouched (a clamped aggregate already
        # throttles everyone proportionally)
        for lst in self.listeners:
            if lst._shared_limiter is not None:
                self.broker.olp.clamp_targets.append(
                    lst._shared_limiter
                )
        if self.broker.zone_limiter is not None:
            self.broker.olp.clamp_targets.append(
                self.broker.zone_limiter
            )
        cfg = self.broker.config
        if cfg.cluster_links:
            from ..cluster_link import ClusterLinks

            # install the $LINK guard hooks BEFORE any listener accepts
            # a client: a subscribe slipping in ahead of the guard would
            # siphon forwarded traffic for the session's lifetime
            self.cluster_links = ClusterLinks(
                self.broker, cfg.cluster_name, cfg.cluster_links
            )
            self.cluster_links.install()
        for lst in self.listeners:
            await lst.start()
        for qlst in self.quic_listeners:
            await qlst.start()
        api_cfg = self.broker.config.api
        if api_cfg.enable:
            from ..mgmt import MgmtApi

            self.api = MgmtApi(self, bind=api_cfg.bind, port=api_cfg.port)
            await self.api.start()
        for gw_cfg in self.broker.config.gateways:
            await self._load_gateway(gw_cfg)
        if self.cluster_links is not None:
            await self.cluster_links.start()
        cl = cfg.cluster
        if cl.get("enable"):
            from ..cluster import ClusterNode

            self.cluster_node = ClusterNode(
                cfg.node_name,
                self.broker,
                bind=cl.get("bind", "127.0.0.1"),
                port=int(cl.get("port", 0)),
                # quorum consensus for conf + DS + registry ships ON
                # (VERDICT r4 #8); "lww" remains the opt-out for
                # fire-and-forget deployments
                consensus=cl.get("consensus", "raft"),
                role=cl.get("role", "core"),
                sharded_routes=bool(cl.get("sharded_routes", False)),
                raft_data_dir=cl.get("raft_data_dir"),
                heartbeat_interval=float(
                    cl.get("heartbeat_interval", 0.5)
                ),
                down_after=float(cl.get("down_after", 2.0)),
                # inter-node link layer: tcp (default) | quic | auto
                # (QUIC preferred, graceful TCP degradation per peer)
                transport_mode=cl.get("transport_mode", "tcp"),
                quic_psk=str(cl.get("quic_psk", "")),
                fwd_inflight_max=int(cl.get("fwd_inflight_max", 512)),
                fwd_ack_timeout=float(cl.get("fwd_ack_timeout", 1.0)),
            )
            await self.cluster_node.start(seeds=[
                (s[0], s[1], int(s[2])) for s in cl.get("seeds", ())
            ])
        for ex_cfg in cfg.exhooks:
            from ..exhook.client import ExhookClient

            client = ExhookClient(
                self.broker,
                name=ex_cfg["name"],
                url=ex_cfg["url"],
                timeout=float(ex_cfg.get("timeout", 5.0)),
                failure_action=ex_cfg.get("failure_action", "deny"),
            )
            # dial in an executor: OnProviderLoaded is a blocking
            # round-trip and must not stall listener startup.  start()
            # never raises on an unreachable provider — deny policies
            # fail closed and the housekeeper retries the load
            await asyncio.get_running_loop().run_in_executor(
                None, client.start
            )
            self.exhook_clients.append(client)
        for sink_cfg in cfg.sinks:
            try:
                await self._start_sink(sink_cfg)
            except Exception:
                log.exception("sink %r failed to start",
                              sink_cfg.get("id"))
        if cfg.ft.enable and cfg.ft.s3:
            from ..s3 import S3Client, S3Sink

            s3c = cfg.ft.s3
            self.broker.ft.s3_exporter = await self.broker.resources.create(
                "ft:s3",
                S3Sink(S3Client(
                    s3c["endpoint"],
                    s3c["bucket"],
                    s3c.get("access_key", ""),
                    s3c.get("secret_key", ""),
                    region=s3c.get("region", "us-east-1"),
                )),
                max_buffer=256,
            )
        if cfg.otel.enable:
            from ..otel import OtelExporter

            self.otel = OtelExporter(
                self.broker,
                cfg.otel.endpoint,
                interval=cfg.otel.interval,
                export_logs=cfg.otel.export_logs,
                export_traces=cfg.otel.export_traces,
                trace_sample_ratio=cfg.otel.trace_sample_ratio,
            )
            await self.otel.start()
        if (cfg.log.format != "text" or cfg.log.level != "info"
                or cfg.log.throttle_window_s):
            from ..logger import configure as configure_logging

            configure_logging(
                fmt=cfg.log.format,
                level=cfg.log.level,
                throttle_window_s=cfg.log.throttle_window_s or None,
            )
        if cfg.telemetry_enable and cfg.telemetry_url:
            from ..telemetry import TelemetryReporter

            self.telemetry = TelemetryReporter(
                self.broker,
                cfg.telemetry_url,
                interval=cfg.telemetry_interval,
            )
            await self.telemetry.start()
        # serving process: arm the event-loop-lag watchdog + GC-pause
        # observer (short-lived test brokers never reach here, so they
        # never spawn the thread)
        self.broker.flight.arm_watchdog()
        self._housekeeper = asyncio.get_running_loop().create_task(
            self._housekeeping()
        )
        self._freeze_boot_heap()

    def _freeze_boot_heap(self) -> None:
        """Take what the boot left on the heap out of the collector's
        reach until the last server of the process stops: the traced
        programs behind every compiled kernel, the rules, the modules,
        a restored table.  It lives as long as the server, and a full
        collection walks all of it with the GIL held (about 0.3 s at
        half a million objects, whatever the table's size: the table
        is native), once every few seconds of traffic, because a
        window's messages outlive the young generations and count as
        promoted when they are long freed: no thread of the process
        delivers anything meanwhile, and a publisher reads that stall
        as its p99.  Collected once here and frozen, a full
        collection walks only what came after.  A cycle among the
        frozen objects that dies while a server runs stays until
        `stop()`: the boot heap is what bounds it."""
        gc.collect()
        gc.freeze()
        self._froze = True
        BrokerServer._serving += 1

    def _thaw_boot_heap(self) -> None:
        if not self._froze:
            return
        self._froze = False
        BrokerServer._serving -= 1
        if BrokerServer._serving == 0:
            gc.unfreeze()

    async def _load_gateway(self, gw_cfg: dict) -> None:
        kind = gw_cfg.get("type")
        if kind == "stomp":
            from ..gateway.stomp import StompGateway

            await self.broker.gateways.load(
                StompGateway(
                    self.broker,
                    bind=gw_cfg.get("bind", "0.0.0.0"),
                    port=int(gw_cfg.get("port", 61613)),
                )
            )
        elif kind == "mqttsn":
            from ..gateway.mqttsn import MqttSnGateway

            await self.broker.gateways.load(
                MqttSnGateway(
                    self.broker,
                    bind=gw_cfg.get("bind", "0.0.0.0"),
                    port=int(gw_cfg.get("port", 1884)),
                    predefined={
                        int(k): v
                        for k, v in gw_cfg.get("predefined", {}).items()
                    },
                    advertise_interval=float(
                        gw_cfg.get("advertise_interval", 0.0)
                    ),
                    broadcast_addr=gw_cfg.get(
                        "broadcast_addr", "255.255.255.255"
                    ),
                    advertise_port=(
                        int(gw_cfg["advertise_port"])
                        if "advertise_port" in gw_cfg else None
                    ),
                )
            )
        elif kind == "jt808":
            from ..gateway.jt808 import Jt808Gateway

            await self.broker.gateways.load(
                Jt808Gateway(
                    self.broker,
                    bind=gw_cfg.get("bind", "0.0.0.0"),
                    port=int(gw_cfg.get("port", 6808)),
                    mountpoint=gw_cfg.get("mountpoint", "jt808/"),
                )
            )
        elif kind == "gbt32960":
            from ..gateway.gbt32960 import GbtGateway

            await self.broker.gateways.load(
                GbtGateway(
                    self.broker,
                    bind=gw_cfg.get("bind", "0.0.0.0"),
                    port=int(gw_cfg.get("port", 7325)),
                    mountpoint=gw_cfg.get("mountpoint", "gbt32960/"),
                )
            )
        elif kind == "coap":
            from ..gateway.coap import CoapGateway

            await self.broker.gateways.load(
                CoapGateway(
                    self.broker,
                    bind=gw_cfg.get("bind", "0.0.0.0"),
                    port=int(gw_cfg.get("port", 5683)),
                )
            )
        elif kind == "ocpp":
            from ..gateway.ocpp import OcppGateway

            await self.broker.gateways.load(
                OcppGateway(
                    self.broker,
                    bind=gw_cfg.get("bind", "0.0.0.0"),
                    port=int(gw_cfg.get("port", 33033)),
                    mountpoint=gw_cfg.get("mountpoint", "ocpp/"),
                    qos=int(gw_cfg.get("qos", 2)),
                )
            )
        elif kind == "lwm2m":
            from ..gateway.lwm2m import Lwm2mGateway

            await self.broker.gateways.load(
                Lwm2mGateway(
                    self.broker,
                    bind=gw_cfg.get("bind", "0.0.0.0"),
                    port=int(gw_cfg.get("port", 5783)),
                    mountpoint=gw_cfg.get("mountpoint", "lwm2m/{ep}/"),
                    translators=gw_cfg.get("translators"),
                    qos=int(gw_cfg.get("qos", 0)),
                )
            )
        elif kind == "exproto":
            from ..gateway.exproto import ExprotoGateway

            await self.broker.gateways.load(
                ExprotoGateway(
                    self.broker,
                    bind=gw_cfg.get("bind", "0.0.0.0"),
                    port=int(gw_cfg.get("port", 7993)),
                    handler_address=gw_cfg.get(
                        "handler", "127.0.0.1:9100"
                    ),
                    adapter_bind=gw_cfg.get("adapter_bind", "127.0.0.1:0"),
                )
            )
        else:
            log.warning("unknown gateway type %r ignored", kind)

    async def _housekeeping(self) -> None:
        """Delayed wills + detached-session expiry (the reference's
        per-process timers, centralized)."""
        while True:
            await asyncio.sleep(1.0)
            self.broker.tick()
            self.sys.tick()
            self.sysmon.tick()
            if self.telemetry is not None:
                self.telemetry.tick()
            if self.otel is not None:
                self.otel.tick()
            defer_flush = self.broker.olp.defer_sink_flush
            for agg in self.broker.aggregators:
                try:
                    agg.tick(defer=defer_flush)
                except Exception:
                    log.exception("aggregator tick failed")
            for client in self.exhook_clients:
                if not client.loaded:
                    # blocking dial: keep it off the event loop
                    await asyncio.get_running_loop().run_in_executor(
                        None, client.retry
                    )
            for lst in self.listeners:
                lst.maybe_reload_crl()

    async def _start_sink(self, sink_cfg: dict) -> None:
        """One config-declared data-integration sink: registered with
        the resource manager under its id, addressable from rule
        SinkActions (the emqx_bridge boot path)."""
        sid = sink_cfg["id"]
        stype = sink_cfg.get("type", "http")
        if stype == "kafka":
            from ..kafka import KafkaProducerResource

            res = KafkaProducerResource(
                [tuple(b) for b in sink_cfg["bootstrap"]],
                topic=sink_cfg["topic"],
                acks=int(sink_cfg.get("acks", -1)),
                client_id=sink_cfg.get(
                    "client_id", self.broker.config.node_name
                ),
            )
        elif stype == "http":
            from ..resources import HttpSink

            res = HttpSink(
                sink_cfg["url"],
                method=sink_cfg.get("method", "POST"),
                headers=sink_cfg.get("headers"),
            )
        else:
            raise ValueError(f"unknown sink type {stype!r}")
        await self.broker.resources.create(
            sid, res,
            max_buffer=int(sink_cfg.get("max_buffer", 10_000)),
        )

    async def stop(self) -> None:
        turn_clock = self.broker.profiler.loop
        if turn_clock is not None:
            turn_clock.uninstall()  # first: whatever fails below
        # elastic-ops agents first: their loops kick sessions and must
        # not keep firing against a half-torn-down broker
        await self.broker.eviction.stop_evacuation()
        await self.broker.rebalance.stop()
        await self.broker.purger.stop_purge()
        if self._housekeeper is not None:
            await cancel_and_wait(self._housekeeper)
            self._housekeeper = None
        if self.api is not None:
            await self.api.stop()
            self.api = None
        if self.cluster_links is not None:
            await self.cluster_links.stop()
            self.cluster_links = None
        if self.cluster_node is not None:
            await self.cluster_node.stop()
            self.cluster_node = None
        for client in self.exhook_clients:
            try:
                await asyncio.get_running_loop().run_in_executor(
                    None, client.stop
                )
            except Exception:
                log.debug("exhook client stop failed", exc_info=True)
        self.exhook_clients = []
        if self.otel is not None:
            await self.otel.stop()
            self.otel = None
        for lst in self.listeners:
            await lst.stop()
        for qlst in self.quic_listeners:
            await qlst.stop()
        if self.broker.resume is not None:
            # after the listeners (no new resumes), before the batcher:
            # uncommitted jobs keep their boot checkpoints on disk, so
            # the NEXT boot replays their intervals — a stop mid-storm
            # is the crash case, handled the crash way (at-least-once)
            await self.broker.resume.stop()
        if self.broker.batcher is not None:
            await self.broker.batcher.stop()
            self.broker.batcher = None
        if self.broker.sender is not None:
            # after the listeners and the batcher: no connection and
            # no flush scope is left; the thread drains and is joined
            self.broker.sender.stop()
            self.broker.sender = None
        if self.broker.reader is not None:
            # (likewise: every connection has closed its slot)
            self.broker.reader.stop()
            self.broker.reader = None
        if self.telemetry is not None:
            await self.telemetry.stop()
            self.telemetry = None
        self.broker.plugins.unload_all()
        await self.broker.gateways.stop_all()
        await self.broker.resources.stop_all()
        await self.broker.access.close()
        self.broker.shutdown()
        self._thaw_boot_heap()

    async def run_forever(self) -> None:
        await self.start()
        try:
            await asyncio.Event().wait()
        finally:
            await self.stop()


def main(argv: Optional[List[str]] = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description="emqx_tpu MQTT broker")
    ap.add_argument("--port", type=int, default=None)
    ap.add_argument("--bind", default=None)
    ap.add_argument("--config", help="JSON config file", default=None)
    ap.add_argument(
        "--workers", type=int, default=0,
        help="spawn N worker processes sharing the port "
        "(SO_REUSEPORT accept pool, clustered on loopback)",
    )
    ap.add_argument(
        "--no-match-service", action="store_true",
        help="with --workers: legacy independent-worker pool (each "
        "worker matches in-process) instead of the shared match "
        "service + shm window ring topology",
    )
    ap.add_argument(
        "--check-config", action="store_true",
        help="validate config (file + EMQX_TPU_* env overrides) and "
        "exit: 0 = boots cleanly (bin/emqx check_config role)",
    )
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )
    if args.workers > 1:
        import json as _json

        from .multicore import main as mc_main

        base = None
        if args.config:
            with open(args.config) as f:
                base = _json.load(f)
        mc_main(
            args.workers,
            args.port or 1883,
            bind=args.bind or "0.0.0.0",
            base_config=base,
            match_service=not args.no_match_service,
        )
        return
    if args.config:
        from ..config import ConfigHandler

        cfg = ConfigHandler.load(args.config).root
    else:
        cfg = BrokerConfig()
    # EMQX_TPU_A__B=value environment overrides land between the file
    # and the CLI flags (the reference's EMQX_* env layering)
    from ..config import apply_env_overrides, check_config

    try:
        applied = apply_env_overrides(cfg)
    except ValueError as exc:
        print(f"config error: {exc}", file=sys.stderr)
        raise SystemExit(2)
    for path, value in applied:
        log.info("env override: %s = %r", path, value)
    if args.check_config:
        problems = check_config(cfg)
        for p in problems:
            print(f"config error: {p}", file=sys.stderr)
        print("config ok" if not problems else
              f"{len(problems)} problem(s)",
              file=sys.stderr if problems else sys.stdout)
        raise SystemExit(1 if problems else 0)
    problems = check_config(cfg)
    if problems:
        for p in problems:
            print(f"config error: {p}", file=sys.stderr)
        raise SystemExit(2)
    # CLI flags override the first listener only when given explicitly
    # (default 1883 / 0.0.0.0 must not clobber a config file)
    if args.port is not None:
        cfg.listeners[0].port = args.port
    if args.bind is not None:
        cfg.listeners[0].bind = args.bind
    server = BrokerServer(cfg)
    try:
        asyncio.run(server.run_forever())
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
