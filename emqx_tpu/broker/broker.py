"""Broker core: publish routing + fan-out dispatch.

The analogue of `emqx_broker` (/root/reference/apps/emqx/src/
emqx_broker.erl): ``publish`` runs the ``message.publish`` hook chain
(:255-278), stores retained copies, routes via the match engine
(match_routes, emqx_router.erl:511-516), and dispatches to subscriber
sessions (:639-673) — including the shared-subscription pick
(emqx_shared_sub.erl:144-166) and dropped-message accounting.

Publishes can go through one-at-a-time (``publish``) or micro-batched
(``PublishBatcher``): connections enqueue concurrently and one device
step matches the whole window — the SURVEY §7 batching strategy that
turns per-publish trie walks into one XLA call.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
from collections import Counter, deque
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..aio import Gate, cancel_and_wait
from ..access import AccessControl
from ..config import BrokerConfig
from ..engine import MatchEngine
from ..hooks import HookRegistry
from ..message import Message
from ..metrics import Metrics, Stats
from ..ops import dispatchasm
from ..ops.match_kernel import (
    DEC_DROP_BIT, DEC_QMAX_SHIFT, DEC_RETAIN_BIT, DEC_SUBID_BIT,
)
from ..retainer import Retainer
from ..router import Router
from ..tracecontext import extract_strip as _strip_ctx

log = logging.getLogger("emqx_tpu.broker")

# sentinel marking a message whose publish-hook fold raised (stage 1
# keeps per-message isolation across both the sync and async folds)
_PREPARE_ERROR = object()
from .. import topic as T
from ..codec import mqtt as C
from .cm import ConnectionManager
from .session import Session, SubOpts, window_entries
from .shared import SharedSubManager


class Broker:
    def __init__(
        self,
        config: Optional[BrokerConfig] = None,
        hooks: Optional[HookRegistry] = None,
        shared_strategy: Optional[str] = None,
    ) -> None:
        self.config = config or BrokerConfig()
        self.hooks = hooks or HookRegistry()
        self.metrics = Metrics()
        self.stats = Stats()
        # hot-path window profiler: stage histograms + flight recorder
        # (observability.py); always on by default, near-free per window
        from ..observability import Profiler

        prof_cfg = self.config.profiler
        self.profiler = Profiler(
            ring_size=prof_cfg.ring_size,
            events_cap=prof_cfg.events_cap,
            enabled=prof_cfg.enable,
            process_label=self.config.node_name,
        )
        # always-on flight recorder (flightrec.py): the per-process
        # black box.  Committed windows mirror into its numeric ring
        # via the profiler hook; olp transitions, breaker/alarm edges,
        # ring occupancy, failpoint fires and watchdog stalls join
        # them, and anomaly triggers freeze + dump the lot.
        from ..flightrec import FlightRecorder

        self.flight = FlightRecorder.from_config(
            self.config.flight,
            process_label=self.config.node_name,
            role="broker",
            metrics=self.metrics,
        )
        self.flight.profiler = self.profiler
        self.profiler.flight = self.flight if self.flight.armed else None
        # per-message lifecycle tracer (tracecontext.py): head-sampled
        # trace contexts through the batched path, spans cut from the
        # profiler's WindowRecords, propagated across cluster/worker
        # hops.  Inactive (the default) = one attribute load per
        # window on the hot path.
        from ..tracecontext import LifecycleTracer

        self.lifecycle = LifecycleTracer(
            self.config.tracing, node=self.config.node_name
        )
        # coordinated overload protection (olp.py): one load level 0-3
        # driving the degradation ladder.  Constructed unconditionally
        # (disabled by default) — hot paths read its precomputed flag
        # attributes, one attribute load per window.
        from ..olp import LoadMonitor

        self.olp = LoadMonitor(self, self.config.olp)
        eng_cfg = self.config.engine
        mc_cfg = self.config.multicore
        eng_kw = dict(
            max_levels=eng_cfg.max_levels,
            f_width=eng_cfg.f_width,
            m_cap=eng_cfg.m_cap,
            rebuild_threshold=eng_cfg.rebuild_threshold,
            use_device=eng_cfg.use_device,
            background_rebuild=eng_cfg.background_rebuild,
        )
        if mc_cfg.service_socket:
            # multicore layer-1 worker: match/decide via the shared
            # match service over the shm window ring, with a host-only
            # in-process mirror as the per-window fallback referee
            from .matchclient import ServiceMatchEngine

            engine = ServiceMatchEngine(
                socket_path=mc_cfg.service_socket,
                worker_id=mc_cfg.worker_id,
                ring_slots=mc_cfg.ring_slots,
                ring_slot_bytes=mc_cfg.ring_slot_bytes,
                decide_min=mc_cfg.decide_min,
                rpc_timeout=mc_cfg.rpc_timeout,
                **eng_kw,
            )
        else:
            engine = MatchEngine(**eng_kw)
        self.router = Router(
            engine=engine,
            shared=SharedSubManager(
                strategy=shared_strategy
                or self.config.mqtt.shared_subscription_strategy
            ),
        )
        # engine lifecycle events (XLA compiles, device_put transfers,
        # delta folds) land in the same profiler as the window stages
        self.router.engine.profiler = self.profiler
        # L1 ladder: background rebuilds defer while the broker is
        # overloaded (the delta tiers keep serving correctness)
        self.router.engine.defer_rebuild = self.olp.defer_rebuild
        if hasattr(engine, "flight_broadcast"):
            # multicore worker: the engine's control stream carries the
            # "dump now, correlated by id" broadcast, detects service
            # restarts, and samples its shm ring's occupancy at 1 Hz
            engine.flight = self.flight
            engine.metrics = self.metrics
            self.flight.on_trigger = engine.flight_broadcast
            from ..flightrec import EV_RING

            def _ring_sampler(fl, _ring=engine._ring) -> None:
                st = _ring.stats()
                fl.record(EV_RING, float(st["in_flight"]),
                          float(st["high_watermark"]),
                          float(st["full"]), float(st["free"]))

            self.flight.add_sampler(_ring_sampler)
        ret_cfg = self.config.retainer
        self.retainer = Retainer(
            max_retained_messages=ret_cfg.max_retained_messages,
            max_payload_size=ret_cfg.max_payload_size,
            msg_expiry_interval=ret_cfg.msg_expiry_interval,
            enable=ret_cfg.enable,
        )
        self.access = AccessControl(
            hooks=self.hooks,
            allow_anonymous=self.config.auth.allow_anonymous,
            authz_default=self.config.auth.authz_default,
            deny_action=self.config.auth.deny_action,
        )
        self.gcp_devices = None
        if self.config.gcp_device_enable:
            from ..gcp_device import (
                GcpDeviceAuthenticator, GcpDeviceRegistry,
            )

            os.makedirs(
                os.path.dirname(self.config.gcp_device_file) or ".",
                exist_ok=True,
            )
            self.gcp_devices = GcpDeviceRegistry(
                self.config.gcp_device_file
            )
            self.access.authenticators.append(
                GcpDeviceAuthenticator(self.gcp_devices)
            )
        self.cm = ConnectionManager(self._make_session)
        # ACL-cache eviction probes session liveness so pressure never
        # wipes a connected client's prefetched rows
        self.access.is_live = (
            lambda cid: self.cm.lookup(cid) is not None
        )
        self.cm.on_discarded = self._session_discarded
        self.cm.on_takenover = lambda s: self.metrics.inc("session.takenover")
        from ..resources import ResourceManager
        from ..rules.engine import RuleEngine

        self.rules = RuleEngine(broker=self)
        # `engine.warmup()` compiles the rules kernel for the
        # registered program along with the match buckets
        engine.rules_source = self.rules.device_program
        self.resources = ResourceManager()  # alarms wired below (init
        # order: the AlarmRegistry is constructed a few lines down)
        # Aggregators attached by rules/bridges (emqx_connector_
        # aggregator buffers): ticked by the server's 1 Hz housekeeping
        self.aggregators: List = []
        from ..modules import DelayedPublish, ExclusiveSub, TopicRewrite

        self.delayed = DelayedPublish(self)
        self.rewrite = TopicRewrite(self)
        self.exclusive = ExclusiveSub()
        from ..modules import TopicMetrics

        self.topic_metrics = TopicMetrics(self)
        from ..ops_guard import (
            AlarmRegistry,
            BannedList,
            FlappingDetector,
            SlowSubs,
        )

        from ..trace import TraceManager

        self.trace = TraceManager(self)
        # OTel span factory (otel.Tracer), wired by the OtelExporter
        # when trace export is enabled; None = zero-cost no-op
        self.tracer = None
        self.alarms = AlarmRegistry(self)
        self.resources.alarms = self.alarms
        # sink egress observability: breaker edges -> flight recorder,
        # flush deferrals -> olp counter, defer signal -> linger
        self.resources.metrics = self.metrics
        self.resources.flight = self.flight
        self.resources.olp = self.olp
        # failure-driven device→host degradation: the match engine's
        # circuit breaker reports trip/clear here, raising/clearing a
        # $SYS alarm and bumping counters.  The callbacks fire on
        # whichever thread ran the match (batcher executor, probe
        # thread), so the alarm publish hops to the event loop.
        self._loop = None  # captured by BrokerServer.start
        # the native sender and reader threads (ops/sockwriter
        # .SockSender, ops/sockreader.SockReader) while a BrokerServer
        # runs and the libraries are there; None otherwise
        self.sender = None
        self.reader = None
        self.router.engine.on_breaker_trip = self._engine_breaker_trip
        self.router.engine.on_breaker_clear = self._engine_breaker_clear
        self.banned = BannedList()
        fl = self.config.flapping
        self.flapping = FlappingDetector(
            self.banned,
            max_count=fl.max_count,
            window=fl.window,
            ban_time=fl.ban_time,
            enable=fl.enable,
        )
        ss = self.config.slow_subs
        self.slow_subs = SlowSubs(
            top_k=ss.top_k,
            # disabled = an unreachable threshold: the hot path's
            # hoisted floor check then never calls record()
            threshold_ms=(
                ss.threshold_ms if ss.enable else float("inf")
            ),
            expire_interval=ss.expire_interval,
        )
        # node/zone-aggregate ingress limiter (top of the hierarchy)
        self.zone_limiter = None
        zm = self.config.mqtt.zone_messages_rate
        zb = self.config.mqtt.zone_bytes_rate
        if zm > 0 or zb > 0:
            from ..limiter import ConnectionLimiter

            self.zone_limiter = ConnectionLimiter(
                messages_rate=zm, bytes_rate=zb, shared=True
            )
        from ..gateway import GatewayRegistry

        self.gateways = GatewayRegistry(self)
        from ..payload_pipeline import PayloadPipeline

        self.pipeline = PayloadPipeline(self)
        from ..rebalance import (
            EvictionAgent, PurgeAgent, RebalanceCoordinator,
        )

        self.eviction = EvictionAgent(self)
        self.rebalance = RebalanceCoordinator(self)
        self.purger = PurgeAgent(self)
        from ..plugins import PluginManager

        self.plugins = PluginManager(self, directory=self.config.plugin_dir)
        for name in self.config.plugins:
            self.plugins.load(name)
        from ..ft import FileTransfer

        ft_cfg = self.config.ft
        self.ft = FileTransfer(
            self,
            directory=ft_cfg.storage_dir,
            max_file_size=ft_cfg.max_file_size,
            transfer_ttl=ft_cfg.transfer_ttl,
            enable=ft_cfg.enable,
        )
        # delivery guards: predicates (clientid, msg) -> bool applied
        # at fan-out, AFTER routing — the last line of defense for
        # RESERVED ($-prefixed) topics, whose subscriptions can exist
        # without ever passing the client.subscribe hook (durable
        # resume, takeover import, boot-window subscribes). Only
        # consulted for $-topics so the ordinary fan-out path pays
        # nothing. Cluster linking uses this to pin $LINK/msg delivery
        # to the peer's agent session.
        self.delivery_guards: List[Callable[[str, Message], bool]] = []
        # window-level delivered observers: called ONCE per dispatch
        # window with [(clientid, deliveries), ...] — the batched
        # bridge point the exhook client uses so a 256-client window
        # costs one bridge call, not 256 hook-chain walks.  The
        # in-process per-(window, client) ``message.delivered`` hook
        # keeps firing with its stable signature regardless.
        self.delivered_batch_sinks: List[Callable] = []
        # ClusterNode installs itself here (the emqx_external_broker
        # registration point, emqx_broker.erl:379-380): provides
        # match_remote(topics) and forward(msg, nodes)
        self.external = None
        # live micro-batcher: installed+started by BrokerServer (needs a
        # running loop); when present, channels route publishes through
        # it instead of calling publish() synchronously
        self.batcher: Optional["PublishBatcher"] = None
        # durable storage + persistent sessions (emqx_persistent_message
        # gate + emqx_persistent_session_ds restore-on-reconnect)
        self.durable = None
        # mass-reconnect admission control + windowed replay (resume.py):
        # constructed with durable storage, DRIVEN by BrokerServer (its
        # async task flips `running`; loop-less unit tests keep the
        # synchronous scalar resume inside open_session)
        self.resume = None
        if self.config.durable.enable:
            from ..ds.persist import DurableSessions

            self.durable = DurableSessions(
                self.config.durable.data_dir,
                n_streams=self.config.durable.n_streams,
                store_qos0=self.config.durable.store_qos0,
                layout=self.config.durable.layout,
                fsync=self.config.durable.fsync,
                n_shards=self.config.durable.n_shards,
            )
            # detected corruption (quarantined log records, unreadable
            # sidecars) surfaces as $SYS alarms + counters — the
            # constructor buffered anything its own loads found
            self.durable.on_corruption = self._ds_corruption
            for evt in self.durable.corruption_events:
                self._ds_corruption(evt)
            self.durable.corruption_events = []
            # background census rebuild lifecycle -> ds_meta_rebuild
            # alarm (raised at start, cleared at completion); the store
            # keeps SERVING during the rebuild — reads are
            # correct-but-wider, which is what the alarm tells ops
            self.durable.on_rebuild = self._ds_rebuild
            for evt in self.durable.rebuild_events:
                self._ds_rebuild(evt)
            self.durable.rebuild_events = []
            # every group fsync is counted + histogrammed (the
            # profiler's ds_sync stage feeds the sync-latency surface)
            self.durable.gate.on_sync = self._ds_synced
            self.durable.gate.on_error = self._ds_sync_error
            # advertise boot-state filters as live routes so peers keep
            # forwarding (and this node keeps persisting) for sessions
            # detached across the restart — the reference gets this from
            # the DS-backed persistent-session router
            # (emqx_persistent_session_ds_router); without it,
            # remote-origin messages in the restart→reconnect window
            # would be persisted nowhere
            self.durable.on_drop = self.router.cleanup_client
            # drop checkpoints that expired while the broker was down
            # BEFORE advertising (and before their gate refs can persist
            # anything for sessions that can never legally resume)
            self.durable.purge_expired()
            for state in self.durable.boot_states():
                # shared filters advertise too (durable shared subs:
                # publishes in the all-offline window must keep
                # matching, and so keep persisting)
                for flt, opts_dict in state.subs.items():
                    self.router.subscribe(
                        state.clientid, flt, SubOpts.from_dict(opts_dict)
                    )
            from .resume import ResumeScheduler

            self.resume = ResumeScheduler(
                self, self.config.durable.resume
            )
            # every channel-detach path (MQTT teardown, gateway
            # adapters) releases a mid-replay session's slot at once;
            # the job — and its boot checkpoint — survive for the
            # reconnect (or, after a crash, the on-disk re-replay)
            self.cm.on_detached = self.resume.pause
        # clientid -> (fire_at, will message): MQTT 5 delayed wills
        self._pending_wills: Dict[str, Tuple[float, Message]] = {}
        self._last_ds_sync = time.time()
        self._last_ds_fsync = time.time()
        # window decision columns (PR 9): per-delivery QoS/no-local/
        # body-slot decisions computed as ONE vectorized pass per
        # window (host numpy or the device decide kernel, chosen by
        # the engine's cost model).  EMQX_TPU_NO_DECIDE=1 pins the
        # scalar per-run path — the property-tested referee.
        self._decide_columns = (
            os.environ.get("EMQX_TPU_NO_DECIDE") != "1"
        )

    # -------------------------------------------------- session setup

    def _make_session(self, clientid: str, clean_start: bool, **kw) -> Session:
        mqtt = self.config.mqtt
        self.metrics.inc("session.created")
        self.hooks.run("session.created", clientid)
        session = Session(
            clientid=clientid,
            clean_start=clean_start,
            max_inflight=kw.get("max_inflight", mqtt.max_inflight),
            max_mqueue_len=mqtt.max_mqueue_len,
            max_awaiting_rel=mqtt.max_awaiting_rel,
            await_rel_timeout=mqtt.await_rel_timeout,
            retry_interval=mqtt.retry_interval,
            expiry_interval=kw.get(
                "expiry_interval",
                0.0 if clean_start else mqtt.session_expiry_interval,
            ),
            upgrade_qos=mqtt.upgrade_qos,
            mqueue_priorities=mqtt.mqueue_priorities,
            mqueue_default_priority=mqtt.mqueue_default_priority,
            mqueue_store_qos0=mqtt.mqueue_store_qos0,
        )

        def on_dropped(msg: Message, reason: str) -> None:
            self.metrics.inc("delivery.dropped")
            self.metrics.inc(f"delivery.dropped.{reason}")
            self.hooks.run("delivery.dropped", clientid, msg, reason)

        session.on_dropped = on_dropped
        return session

    def _session_discarded(self, session: Session) -> None:
        self.metrics.inc("session.discarded")
        # a discarded session's parked retained catch-up dies with it
        # (dead jobs must not exhaust the defer cap)
        self.olp.cancel_retained_client(session.clientid)
        if self.resume is not None:
            # a discarded session is owed nothing: drop any in-flight
            # replay job (its checkpoint teardown follows right below)
            self.resume.cancel(session.clientid)
        if self.durable is not None:
            # the persistence gate must not outlive the session, or the
            # DS log grows forever for a subscriber that can never return
            self._release_gate(session)
            self.durable.discard(session.clientid)
        self.router.cleanup_client(session.clientid)
        self.exclusive.release_all(session.clientid)
        if self.external is not None:
            self.external.client_closed(session.clientid)
        self.hooks.run("session.discarded", session.clientid)

    @staticmethod
    def _gate_real(flt: str) -> str:
        """The persistence gate matches MESSAGE TOPICS, so a $share
        filter contributes its real topic part."""
        share = T.parse_share(flt)
        return share.topic if share else flt

    def _release_gate(self, session: Session) -> None:
        """Release exactly the persistence-gate refs this session holds."""
        if self.durable is not None:
            for flt in session.gate_filters:
                self.durable.remove_filter(self._gate_real(flt))
                if T.parse_share(flt) is not None:
                    self.durable.shared_leave(flt, session.clientid)
            session.gate_filters.clear()

    def session_terminated(self, clientid: str, session: Session) -> None:
        """A session ending with expiry<=0 (e.g. MQTT5 DISCONNECT that
        lowered session_expiry_interval to 0): drop router state AND the
        gate refs, or the gate persists messages for a session that can
        never return (emqx_channel session-expiry handling)."""
        self.olp.cancel_retained_client(clientid)
        if self.resume is not None:
            # the client explicitly abandoned the session: nothing is
            # owed — drop any in-flight replay job AND the boot
            # checkpoint it was draining (a later reconnect must not
            # resurrect state the protocol says is gone).  discard,
            # not drop_checkpoint: the boot state's gate refs were
            # transferred to the live session at restore and are
            # released exactly once by _release_gate below.
            self.resume.cancel(clientid)
            self.durable.discard(clientid)
        self._release_gate(session)
        self.router.cleanup_client(clientid)
        self.exclusive.release_all(clientid)
        # deliberately NOT dropping the ACL cache entry here: an
        # immediate reconnect's fresh prefetch can precede this
        # teardown; dead entries reclaim under cache pressure instead
        if self.external is not None:
            self.external.client_closed(clientid)
        self.metrics.inc("session.terminated")

    # ---------------------------------------------------- subscribe

    def subscribe(
        self,
        clientid: str,
        flt: str,
        opts: SubOpts,
        is_new_sub: bool = True,
        defer_ok: bool = False,
    ) -> List[Message]:
        """Register the subscription; returns retained messages to
        replay per retain_handling ([MQTT-3.3.1-9..11]).

        ``defer_ok``: the caller DELIVERS the returned retained list
        (the MQTT SUBSCRIBE path), so under the olp ladder its
        catch-up may park for a deferred flush.  Callers that discard
        the return (gateway adapters, takeover import, auto-subscribe)
        must leave it False — a parked job would later deliver a
        retained burst those paths never produce."""
        self.router.subscribe(clientid, flt, opts)
        # gate refcount: only a NEW subscription counts (an options
        # refresh re-subscribe must not inflate it past drainability).
        # session.gate_filters records exactly which refs this session
        # holds, so every termination path releases them exactly once.
        if self.durable is not None:
            # shared filters gate too (durable shared subs,
            # emqx_ds_shared_sub): the group's offline interval must
            # persist so members replay their stream shares on resume
            session = self.cm.lookup(clientid)
            if (
                session is not None
                and session.expiry_interval > 0
                and flt not in session.gate_filters
            ):
                self.durable.add_filter(self._gate_real(flt))
                session.gate_filters.add(flt)
                if opts.share_group is not None:
                    # durable group membership drives the replay-time
                    # stream assignment across restarts
                    self.durable.shared_join(flt, clientid)
        self.hooks.run("session.subscribed", clientid, flt, opts)
        self.stats.set("subscriptions.count", self._sub_count())
        if opts.share_group is not None:
            return []  # retained never replay to shared subs [MQTT-4.8.2-27]
        rh = opts.retain_handling
        if rh == 2 or (rh == 1 and not is_new_sub):
            # a re-subscribe whose options forbid retained also
            # cancels any catch-up job a deferred earlier subscribe
            # parked — the flush must honor the CURRENT options
            self.olp.cancel_retained(clientid, flt)
            return []
        if (
            defer_ok
            and self.olp.defer_admissions
            and self.olp.defer_retained(clientid, flt)
        ):
            # L1 ladder: the retained match walk + catch-up burst park
            # until the ladder steps back to 0 (counted + alarmed;
            # flushed by the olp tick)
            return []
        # an inline replay supersedes any job still parked from an
        # earlier deferred subscribe — delivering both would duplicate
        # the retained burst (QoS1 included)
        self.olp.cancel_retained(clientid, flt)
        return self.retainer.match(flt)

    def unsubscribe(self, clientid: str, flt: str) -> bool:
        ok = self.router.unsubscribe(clientid, flt)
        if ok:
            self.olp.cancel_retained(clientid, flt)
            if self.durable is not None:
                session = self.cm.lookup(clientid)
                if session is not None and flt in session.gate_filters:
                    session.gate_filters.discard(flt)
                    self.durable.remove_filter(self._gate_real(flt))
                    if T.parse_share(flt) is not None:
                        self.durable.shared_leave(flt, clientid)
            self.hooks.run("session.unsubscribed", clientid, flt)
            self.stats.set("subscriptions.count", self._sub_count())
        return ok

    def _sub_count(self) -> int:
        return self.router.subscription_count()

    # --------------------------------------------- session open/close

    def open_session(
        self, clean_start: bool, clientid: str, channel, **session_kwargs
    ) -> Tuple[Session, bool]:
        """`emqx_cm:open_session` plus durable restore: when the broker
        restarted and the in-memory session is gone, a clean_start=false
        reconnect rebuilds the session from its DS checkpoint and
        replays messages persisted since disconnect
        (emqx_persistent_session_ds resume).

        Under a running server the replay itself is handed to the
        resume scheduler (CONNACK-then-drain: the session returns
        immediately, its backlog streams in as dispatch windows under
        admission control); with no scheduler running (unit tests
        driving the broker synchronously) the legacy in-line scalar
        replay fills the mqueue before returning.  Raises `ResumeBusy`
        — BEFORE creating any session state — when admission is
        saturated, so the channel answers CONNACK server-busy and the
        client backs off."""
        resume = self.resume
        if (
            resume is not None
            and resume.running
            and not clean_start
            and self.cm.lookup(clientid) is None
            and self.durable.has_checkpoint(clientid)
            and not resume.pending(clientid)
            and resume.saturated()
        ):
            from .resume import ResumeBusy

            self.metrics.inc("session.resume.busy")
            raise ResumeBusy(clientid)
        session, present = self.cm.open_session(
            clean_start, clientid, channel, **session_kwargs
        )
        if self.external is not None:
            self.external.client_opened(clientid)
        if present or clean_start or self.durable is None:
            if self.durable is not None and (clean_start or present):
                if (
                    present
                    and not clean_start
                    and resume is not None
                    and resume.pending(clientid)
                ):
                    # reconnect of a session still mid-replay: the new
                    # channel takes over and the scheduler continues
                    # where the cursors left off.  The boot checkpoint
                    # STAYS until commit — its on-disk cursors are the
                    # crash-recovery story for the un-replayed tail.
                    resume.reattach(clientid)
                else:
                    # a live resume or clean start invalidates any
                    # on-disk checkpoint — else a later restart would
                    # double-replay messages already delivered live.
                    # drop_checkpoint also releases the gate refs
                    # _load_states took for the boot state, which no
                    # live session carries.
                    self.durable.drop_checkpoint(clientid)
            if (
                present
                and not clean_start
                and self.external is not None
                and hasattr(self.external, "merge_replica_into")
            ):
                # quorum-replica tail merge (raft mode): a local resume
                # on an ADOPTER node must still see entries that
                # committed after the adoption import
                self.external.merge_replica_into(session)
            return session, present
        state = self.durable.load(clientid)
        if state is None:
            return session, False
        # rebuild subscriptions, then replay the missed interval —
        # scheduled (windows after CONNACK) or in-line (scalar referee)
        for flt, opts_dict in state.subs.items():
            opts = SubOpts.from_dict(opts_dict)
            session.subscribe(flt, opts)
            self.router.subscribe(clientid, flt, opts)
            # the boot-state gate refs (taken in _load_states, shared
            # filters included) transfer to the live session, to be
            # released exactly once on its eventual discard/termination
            session.gate_filters.add(flt)
        if resume is not None and resume.running:
            # CONNACK-then-drain: the backlog arrives as replay windows
            # under admission control; commit (checkpoint discard +
            # session.resumed) fires when the last window is handed off
            resume.admit(clientid, state, session)
            return session, True
        complete = self._resume_scalar(session, state)
        if complete:
            # live again; saved on next disconnect.  An INCOMPLETE
            # replay (a chaos-dropped read with no scheduler to retry)
            # keeps the checkpoint — a restart re-replays the interval
            # instead of skipping the blocked tail — and does NOT
            # count as resumed: the backlog was never fully handed off.
            self.durable.discard(clientid)
            self.metrics.inc("session.resumed")
            self.hooks.run("session.resumed", clientid)
        return session, True

    def _resume_scalar(self, session: Session, state) -> bool:
        """The scalar per-session resume loop — chunked `replay_chunk`
        reads baked into the session's mqueue, drained into the send
        window after CONNACK by `session.resume()`.  The referee the
        windowed resume path is property-tested bit-identical against
        (per-connection wire bytes, per-qos sent metrics, inflight
        windows), and the synchronous fallback when no scheduler task
        is running.  Returns True when the whole interval was read
        (False = a blocked read stopped progress; the checkpoint must
        survive)."""
        while True:
            msgs, done = self.durable.replay_chunk(state)
            self._resume_enqueue(session, msgs)
            if done:
                return True
            if not msgs:
                # no progress and not done: a blocked (chaos-dropped)
                # read — bail instead of spinning the event loop
                return False
            # NOTE: the iterator cursors are NOT checkpointed here.
            # Chunk messages live only in the in-memory mqueue until
            # the client drains them — persisting advanced cursors now
            # would skip those messages if we crash before delivery.
            # Chunking bounds replay memory; save_state is for callers
            # that durably hand off each chunk before advancing.

    def _resume_enqueue(self, session: Session, msgs) -> int:
        """Bake one replay chunk into a session's mqueue (the scalar
        resume path's delivery half; the scheduler's scalar mode calls
        it per chunk).  Applies the replay admission filters: the
        subscription must still exist, delivery guards for $-topics,
        no-local ([MQTT-3.8.3-3] — live-delivery parity: a client's
        own publishes never replay to a no_local subscription), and
        the mqueue's QoS0 store gate."""
        clientid = session.clientid
        store_q0 = self.config.mqtt.mqueue_store_qos0
        replayed = 0
        # PERF403 ignores: this loop is the scalar REFEREE — its
        # per-delivery reads define the semantics the windowed replay
        # columns are property-tested bit-identical against
        for flt, msg in msgs:
            opts = session.subscriptions.get(flt)
            if opts is None:
                continue
            if not self._delivery_allowed(clientid, msg):
                continue
            if opts.no_local and msg.from_client == clientid:  # brokerlint: ignore[PERF403]
                continue
            qos = session._effective_qos(msg.qos, opts)
            if qos == 0 and not store_q0:
                continue
            session.mqueue.insert(
                session._queued(msg, opts, max(qos, 0))
            )
            replayed += 1
        return replayed

    # ------------------------------------------- cross-node takeover

    @staticmethod
    def _serialize_pending(session: Session) -> List[Dict]:
        """Wire-serialize everything a session still owes its client:
        unacked inflight PUBLISHes FIRST (granted qos + dup, exactly as
        a local resume redelivers, [MQTT-4.6.0-1]) then the mqueue
        backlog.  Shared by takeover export and buddy replication."""
        from ..cluster.node import msg_to_wire

        queued: List[Dict] = []
        for _pid, entry in session.inflight.items():
            if entry.msg is not None:
                w = msg_to_wire(entry.msg)
                w["qos"] = entry.qos
                w["dup"] = True
                queued.append(w)
        queued.extend(msg_to_wire(m) for m in session.mqueue)
        return queued

    def export_session(self, clientid: str) -> Optional[Dict]:
        """Serialize and REMOVE a session for migration to another node
        (the owning side of emqx_cm's takeover protocol,
        emqx_cm.erl:314-317).  The live channel (if any) is closed with
        the takeover reason; local router/gate/checkpoint state is
        released because the session now lives elsewhere."""
        from ..cluster.node import msg_to_wire

        session = self.cm.lookup(clientid)
        if session is None:
            return None
        channel = self.cm.channel(clientid)
        if channel is not None:
            channel.close("takenover")
        self.olp.cancel_retained_client(clientid)  # leaves this node
        queued = self._serialize_pending(session)
        while session.mqueue.pop() is not None:
            pass  # drained: the session leaves this node
        state = {
            "subs": {
                flt: opts.to_dict()
                for flt, opts in session.subscriptions.items()
            },
            "expiry": session.expiry_interval,
            "queued": queued,
            "awaiting_rel": list(session.awaiting_rel.keys()),
        }
        self._release_gate(session)
        if self.resume is not None:
            # the session leaves this node: drop any pending replay
            # job with it.  A takeover racing a mid-replay drain
            # exports only inflight+mqueue (the DS tail travels as far
            # as it was drained) — the pre-scheduler code had no such
            # window because replay completed inside CONNECT, but it
            # also stalled the broker for the whole backlog to get it.
            self.resume.cancel(clientid)
        if self.durable is not None:
            self.durable.discard(clientid)
        self.router.cleanup_client(clientid)
        self.exclusive.release_all(clientid)
        self.cm.remove(clientid)
        if self.external is not None:
            self.external.client_closed(clientid)
        self.metrics.inc("session.takenover")
        self.hooks.run("session.takenover", clientid)
        return state

    def adopt_orphan_session(
        self, clientid: str, state: Dict, expiry: float
    ) -> None:
        """The connection that requested a takeover died before the
        state arrived; the owning node already destroyed its copy, so
        re-home it as a DETACHED local session (resumable by the next
        reconnect) instead of losing it."""
        session = self._make_session(
            clientid,
            clean_start=False,
            expiry_interval=max(expiry, float(state.get("expiry", 0.0))),
        )
        self.cm.attach_detached(clientid, session)
        self.import_session(session, state)
        if self.external is not None:
            self.external.client_opened(clientid)
        log.warning(
            "adopted orphaned takeover state for %s (requester died)",
            clientid,
        )

    def import_session(self, session: Session, state: Dict) -> None:
        """Rebuild a migrated session's state into a freshly opened
        local session (the taking side of the takeover protocol)."""
        from ..cluster.node import msg_from_wire

        for flt, opts_dict in state.get("subs", {}).items():
            opts = SubOpts.from_dict(opts_dict)
            session.subscribe(flt, opts)
            self.subscribe(session.clientid, flt, opts, is_new_sub=True)
        for wire in state.get("queued", ()):
            m = msg_from_wire(wire)
            if self._delivery_allowed(session.clientid, m):
                session.mqueue.insert(m)
        now = time.time()
        for pid in state.get("awaiting_rel", ()):
            session.awaiting_rel[int(pid)] = now
        self.metrics.inc("session.imported")

    def channel_disconnected(self, clientid: str) -> None:
        """Checkpoint a persistent session at channel close so a broker
        restart can rebuild it (emqx_persistent_session_ds commit).
        A stale close (takeover: a NEW channel is already attached) must
        not checkpoint, or a restart would double-replay messages the
        live connection already received."""
        session = self.cm.lookup(clientid)
        if (
            session is not None
            and self.cm.channel(clientid) is None
            and session.expiry_interval > 0
            and session.subscriptions
        ):
            if self.durable is not None:
                if self.resume is not None and self.resume.pending(
                    clientid
                ):
                    # disconnected MID-REPLAY: do NOT overwrite the
                    # boot checkpoint — a fresh disconnected_at=now
                    # checkpoint would skip the un-replayed tail after
                    # a restart (QoS1 loss).  The original checkpoint
                    # still covers the whole interval; the paused job
                    # continues on reconnect, or a restart re-replays
                    # from disk (at-least-once).  Subscription changes
                    # the live window made DO need to reach disk, with
                    # the original disconnected_at/cursors preserved.
                    self.resume.pause(clientid)
                    self.resume.refresh_checkpoint(clientid, session)
                elif not self.resume_home_shard(clientid):
                    # multicore foreign-shard worker: never checkpoint
                    # here — the client's home worker keeps the ONE
                    # canonical checkpoint (two data dirs holding rival
                    # checkpoints for one client would split-brain the
                    # next resume)
                    self.metrics.inc("session.resume.foreign_shard")
                else:
                    try:
                        self.durable.save(
                            clientid, session.subscriptions,
                            session.expiry_interval,
                        )
                    except Exception:
                        # a failed checkpoint write (disk fault,
                        # ds.meta.write chaos) leaves the PREVIOUS
                        # checkpoint in place: recovery replays from
                        # the older disconnected_at — at-least-once,
                        # and teardown must not die over it
                        log.exception(
                            "durable checkpoint failed for %s", clientid
                        )
            if self.external is not None:
                # buddy replication (simplified emqx_ds_builtin_raft):
                # the checkpoint + everything pending survives this
                # node's death on the clientid's buddy peer
                queued = self._serialize_pending(session)
                self.external.replicate_checkpoint(
                    clientid,
                    {
                        flt: o.to_dict()
                        for flt, o in session.subscriptions.items()
                    },
                    session.expiry_interval,
                    queued,
                )

    # ------------------------------------------------------ publish

    def publish(self, msg: Message) -> int:
        """Route one message; returns the delivery count."""
        return self.publish_many([msg])[0]

    def publish_many(self, msgs: Sequence[Message]) -> List[int]:
        """Route a micro-batch: all topics matched in one device step.

        Composed of three stages so the `PublishBatcher` can run the
        device-bound middle stage in an executor (keeping the event loop
        reading sockets during the kernel round-trip) while the
        state-mutating stages stay on the loop thread."""
        rec = self.profiler.begin(len(msgs))
        dur = self.durable
        always = dur is not None and dur.fsync_mode == "always"
        wm0 = dur.gate.appended if always else 0
        live, results = self.publish_prepare(msgs)
        if rec is not None:
            rec.lap("prepare")
        matched, remote = self.publish_match(live, rec=rec)
        counts = self.publish_dispatch(live, matched, remote, results, rec)
        if always and dur.gate.appended > wm0 and dur.gate.dirty:
            # loop-less group commit (no batcher): the caller acks
            # after this returns, so the covering flush happens here —
            # still amortized once per publish_many window.  Gated on
            # THIS window's captures (watermark moved), so a $SYS tick
            # or other non-captured publish never pays a blocking
            # fsync for the batcher's in-flight appends.
            dur.gate.sync_now()
        return counts

    def publish_prepare(
        self, msgs: Sequence[Message]
    ) -> Tuple[List[Message], List[Optional[int]]]:
        """Stage 1 (loop thread): publish hooks, retained store, and the
        durable persistence gate."""
        lifecycle = self.lifecycle
        if lifecycle.active:
            # head-sample BEFORE the hook fold so egress taps that run
            # inside it (cluster-link forward) see the context; an
            # inactive tracer costs this one bool per window
            for msg in msgs:
                lifecycle.ingress(msg)
        outs: List[object] = []
        for msg in msgs:
            # per-message isolation: one hook/retainer failure must not
            # poison the other up-to-4095 messages in the window
            try:
                outs.append(self.hooks.run_fold("message.publish", (), msg))
            except Exception:
                log.exception("publish prepare failed for %s", msg.topic)
                outs.append(_PREPARE_ERROR)
        return self._prepare_finish(msgs, outs)

    async def publish_prepare_async(
        self, msgs: Sequence[Message]
    ) -> Tuple[List[Message], List[Optional[int]]]:
        """`publish_prepare` for the batcher: when an IO-backed
        ``message.publish`` hook is loaded (exhook verdict RPC), the
        folds await off-loop concurrently instead of serializing
        blocking round-trips on the event loop; without one this is
        exactly the sync path."""
        if not self.hooks.has_async("message.publish"):
            return self.publish_prepare(msgs)
        lifecycle = self.lifecycle
        if lifecycle.active:
            for msg in msgs:  # idempotent: see publish_prepare
                lifecycle.ingress(msg)

        async def fold_one(msg: Message) -> object:
            try:
                return await self.hooks.run_fold_async(
                    "message.publish", (), msg
                )
            except Exception:
                log.exception("publish prepare failed for %s", msg.topic)
                return _PREPARE_ERROR

        outs = await asyncio.gather(*(fold_one(m) for m in msgs))
        return self._prepare_finish(msgs, list(outs))

    def _prepare_finish(
        self, msgs: Sequence[Message], outs: List[object]
    ) -> Tuple[List[Message], List[Optional[int]]]:
        """Shared tail of stage 1: apply fold verdicts, store retained,
        persist the surviving window."""
        live: List[Message] = []
        results: List[Optional[int]] = []
        for msg, out in zip(msgs, outs):
            if out is _PREPARE_ERROR:
                self.metrics.inc("messages.publish.error")
                results.append(0)
                continue
            if out is None:
                self.metrics.inc("messages.dropped")
                self.hooks.run("message.dropped", msg, "by_hook")
                results.append(0)
                continue
            msg = out  # type: ignore[assignment]
            try:
                self.metrics.inc("messages.publish")
                if self.tracer is not None and not msg.sys:
                    # one publish span per routed message; an upstream
                    # traceparent (publisher's user property) becomes
                    # the parent and the span's context is injected so
                    # every subscriber receives the continued trace
                    span = self.tracer.start(
                        "message.publish",
                        parent=self.tracer.extract(msg.properties),
                        attrs={
                            "messaging.system": "mqtt",
                            "messaging.destination.name": msg.topic,
                            "messaging.client_id": msg.from_client or "",
                            "mqtt.qos": msg.qos,
                        },
                        kind=2,  # SERVER: the broker handling the inbound publish
                    )
                    if span is not None:
                        self.tracer.inject(msg.properties, span)
                        msg._otel_span = span
                if msg.retain and not msg.sys:
                    if self.retainer.store(msg):
                        if msg.payload:
                            self.metrics.inc("messages.retained")
            except Exception:
                log.exception("publish prepare failed for %s", msg.topic)
                self.metrics.inc("messages.publish.error")
                results.append(0)
                continue
            live.append(msg)
            results.append(None)  # fill from dispatch below
        if live and self.durable is not None:
            try:
                self.durable.persist(live)
            except Exception:
                log.exception("durable persist failed for window")
        return live, results

    def publish_match(
        self, live: Sequence[Message], congested: bool = False, rec=None
    ) -> Tuple[List[Set[str]], Optional[List[Set[str]]]]:
        """Stage 2 (any thread): one batched match step for local
        filters + remote route nodes.  Only reads engine state the
        MatchEngine locks internally."""
        return self.publish_match_finish(
            self.publish_match_submit(live, congested, rec)
        )

    def publish_match_submit(
        self, live: Sequence[Message], congested: bool = False, rec=None
    ):
        """Stage 2a: dispatch the window's match WITHOUT waiting on the
        device (JAX async dispatch), so the batcher can submit the next
        windows while this one's transfer streams back — the pipelining
        that amortizes the host<->device round-trip from one thread.

        ``rec`` (the window's flight-recorder entry) rides the handle
        to the finish side: the two match stages may run on different
        executor threads, but strictly one after the other."""
        if not live:
            return (None, [], rec)
        if rec is not None:
            entered = time.perf_counter()
            rec.mark("match_submit")
        topics = [m.topic for m in live]
        engine = self.router.engine
        try:
            pending = engine.match_batch_submit(topics, congested=congested)
        except Exception:
            log.exception(
                "match submit failed for window of %d; host fallback",
                len(topics),
            )
            pending = None
        if rec is not None:
            # the wait is the executor's queue, between the collector's
            # call and the engine's first timed section
            rec.lap_parts(
                "match_submit", "submit_queue_wait", entered,
                engine.submit_timings(pending) if pending else (),
            )
        return (pending, topics, rec)

    def publish_match_finish(
        self, handle
    ) -> Tuple[List[Set[str]], Optional[List[Set[str]]]]:
        """Stage 2b: wait for the device result, overlay host tiers,
        and run the remote route match.  Any failure degrades to the
        host oracle instead of failing (and disconnecting) the whole
        window."""
        pending, topics, rec = handle
        if not topics:
            return [], None
        path = "host-fallback"
        # the engine reports the path that ACTUALLY served the window
        # (an internal device fault degrades to host without raising —
        # the flight record must say so) and what it timed on the way
        info: Dict[str, object] = {}
        if rec is not None:
            entered = time.perf_counter()
            info["seq"] = rec.seq
        try:
            if pending is None:
                matched = self.router.engine.match_batch_host(topics)
            else:
                matched = self.router.engine.match_batch_finish(
                    pending, info=info
                )
                path = info.get("path", pending[0])
        except Exception:
            log.exception(
                "device match failed for window of %d; host fallback",
                len(topics),
            )
            matched = self.router.engine.match_batch_host(topics)
        if rec is not None:
            # the wait: queued behind the predecessors' dispatch in the
            # ordered dispatch loop, then the hop into this thread
            timings = info.get("timings", ())
            rec.lap_parts(
                "match_wait", "finish_queue_wait", entered, timings
            )
            rec.n_clips = sum(
                name == "dense_rematch" for name, _, _ in timings
            )
            rec.n_host_rows = info.get("host_rows", 0)
            rec.residual_filters = info.get("residual")
            rec.path = path
            rec.breaker_open = self.router.engine.breaker_open
        remote: Optional[List[Set[str]]] = None
        if self.external is not None:
            try:
                remote = self.external.match_remote(topics)
            except Exception:
                log.exception("remote match failed for window")
        return matched, remote

    def publish_dispatch(
        self,
        live: Sequence[Message],
        matched: Sequence[Set[str]],
        remote: Optional[Sequence[Set[str]]],
        results: List[Optional[int]],
        rec=None,
    ) -> List[int]:
        """Stage 3 (loop thread): fan the WHOLE window out to sessions
        in one vectorized pass, forward to peers, then run all rule
        hits over the batch in one predicate step.  Commits ``rec`` —
        the window's profiler record — whatever happens above."""
        if rec is not None:
            # the hop from the finish's executor thread back to the
            # loop (the queue behind predecessor windows is the match
            # wait's ``finish_queue_wait``): its own span, not smeared
            # into expand
            rec.lap("dispatch_wait")
        rule_sink: List[Tuple[Message, List[str]]] = []
        counts: List[int] = []
        if live:
            try:
                counts = self._dispatch_window(
                    live, matched, rule_sink=rule_sink, rec=rec
                )
            except Exception as exc:
                log.exception(
                    "window dispatch failed for %d messages", len(live)
                )
                self.metrics.inc("messages.publish.error", len(live))
                counts = [0] * len(live)
                # unhandled dispatch fault: exactly the black-box case —
                # freeze the ring while the evidence is still in it
                self.flight.dispatch_fault("publish_dispatch", exc)
        j = 0
        for i, r in enumerate(results):
            if r is None:
                results[i] = counts[j]
                if remote is not None and remote[j]:
                    try:
                        self.metrics.inc("messages.forward")
                        self.external.forward(live[j], remote[j])
                    except Exception:
                        log.exception(
                            "forward failed for %s", live[j].topic
                        )
                        self.metrics.inc("messages.publish.error")
                        results[i] = 0
                j += 1
        if rule_sink:
            # ONE registry pass for the whole window: shared column
            # extraction + the rules x window matrix (rec carries the
            # rules_extract/rules_eval sub-stage attribution)
            if rec is not None:
                rec.mark("rules")
            try:
                self.rules.apply_batch(rule_sink, rec=rec)
            except Exception:
                log.exception("rule batch failed for window")
            if rec is not None:
                rec.lap("rules")
        if rec is not None:
            self.profiler.commit(rec)
        return [r if r is not None else 0 for r in results]

    def dispatch_forwarded(self, msg: Message) -> int:
        """Deliver a message forwarded in from a peer node: local
        dispatch only — publish hooks, retained storage, and rules
        already ran on the origin node, and re-forwarding would loop
        (the reference's forward lands directly in `dispatch/2`,
        emqx_broker.erl:408-420)."""
        return self.dispatch_forwarded_many([msg])

    def dispatch_forwarded_many(self, msgs: Sequence[Message]) -> int:
        """Batched forwarded dispatch: one gate pass + one match step
        per inbound cluster frame."""
        if not msgs:
            return 0
        lifecycle = self.lifecycle
        if lifecycle.active:
            # adopt the origin node's sampled contexts (stripped from
            # the wire properties) so this node's dispatch spans parent
            # to the origin's forward span — the cross-node half of one
            # connected trace.  sample=False: the head decision was
            # made ONCE, at the origin's ingress
            for msg in msgs:
                lifecycle.ingress(msg, sample=False)
        else:
            # tracing off on this node: still strip the carrier so the
            # internal property never reaches a subscriber's wire
            for msg in msgs:
                if msg.properties:
                    _strip_ctx(msg.properties)
        if self.durable is not None:
            # each node durably stores what its own gate needs: DS is
            # node-local here (unlike the reference's replicated DS), so
            # a local persistent session's messages must be persisted on
            # THIS node even when published remotely
            try:
                self.durable.persist(list(msgs))
            except Exception:
                if self.durable.fsync_mode == "always":
                    # the receiver must NOT fwd-ack a window it failed
                    # to store — the origin's replay copy is the only
                    # remaining one.  Raising leaves the frame un-acked
                    # (and un-deduped), so the retransmit re-delivers:
                    # at-least-once instead of silent loss.
                    raise
                log.exception("durable persist failed for forwarded batch")
        rec = self.profiler.begin(len(msgs), source="forwarded")
        matched = self.router.match_batch([m.topic for m in msgs])
        if rec is not None:
            rec.lap("match_submit")
            rec.path = "host"
        try:
            return sum(self._dispatch_window(
                list(msgs), matched, run_rules=False, rec=rec
            ))
        except Exception:
            log.exception(
                "forwarded dispatch failed for window of %d", len(msgs)
            )
            return 0
        finally:
            if rec is not None:
                self.profiler.commit(rec)

    # ----------------------------------------------------- dispatch

    def _dispatch(
        self,
        msg: Message,
        filters: Set[str],
        run_rules: bool = True,
        rule_sink: Optional[List] = None,
    ) -> int:
        """Fan one routed message out (a 1-message window)."""
        return self._dispatch_window(
            [msg], [filters], run_rules=run_rules, rule_sink=rule_sink
        )[0]

    def _dispatch_window(
        self,
        msgs: Sequence[Message],
        matched: Optional[Sequence[Set[str]]],
        run_rules: bool = True,
        rule_sink: Optional[List] = None,
        rec=None,
        preexpanded: Optional[Tuple] = None,
        replay: bool = False,
    ) -> List[int]:
        """Fan a whole routed window out to subscriber sessions
        (emqx_broker:dispatch + do_dispatch, :408-420, :639-673),
        window-at-a-time, mirroring how the match half works:

          1. the router CSR-expands every message's matched fid set to
             flat (msg_idx, client_row, opts_row) arrays in one
             vectorized pass — rule fids and shared-group fids split
             off as distinct columns;
          2. pure-rule / no-subscriber messages short-circuit before
             any subscriber grouping;
          3. one stable lexsort groups the window per client, so each
             session takes ONE deliver call, each connection ONE
             corked write, and counters/spans aggregate per
             (window, client) instead of per delivery.

        Rule hits accumulate into ``rule_sink`` for one batched
        predicate pass over the window (or run per message without
        one).  Delivery-guard, shared-pick skip-dead, no-local and
        RAP semantics are bit-identical to the per-message walk (the
        CSR property/regression suites are the referee).

        ``preexpanded`` (the durable-replay window path) supplies the
        ``(msg_idx, client_rows, opts_rows)`` delivery columns
        directly — already client-contiguous, each client's entries in
        its own replay order — bypassing route expansion AND the
        per-client lexsort: replay targets are explicit (the resuming
        client, not every subscriber of the filter) and their
        per-client order is the replay-cursor order the scalar referee
        produces.  ``replay`` suppresses the live-traffic accounting
        that has no meaning for catch-up backlogs (no-subscriber
        drops, e2e latency samples, slow-subs scans), while decision
        columns, encode-once slots, the native window splice, and
        lifecycle spans run exactly as for live fan-out."""
        router = self.router
        n = len(msgs)
        counts = [0] * n
        if rec is not None:
            rec.mark("expand")
        if preexpanded is None:
            msg_idx, rows, opts_rows, rules, s_msg, s_key = (
                router.expand_window(matched)
            )
        else:
            msg_idx, rows, opts_rows = preexpanded
            rules = []
            s_key = ()
        if len(s_key):
            # shared-group columns: one live member per (msg, filter,
            # group), picked for the whole window at once
            s_msg, s_rows, s_opts_rows = self._shared_window(
                msgs, s_msg, s_key, rec
            )
        else:
            s_rows = ()
        if rec is not None:
            rec.lap("expand")
            # the socket writes from here to the flush lap are inside
            # this window's laps as well as on the loop's clock
            self.profiler.loop.in_window = True
        if rules and run_rules:
            # ``rules`` is already grouped per message; the sink takes
            # the RAW id lists (the rule engine's flatten cache dedups
            # and canonicalizes vectorized), the per-message path
            # dedups here
            for i, rids in rules:
                if rule_sink is not None:
                    rule_sink.append((msgs[i], rids))
                else:
                    self.rules.apply(msgs[i], sorted(set(rids)))
        n_direct = len(rows)
        mloc: Counter = Counter()  # batched counter deltas (one lock)
        touched = bytearray(n)
        corked: List = []
        n_clients = 0
        traced_clients: Optional[Dict] = None
        bake_cache: Dict = {}  # shared detached-window mqueue bakes
        delivered_runs: Optional[List] = (
            [] if self.delivered_batch_sinks else None
        )
        # one O(1) registry probe per window: with no hook registered
        # (the common deployment) every run skips the hook walk AND the
        # per-run delivery-list materialization feeding it
        deliver_hook = self.hooks.has("message.delivered")
        asm = [0.0] if rec is not None else None  # native assemble time
        # oldest publish timestamp in the window: the per-run slow-subs
        # scan only runs when this could possibly cross the threshold.
        # Replay windows carry hours-old timestamps by construction —
        # a catch-up backlog is not a slow subscriber.
        ts_min = 0.0 if replay else min(
            (m.timestamp for m in msgs if m.timestamp), default=0.0
        )
        if n_direct or len(s_rows):
            if len(s_rows):
                all_rows = np.concatenate([rows, s_rows])
                all_msg = np.concatenate([msg_idx, s_msg])
                all_opts_rows = np.concatenate([opts_rows, s_opts_rows])
            else:
                all_rows, all_msg = rows, msg_idx
                all_opts_rows = opts_rows
            if preexpanded is None:
                # stable sort: per-client deliveries keep publish
                # order, and direct entries stay ahead of shared for
                # equal keys
                order = np.lexsort((all_msg, all_rows))
                sra = all_rows[order]
                sm_a = all_msg[order]
                so_a = all_opts_rows[order]
            else:
                # replay columns arrive client-contiguous with each
                # client's entries in REPLAY order (not msg_idx order
                # — two resuming clients may legitimately see shared
                # messages in different per-filter orders); the run
                # machinery only needs contiguity
                sra, sm_a, so_a = all_rows, all_msg, all_opts_rows
            dollar = None
            if self.delivery_guards and not replay:
                # guards are only ever consulted for $-topics, so a
                # guarded broker with none in the window still takes
                # the vectorized path
                dollar = [m.topic.startswith("$") for m in msgs]
                if not any(dollar):
                    dollar = None
            if dollar is None:
                # every expanded delivery reaches a target: mark the
                # window's matched messages in one pass
                for i in np.unique(all_msg).tolist():
                    touched[i] = 1
            enc = C.DispatchEncoder()
            if dollar is None and self._decide_columns:
                n_clients, traced_clients = self._dispatch_columns(
                    msgs, sra, sm_a, so_a, counts, enc, mloc, corked,
                    bake_cache, delivered_runs, deliver_hook, asm,
                    ts_min, rec,
                )
            else:
                if rec is not None:
                    rec.mark("deliver")
                n_clients = self._dispatch_scalar(
                    msgs, sra, sm_a, so_a, dollar, touched, counts,
                    enc, mloc, corked, bake_cache, delivered_runs,
                    deliver_hook, asm, ts_min,
                )
        if rec is not None:
            rec.lap("deliver", then="flush")
            if asm[0]:
                # nested sub-stage: the native splice share of deliver
                rec.sub("assemble", asm[0])
        # flush: ONE concatenated write per connection for the whole
        # window (each channel was corked on first touch), and ONE
        # hand-over of them all to the sender thread where one runs
        snd = self.sender
        if snd is not None:
            snd.begin()
        try:
            for ch in corked:
                try:
                    ch.uncork()
                except Exception:
                    log.exception("window uncork failed")
        finally:
            if snd is not None:
                snd.end()
        if delivered_runs:
            # ONE bridge call per window per sink (exhook coalescing);
            # fired after the flush so the wire never waits on it
            for sink in self.delivered_batch_sinks:
                try:
                    sink(delivered_runs)
                except Exception:
                    log.exception("delivered batch sink failed")
        delivered = sum(counts)
        if delivered:
            mloc["messages.delivered"] += delivered
        if rec is not None:
            rec.lap("flush")
            self.profiler.loop.in_window = False
            rec.n_deliveries = delivered
            rec.n_clients = n_clients
            if delivered and not replay:
                # end-to-end publish→delivery latency per delivered
                # message (Message.timestamp is stamped at ingress —
                # replay windows would only pollute the histogram with
                # outage-length "latencies")
                now_e2e = time.time()
                e2e = rec.e2e_ms
                for i, msg in enumerate(msgs):
                    if counts[i] and msg.timestamp:
                        e2e.append((now_e2e - msg.timestamp) * 1e3)
        lifecycle = self.lifecycle
        if lifecycle.active:
            # lifecycle spans for the window's SAMPLED messages, cut
            # entirely from the flight record's existing timestamps —
            # one call per window, outside the dispatch loops, zero
            # additional clock reads (the OBS601 gate pins this down).
            # ``traced_clients`` (columns mode) names each sampled
            # message's delivering clients on its span.
            lifecycle.window_spans(
                msgs, counts, rec, n_clients, clients=traced_clients
            )
        tracer = self.tracer
        if not replay:
            # replay windows never account "no subscribers": a backlog
            # entry filtered at window build (unsubscribed since the
            # checkpoint, QoS0 store gate) was not a dropped publish
            for i, msg in enumerate(msgs):
                if not touched[i]:
                    mloc["messages.dropped"] += 1
                    mloc["messages.dropped.no_subscribers"] += 1
                    self.hooks.run(
                        "message.dropped", msg, "no_subscribers"
                    )
                if tracer is not None:
                    span = getattr(msg, "_otel_span", None)
                    if span is not None:
                        span.attrs["messaging.deliveries"] = counts[i]
                        tracer.end(span)
        self.metrics.inc_bulk(mloc)
        return counts

    def _dispatch_scalar(
        self,
        msgs: Sequence[Message],
        sra: np.ndarray,
        sm_a: np.ndarray,
        so_a: np.ndarray,
        dollar: Optional[List[bool]],
        touched: bytearray,
        counts: List[int],
        enc: "C.DispatchEncoder",
        mloc: Counter,
        corked: List,
        bake_cache: Dict,
        delivered_runs: Optional[List],
        deliver_hook: bool,
        asm: Optional[List[float]],
        ts_min: float,
    ) -> int:
        """The scalar per-run window fan-out: one `_deliver_run` per
        client with eagerly materialized delivery lists — the
        decision-column path's property-tested referee, and the only
        path for $-topic windows with delivery guards (whose
        per-delivery predicate has no columnar form)."""
        router = self.router
        srl = sra.tolist()
        sm = sm_a.tolist()
        # resolve every delivery's (msg, opts) object refs once, with
        # C-speed maps over the flat columns — the vectorized
        # replacement for per-subscriber dict churn
        msg_seq = list(map(msgs.__getitem__, sm))
        opts_seq = list(map(router.opts_at, so_a.tolist()))
        cuts = np.flatnonzero(sra[1:] != sra[:-1]) + 1
        bounds = [0, *cuts.tolist(), len(srl)]
        client_of = router.client_of_row
        n_clients = 0
        for bi in range(len(bounds) - 1):
            k, e = bounds[bi], bounds[bi + 1]
            clientid = client_of(srl[k])
            if dollar is None:
                deliveries = list(zip(msg_seq[k:e], opts_seq[k:e]))
                d_idx = sm[k:e]
            else:
                deliveries = []
                d_idx = []
                for t in range(k, e):
                    i = sm[t]
                    msg = msg_seq[t]
                    if dollar[i] and not self._delivery_allowed(
                        clientid, msg
                    ):
                        continue
                    deliveries.append((msg, opts_seq[t]))
                    d_idx.append(i)
                    touched[i] = 1
                if not deliveries:
                    continue
            n_clients += 1
            try:
                flags = self._deliver_run(
                    clientid, deliveries, enc, mloc, corked,
                    bake_cache=bake_cache,
                    delivered_runs=delivered_runs,
                    deliver_hook=deliver_hook,
                    asm=asm,
                    ts_min=ts_min,
                )
            except Exception:
                log.exception("dispatch to %s failed", clientid)
                # keep the error observable: the legacy per-message
                # path bumped this counter on any dispatch failure
                mloc["messages.publish.error"] += 1
                continue
            if flags is None:  # connected channel: all delivered
                for i in d_idx:
                    counts[i] += 1
            else:
                for i, f in zip(d_idx, flags):
                    if f:
                        counts[i] += 1
        return n_clients

    @staticmethod
    def _materialize_run(msgs, router, sm_l, so_a, k: int, e: int):
        """One client run's ``[(msg, opts)]`` delivery list.  The
        columns path builds this ONLY when a consumer actually needs
        it — a registered ``message.delivered`` hook, a batch sink, an
        OTel deliver span, or a lifecycle-sampled message in the run —
        so an unconsumed fanout window allocates zero per-delivery
        tuples (the regression suite spies on this exact method)."""
        opts_at = router.opts_at
        so = so_a[k:e].tolist()
        return [
            (msgs[sm_l[t]], opts_at(so[t - k])) for t in range(k, e)
        ]

    def _dispatch_columns(
        self,
        msgs: Sequence[Message],
        sra: np.ndarray,
        sm_a: np.ndarray,
        so_a: np.ndarray,
        counts: List[int],
        enc: "C.DispatchEncoder",
        mloc: Counter,
        corked: List,
        bake_cache: Dict,
        delivered_runs: Optional[List],
        deliver_hook: bool,
        asm: Optional[List[float]],
        ts_min: float,
        rec=None,
    ) -> Tuple[int, Optional[Dict]]:
        """Decision-column window fan-out: every per-delivery decision
        — effective QoS (both upgrade variants), the no-local drop
        mask, retain-as-published, subscription-identifier presence,
        the DispatchEncoder body-slot key, the QoS1-needs-pid mask —
        computes in ONE vectorized pass over the sorted ``(msg_idx,
        client_rows, opts_rows)`` columns (host numpy or the device
        decide kernel, per the engine's cost model), and the whole
        window's wire assembles in ONE GIL-released native splice with
        per-client output slices.  Per run, Python touches only the
        session: look-up, stall test, cork, one packet-id block and
        one bulk inflight insert of entries the window built once a
        (message, QoS); a run that takes no other branch is *plain*
        (``WindowRecord.n_clients_plain``).  What the splice reads —
        body slots (one `key_slots` a protocol version over the kept
        key column), packet ids (each run's first id plus the pending
        rank) and counts (one bincount) — is built once a window
        after the loop.  The branches off that path (no session,
        detached, stalled, a subscription identifier, no room in the
        in-flight window, a channel without ``send_wire``, the exact
        id allocator, a consumer of per-delivery objects) stay in the
        one loop body; delivery lists materialize lazily via
        `_materialize_run`.

        Wire bytes, counts, per-qos sent metrics and inflight windows
        are bit-identical to `_dispatch_scalar` (the property suite in
        tests/test_decide_columns.py is the referee).  Returns
        ``(n_clients, traced_clients)``."""
        router = self.router
        n = len(msgs)
        nd_total = len(sra)
        row_of = router.row_of_client
        if rec is not None:
            rec.mark("decide")

        def from_row(m) -> int:
            r = row_of(m.from_client) if m.from_client else None
            return -1 if r is None else r

        # per-message attribute vectors: one short pass over the
        # window's B messages, never its N deliveries
        m_qos = np.fromiter((m.qos for m in msgs), np.int8, n)
        m_retain = np.fromiter((m.retain for m in msgs), bool, n)
        m_from = np.fromiter((from_row(m) for m in msgs), np.int32, n)
        dec_info: Optional[Dict] = {} if rec is not None else None
        packed, _dec_path = router.engine.decide_window(
            router.opts_columns(), router.opts_rev,
            so_a, sra, sm_a, m_qos, m_retain, m_from, dec_info,
        )
        # unpack the compact column into the window-wide decision
        # views (numpy bit ops; one byte per delivery came back)
        qmin = (packed & 3).astype(np.int64)
        qmax = ((packed >> DEC_QMAX_SHIFT) & 3).astype(np.int64)
        drop = (packed & DEC_DROP_BIT) != 0
        retn = (packed & DEC_RETAIN_BIT) != 0
        sidb = (packed & DEC_SUBID_BIT) != 0
        # body-slot keys less the effective QoS, which a run's variant
        # adds (the run picks one by its session's upgrade_qos)
        base_key = sm_a * 6 + retn
        if rec is not None:
            if "device_wait" in dec_info:
                start, dur = dec_info["upload"]
                rec.sub("decide_upload", dur, start)
                start, dur = dec_info["device_wait"]
                rec.sub("decide_device_wait", dur, start)
                rec.decide_rows, rec.decide_rows_padded = dec_info["rows"]
            rec.lap("decide", then="deliver")
        # per-message tracing masks, computed ONCE per window: a run
        # materializes its deliveries for the OTel span / lifecycle
        # trace only when it actually carries a traced message
        tracer = self.tracer
        otel = None
        if tracer is not None:
            otel = np.fromiter(
                (getattr(m, "_otel_span", None) is not None
                 for m in msgs), bool, n,
            )
            if not otel.any():
                otel = None
        samp = None
        if self.lifecycle.active:
            samp = np.fromiter(
                (getattr(m, "_trace_ctx", None) is not None
                 for m in msgs), bool, n,
            )
            if not samp.any():
                samp = None
        traced_clients: Optional[Dict] = {} if samp is not None else None
        lib = dispatchasm.load()
        native_ok = lib is not None
        cnt = np.zeros(n, dtype=np.int64)
        now_w = time.time()  # ONE clock read for the whole window
        floor = now_w - self.slow_subs.threshold_ms / 1000.0
        scan_slow = bool(ts_min) and ts_min < floor
        cm_lookup = self.cm.lookup
        cm_channel = self.cm.channel
        client_of = router.client_of_row
        starts = np.concatenate(
            (np.zeros(1, dtype=np.int64),
             np.flatnonzero(sra[1:] != sra[:-1]) + 1)
        )
        bounds = starts.tolist()
        bounds.append(nd_total)
        nruns = len(starts)
        run_len = np.diff(starts, append=nd_total)
        rows_l = sra[starts].tolist()
        # what a run is told from columns, window-wide: a subscription
        # identifier in it, and whether a consumer wants its deliveries
        # as objects (hook / batch sink: every run; an OTel or sampled
        # message: the runs that carry one)
        keepw = ~drop
        subid_l = np.maximum.reduceat(sidb, starts).tolist()
        cons_l: Optional[List[bool]] = None
        if deliver_hook or delivered_runs is not None:
            cons_l = [True] * nruns
        elif otel is not None or samp is not None:
            traced = otel if samp is None else (
                samp if otel is None else otel | samp
            )
            cons_l = np.maximum.reduceat(traced[sm_a], starts).tolist()
        # the delivery lists' message column as a list: only the runs
        # off the wire path (and a window with consumers) read it
        sm_l: Optional[List[int]] = (
            sm_a.tolist() if cons_l is not None else None
        )
        # L2 overload shed: effective-QoS0 deliveries fold out of the
        # kept-for-wire set in ONE vectorized AND per QoS variant
        # ($SYS messages exempt — the overload alarm itself must
        # survive the ladder)
        shed0 = self.olp.shed_qos0_mask
        elig = shed_cell = None
        if shed0:
            elig = np.fromiter(
                (not m.sys for m in msgs), bool, n
            )[sm_a]
            shed_cell = [0]
        shed_native = 0
        # per-connection outbound high-watermark: a stalled
        # subscriber past it takes the drop/queue path, never the wire
        out_wm = self.config.mqtt.outbound_high_watermark
        # ONE in-flight entry a distinct (message, effective QoS > 0)
        # of the window, whichever variant asks first (entries are
        # replace-not-mutate; see session._InflightEntry)
        ent_tbl = np.empty(2 * n, dtype=object)
        variants: List[Optional[Tuple]] = [None, None]

        def variant(upgrade: bool) -> Tuple:
            """One effective-QoS variant's view of the window, made on
            the first run whose session asks for it (a deployment has
            one `upgrade_qos`): per-run kept / pending / QoS1 counts
            and shed units as lists, each run's offset into the
            variant's pending-order entry list, and last the
            per-delivery (kept mask, pending mask, body keys)."""
            q = qmax if upgrade else qmin
            if shed0:
                kw = keepw & ~((q == 0) & elig)
                shed_l = np.add.reduceat(
                    (keepw & ~kw).astype(np.int64), starts
                ).tolist()
            else:
                kw = keepw
                shed_l = None
            pend = keepw & (q > 0)
            kq = np.add.reduceat(pend.astype(np.int64), starts)
            n1 = np.add.reduceat(
                (pend & (q == 1)).astype(np.int64), starts
            )
            nk = np.add.reduceat(kw.astype(np.int64), starts)
            ents = window_entries(
                msgs, sm_a[pend] * 2 + (q[pend] - 1), ent_tbl, now_w
            )
            v = variants[upgrade] = (
                kq.tolist(), n1.tolist(), nk.tolist(),
                (np.cumsum(kq) - kq).tolist(), ents, shed_l,
                (kw, pend, base_key + q * 2),
            )
            return v

        # what the run loop leaves for the window's columnar pass:
        # which runs are in the splice plan, their first packet id
        # (the exact allocator's explicit lists aside), protocol
        # version and QoS variant; `late` marks the connected runs
        # that count whether or not the splice succeeds
        planned = bytearray(nruns)
        late = bytearray(nruns)
        upg_b = bytearray(nruns)
        first = [-1] * nruns
        ver_l = [-1] * nruns
        pid_over: List[Tuple[int, List[int]]] = []
        plan_sends: List[Tuple] = []  # (send_wire, (n0, n1, n2))
        cur = kq_l = n1_l = nk_l = poff_l = ents = shed_l = rows = None
        n_plain = 0
        for bi in range(nruns):
            clientid = client_of(rows_l[bi])
            try:
                session = cm_lookup(clientid)
                if session is None:
                    if self.durable is not None and \
                            self.durable.has_checkpoint(clientid):
                        # detached across a restart: already persisted
                        # by the gate, replays on resume — not a drop
                        continue
                    mloc["delivery.dropped"] += bounds[bi + 1] - bounds[bi]
                    continue
                channel = cm_channel(clientid)
                if channel is None or (
                    out_wm and self._stalled(session, channel)
                ):
                    # detached persistent session, or a stalled
                    # subscriber past its outbound watermark (the
                    # queue path keeps the wire buffers bounded):
                    # materialize the run (off the wire hot path) and
                    # take the SAME queue/bake/replicate code the
                    # scalar path uses
                    k, e = bounds[bi], bounds[bi + 1]
                    if sm_l is None:
                        sm_l = sm_a.tolist()
                    flags = (
                        self._queue_detached_run if channel is None
                        else self._queue_stalled_run
                    )(
                        session, clientid,
                        self._materialize_run(
                            msgs, router, sm_l, so_a, k, e
                        ),
                        mloc, bake_cache,
                    )
                    for t, f in enumerate(flags):
                        if f:
                            cnt[sm_l[k + t]] += 1
                    continue
                cork = getattr(channel, "cork", None)
                if cork is not None:
                    cork()
                    corked.append(channel)
                version = getattr(channel, "version", None)
                send_wire = getattr(channel, "send_wire", None)
                upgrade = session.upgrade_qos
                if upgrade is not cur:
                    cur = upgrade
                    kq_l, n1_l, nk_l, poff_l, ents, shed_l, rows = (
                        variants[upgrade] or variant(upgrade)
                    )
                kq = kq_l[bi]
                # lazy delivery lists: materialize ONLY for an actual
                # consumer — hook/batch sink (window-wide), or a
                # traced/sampled message in THIS run
                deliveries = None
                sampled_run = False
                plain = cons_l is None or not cons_l[bi]
                if not plain:
                    k, e = bounds[bi], bounds[bi + 1]
                    need = deliver_hook or delivered_runs is not None
                    if not need and otel is not None:
                        need = bool(otel[sm_a[k:e]].any())
                    sampled_run = (
                        samp is not None
                        and bool(samp[sm_a[k:e]].any())
                    )
                    if need or sampled_run:
                        deliveries = self._materialize_run(
                            msgs, router, sm_l, so_a, k, e
                        )
                in_plan = False
                if (
                    native_ok
                    and version is not None
                    and send_wire is not None
                    and not subid_l[bi]
                    # full/near-full inflight window: the scalar
                    # loop queues the overflow per delivery
                    and (not kq or session.inflight.room_for(kq))
                ):
                    if kq:
                        # the run's entries are its slice of the
                        # variant's pending-order list; its packet ids
                        # one consecutive block but for a wrap or a
                        # collision (the exact allocator's list)
                        p0 = poff_l[bi]
                        pids = session.bookkeep_entries(
                            ents[p0:p0 + kq]
                        )
                        if type(pids) is int:
                            first[bi] = pids
                        else:
                            pid_over.append((bi, pids))
                            plain = False
                    if shed_l is not None:
                        shed_native += shed_l[bi]
                    nk = nk_l[bi]
                    if nk:  # an all-dropped run has no wire (and
                        # would break the assemble plan's reduceat)
                        n1 = n1_l[bi]
                        plan_sends.append(
                            (send_wire, (nk - kq, n1, kq - n1))
                        )
                        # counts for planned runs are deferred until
                        # the window splice SUCCEEDS (parity with the
                        # scalar path, where a native failure raises
                        # before counting)
                        planned[bi] = in_plan = True
                        upg_b[bi] = upgrade
                        ver_l[bi] = version
                    n_plain += plain
                else:
                    if deliveries is None:
                        if sm_l is None:
                            sm_l = sm_a.tolist()
                        deliveries = self._materialize_run(
                            msgs, router, sm_l, so_a,
                            bounds[bi], bounds[bi + 1],
                        )
                    packets = session.deliver(
                        deliveries, encoder=enc, version=version,
                        shed_qos0=shed0, shed_cell=shed_cell,
                    )
                    channel.send_packets(packets)
                if cons_l is not None and cons_l[bi]:
                    if deliver_hook:
                        self.hooks.run(
                            "message.delivered", clientid, deliveries
                        )
                    if delivered_runs is not None:
                        delivered_runs.append((clientid, deliveries))
                    if sampled_run:
                        # a sampled message's lifecycle span names the
                        # clients that RECEIVED it (guard: sampled
                        # runs only — unsampled windows never enter
                        # here); a no-local-dropped (or olp-shed)
                        # delivery never reached this client, so the
                        # run's kept mask gates the attribution
                        keptr = rows[0][k:e]
                        for t, (dm, _o) in enumerate(deliveries):
                            if not keptr[t]:
                                continue
                            tctx = getattr(dm, "_trace_ctx", None)
                            if tctx is not None:
                                traced_clients.setdefault(
                                    id(dm), []
                                ).append(clientid)
                    if tracer is not None and deliveries is not None:
                        self._deliver_span(clientid, deliveries)
                # a connected run counts every delivery (parity with
                # the scalar path's all-delivered return), marked
                # LAST so a failed run contributes none; native-
                # planned runs count after the window splice succeeds
                if not in_plan:
                    late[bi] = True
            except Exception:
                log.exception("dispatch to %s failed", clientid)
                mloc["messages.publish.error"] += 1
                continue
        if shed0:
            # shed units from BOTH sub-paths (native kept-mask fold +
            # the session.deliver fallback's cell), flushed with the
            # window's other counters — never silent
            nshed = shed_native + shed_cell[0]
            if nshed:
                mloc["delivery.dropped"] += nshed
                mloc["delivery.dropped.olp_shed"] += nshed
        if rec is not None:
            rec.n_clients_plain = n_plain
        counted = np.frombuffer(late, dtype=bool)
        if scan_slow:
            # the window's OLDEST publish is past the slow-subs
            # threshold (a flood's every window is): ONE pass over the
            # connected runs' deliveries, not a scan a run
            served = counted | np.frombuffer(planned, dtype=bool)
            self._slow_scan_window(
                msgs, sra, sm_a,
                None if served.all() else np.repeat(served, run_len),
                now_w, floor,
            )
        if plan_sends:
            # the window's columnar pass over the planned runs: the
            # kept key column in run order IS the splice's body
            # column, packet ids are each run's first id plus the
            # pending rank, sizes come from the run bounds
            in_plan_a = np.frombuffer(planned, dtype=bool)
            vmin, vmax = variants
            if vmin is None or vmax is None:
                kw, pend, keys = (vmin or vmax)[-1]
            else:
                upg_row = np.repeat(
                    np.frombuffer(upg_b, dtype=bool), run_len
                )
                kw, pend, keys = (
                    np.where(upg_row, a, b)
                    for a, b in zip(vmax[-1], vmin[-1])
                )
            sel = kw if len(plan_sends) == nruns else (
                kw & np.repeat(in_plan_a, run_len)
            )
            pend_cum = np.cumsum(pend)
            # pending deliveries before each run's first: a pending
            # row's id is its run's first id plus its rank in the run
            before = pend_cum[starts] - pend[starts]
            pid_row = np.where(
                pend,
                np.repeat(
                    np.asarray(first, dtype=np.int64) - before - 1,
                    run_len,
                ) + pend_cum,
                -1,
            )
            for bi, pids in pid_over:
                k, e = bounds[bi], bounds[bi + 1]
                pid_row[k:e][pend[k:e]] = pids
            sel_cum = np.cumsum(sel)
            run_start = (sel_cum[starts] - sel[starts])[in_plan_a]
            if sel_cum[-1] != nd_total:
                keys = keys[sel]
                pid_row = pid_row[sel]
            vers = set(ver_l)
            vers.discard(-1)
            if len(vers) == 1:
                body_all = enc.key_slots(msgs, vers.pop(), keys)
            else:
                ver_row = np.repeat(np.asarray(ver_l), run_len)
                if len(ver_row) != len(keys):
                    ver_row = ver_row[sel]
                body_all = np.empty(len(keys), dtype=np.int64)
                for version in vers:
                    at = ver_row == version
                    body_all[at] = enc.key_slots(
                        msgs, version, keys[at]
                    )
            if self._assemble_window_native(
                lib, enc, body_all, pid_row, run_start, plan_sends,
                mloc, asm,
            ):
                counted = counted | in_plan_a
        # ONE bincount over the counted runs' message column
        if counted.all():
            cnt += np.bincount(sm_a, minlength=n)
        elif counted.any():
            cnt += np.bincount(
                sm_a[np.repeat(counted, run_len)], minlength=n
            )
        if cnt.any():
            for i in np.flatnonzero(cnt).tolist():
                counts[i] += int(cnt[i])
        return nruns, traced_clients

    def _assemble_window_native(
        self, lib, enc, body_all, pid_all, run_start, plan_sends,
        mloc, asm,
    ) -> bool:
        """Execute the window's splice plan: ONE GIL-released
        `da_assemble_window` call builds every planned run's wire into
        one buffer, then each connection gets its zero-copy slice as a
        corked ``Raw`` blob.  ``body_all`` / ``pid_all`` are the
        planned runs' kept deliveries in run order, ``run_start`` each
        run's first.  On a span-table mismatch (negative
        return) NO run's bytes ship — QoS>0 deliveries redeliver via
        the inflight retry path with dup=1, QoS0 are lost as on any
        failed write — because a partially shifted buffer could
        interleave one client's frames into another's stream.
        Returns False on that failure so the caller skips the planned
        runs' delivery counts too (the ``message.delivered`` hooks may
        already have fired — that asymmetry is accepted on this
        defensive invariant-violated path)."""
        nruns = len(plan_sends)
        # per-run byte sizes from the (now complete) span tables in
        # ONE vectorized pass over the window columns; the exclusive
        # cumsum is each run's planned output offset.  Zero-length
        # runs never enter the plan, so reduceat boundaries are sound.
        ho, hl, to, tl = enc.span_arrays()
        d_sizes = hl[body_all] + tl[body_all] + 2 * (pid_all >= 0)
        sizes = np.add.reduceat(d_sizes, run_start)
        run_out = np.zeros(nruns, dtype=np.int64)
        np.cumsum(sizes[:-1], out=run_out[1:])
        total = int(sizes.sum())
        out = bytearray(total)
        t0 = time.perf_counter() if asm is not None else 0.0
        try:
            wrote = dispatchasm.assemble_window(
                lib, enc.native_views(), body_all, pid_all,
                run_start, run_out, nruns, len(body_all), out,
            )
            if wrote != total:
                raise RuntimeError(
                    f"native window assembly wrote {wrote} of "
                    f"{total} bytes across {nruns} runs"
                )
        except Exception:
            log.exception(
                "native window assembly failed; dropping %d runs' "
                "wire (QoS>0 redelivers via retry)", nruns,
            )
            mloc["messages.publish.error"] += nruns
            return False
        finally:
            if asm is not None:
                asm[0] += time.perf_counter() - t0
        mv = memoryview(out)
        w0 = w1 = w2 = 0
        for (send_wire, npub), o, ln in zip(
            plan_sends, run_out.tolist(), sizes.tolist()
        ):
            # a channel that started closing mid-window drops its blob
            # (send_wire returns False) — its counters must not flush
            if send_wire(mv[o:o + ln], npub, count=False):
                w0 += npub[0]
                w1 += npub[1]
                w2 += npub[2]
        # ONE window-level flush of the sent counters (same registry
        # names `Channel.send_wire`/`send_packets` bump; inc_bulk
        # lands them under one lock with the rest of the window)
        total_pub = w0 + w1 + w2
        if total_pub:
            mloc["messages.sent"] += total_pub
            mloc["packets.publish.sent"] += total_pub
            if w0:
                mloc["messages.qos0.sent"] += w0
            if w1:
                mloc["messages.qos1.sent"] += w1
            if w2:
                mloc["messages.qos2.sent"] += w2
        return True

    def _stalled(self, session: Session, channel) -> bool:
        """Is this CONNECTED channel past its outbound high-watermark
        (or still draining a watermark-parked backlog)?  ONE home for
        the stall predicate on both dispatch paths."""
        out_wm = self.config.mqtt.outbound_high_watermark
        if not out_wm:
            return False
        ob = getattr(channel, "out_buffered", None)
        return ob is not None and (
            session.out_parked or ob() >= out_wm
        )

    def _queue_stalled_run(
        self, session: Session, clientid: str, deliveries,
        mloc: Counter, bake_cache: Optional[Dict],
    ) -> List[int]:
        """Route one stalled-subscriber run to the queue path: QoS0
        drops (counted ``delivery.dropped.out_buffer``), QoS>0 parks
        on the mqueue, and ``out_parked`` pins LATER deliveries behind
        the parked backlog (same-topic QoS>0 order must not invert);
        the channel's retry timer drains it once the buffer recovers.
        ONE home for the stall action on both dispatch paths."""
        flags = self._queue_detached_run(
            session, clientid, deliveries, mloc, bake_cache,
            q0_reason="out_buffer", replicate=False,
        )
        if any(flags):
            session.out_parked = True
        return flags

    def _delivery_allowed(self, clientid: str, msg: Message) -> bool:
        """Delivery-guard check; must gate EVERY path that puts a
        message in front of a session — live fan-out, durable replay,
        and takeover import — or a hookless subscription could receive
        reserved-topic traffic the guards exist to pin down."""
        if self.delivery_guards and msg.topic.startswith("$"):
            return all(g(clientid, msg) for g in self.delivery_guards)
        return True

    def _shared_window(
        self,
        msgs: Sequence[Message],
        s_msg: np.ndarray,
        s_key: np.ndarray,
        rec=None,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """A window's shared rows to delivery columns ``(msg_idx,
        client_rows, opts_rows)``, in the rows' order: one
        `SharedSubManager.pick_window` for every key whose members are
        all eligible, `_shared_pick` row by row for the rest; a row
        with no eligible member delivers nothing."""
        t0 = rec.now() if rec is not None else 0.0
        shared = self.router.shared
        p_rows, p_slots, served = shared.pick_window(
            s_msg, s_key, msgs, self._shared_eligible
        )
        n_vector = int(served.sum())
        if n_vector < len(s_key):
            no_member = 0
            for r in np.flatnonzero(~served).tolist():
                group, real = shared.key_of(int(s_key[r]))
                got = self._shared_pick(msgs[s_msg[r]], real, group)
                if got is None:
                    no_member += 1
                else:
                    p_rows[r], p_slots[r] = got
            shared.picks_no_member += no_member
            keep = p_rows >= 0
            s_msg, p_rows, p_slots = s_msg[keep], p_rows[keep], p_slots[keep]
        if rec is not None:
            rec.nest("shared_pick", t0)
            rec.n_shared += len(s_key)
            rec.n_shared_vector += n_vector
        return s_msg, p_rows, p_slots

    def _shared_eligible(self, clientid: str) -> bool:
        """A member may take a pick: its session exists and, with
        durable storage on, it is attached or has no expiry (a
        DETACHED persistent member's share of the group's traffic
        arrives via stream-assigned replay, durable shared subs —
        queueing here as well would double-deliver the offline
        interval)."""
        session = self.cm.lookup(clientid)
        return session is not None and (
            self.durable is None
            or self.cm.channel(clientid) is not None
            or session.expiry_interval <= 0
        )

    def _shared_pick(
        self, msg: Message, real: str, group: str
    ) -> Optional[Tuple[int, int]]:
        """Pick one eligible group member, skipping the others
        (redispatch, emqx_shared_sub.erl:144-166): its client row and
        the opts-TABLE slot its delivery rides, so shared deliveries
        ride the decision columns like direct ones; None where no
        member is eligible.  The scalar referee of `pick_window`."""
        shared = self.router.shared
        tried: Set[str] = set()
        while True:
            picked = shared.pick(group, real, msg, exclude=tried)
            if picked is None:
                return None
            if self._shared_eligible(picked):
                slot = self.router.shared_slot_of(real, group, picked)
                if slot is None:
                    return None
                row = self.router.row_of_client(picked)
                if row is None:  # defensive: intern on demand
                    row = self.router._intern(picked)
                return row, slot
            tried.add(picked)

    def _deliver_run(
        self,
        clientid: str,
        deliveries: List[Tuple[Message, SubOpts]],
        encoder: "C.DispatchEncoder",
        mloc: Counter,
        corked: List,
        bake_cache: Optional[Dict] = None,
        delivered_runs: Optional[List] = None,
        deliver_hook: bool = True,
        asm: Optional[List[float]] = None,
        ts_min: float = 0.0,
    ) -> Optional[List[int]]:
        """Deliver one client's slice of the window; returns a 0/1
        kept flag per delivery so counts attribute back to their
        messages (``None`` = the all-kept connected fast path, so the
        hot case allocates no flag list).  Counter deltas accumulate
        into ``mloc`` (flushed once per window); the client's channel
        is corked on first touch and flushed by the window.

        Connected channels take the native window fast path when the
        run qualifies (`Session.deliver_run_native`): one GIL-released
        splice builds the whole run's wire buffer, written into the
        cork buffer as one blob — per-delivery ``Packet`` objects only
        exist on the fallback loop.  ``asm`` accumulates the native
        splice time for the profiler's ``assemble`` sub-stage;
        ``bake_cache`` shares detached-session mqueue bakes across the
        window; ``delivered_runs`` collects (clientid, deliveries) for
        the window-level delivered sinks."""
        session = self.cm.lookup(clientid)
        nd = len(deliveries)
        if session is None:
            if self.durable is not None and self.durable.has_checkpoint(
                clientid
            ):
                # detached across a restart: the message was already
                # persisted by the gate and will replay on resume —
                # not a drop
                return [0] * nd
            mloc["delivery.dropped"] += nd
            return [0] * nd
        channel = self.cm.channel(clientid)
        if channel is not None:
            if self._stalled(session, channel):
                # stalled subscriber past its outbound watermark: the
                # queue path, shared with the columns gate
                return self._queue_stalled_run(
                    session, clientid, deliveries, mloc, bake_cache
                )
            # L2 overload shed on the scalar referee path: identical
            # semantics to the columns' folded mask (QoS0-only, $SYS
            # exempt), counted through the same registry names
            shed0 = self.olp.shed_qos0_mask
            shed_cell = [0] if shed0 else None
            cork = getattr(channel, "cork", None)
            if cork is not None:
                cork()
                corked.append(channel)
            version = getattr(channel, "version", None)
            res = None
            send_wire = getattr(channel, "send_wire", None)
            if encoder is not None and version is not None \
                    and send_wire is not None:
                if asm is not None:
                    t0 = time.perf_counter()
                    res = session.deliver_run_native(
                        deliveries, encoder, version,
                        shed_qos0=shed0, shed_cell=shed_cell,
                    )
                    if res is not None:  # only count runs it served
                        asm[0] += time.perf_counter() - t0
                else:
                    res = session.deliver_run_native(
                        deliveries, encoder, version,
                        shed_qos0=shed0, shed_cell=shed_cell,
                    )
            if res is not None:
                data, npub = res
                if data:
                    send_wire(data, npub)
            else:
                if shed_cell is not None:
                    shed_cell[0] = 0  # ineligible native probe: the
                    # fallback loop re-decides every delivery
                packets = session.deliver(
                    deliveries, encoder=encoder, version=version,
                    shed_qos0=shed0, shed_cell=shed_cell,
                )
                channel.send_packets(packets)
            if shed_cell is not None and shed_cell[0]:
                mloc["delivery.dropped"] += shed_cell[0]
                mloc["delivery.dropped.olp_shed"] += shed_cell[0]
            if deliver_hook:
                # skipped entirely (no method resolution, no chain
                # walk) when nothing registered for the hookpoint
                self.hooks.run("message.delivered", clientid, deliveries)
            if delivered_runs is not None:
                delivered_runs.append((clientid, deliveries))
            now = time.time()
            floor = now - self.slow_subs.threshold_ms / 1000.0
            if ts_min and ts_min < floor:
                # only scan the run when the window's OLDEST publish
                # could cross the threshold (the common all-fresh
                # window pays one compare, not one per delivery)
                self._slow_scan_run(
                    clientid, (m for m, _o in deliveries), now, floor
                )
            if self.tracer is not None:
                self._deliver_span(clientid, deliveries)
            return None  # all delivered
        # detached persistent session
        return self._queue_detached_run(
            session, clientid, deliveries, mloc, bake_cache
        )

    def _queue_detached_run(
        self,
        session: Session,
        clientid: str,
        deliveries: List[Tuple[Message, SubOpts]],
        mloc: Counter,
        bake_cache: Optional[Dict],
        q0_reason: Optional[str] = None,
        replicate: bool = True,
    ) -> List[int]:
        """Queue one DETACHED persistent session's run: QoS>0 queued,
        QoS0 dropped; returns per-delivery kept flags.  The baked
        queued copy (effective qos + subopts folded in) is shared
        across every detached session in the window via ``bake_cache``
        — one bake per (msg, qos, retain, subid) signature instead of
        one per (client, delivery); queued copies are never mutated
        downstream, so sharing is safe and `replicate_queued` wire
        output is unchanged.  ONE implementation serves both the
        scalar and the decision-column dispatch paths, so the bake
        signature and queue_full accounting can never diverge.  (Off
        the wire hot path: detached runs queue, they don't encode.)

        Also serves the CONNECTED-but-stalled case (outbound
        high-watermark): ``q0_reason`` attributes the QoS0 drops
        (``delivery.dropped.<q0_reason>``) and ``replicate=False``
        skips buddy replication — a live session's mqueue overflow is
        never replicated on the deliver path either."""
        flags = [0] * len(deliveries)
        replicated = []
        for k, (m, opts) in enumerate(deliveries):
            if opts.no_local and m.from_client == clientid:
                # [MQTT-3.8.3-3] — live-delivery parity: the wire
                # paths skip these via the drop column / deliver loop,
                # and a CONNECTED-but-stalled session routed here must
                # not have its own publishes queued back to it
                continue
            qos = session._effective_qos(m.qos, opts)
            if qos == 0:
                mloc["delivery.dropped"] += 1
                if q0_reason is not None:
                    mloc["delivery.dropped." + q0_reason] += 1
                continue
            if bake_cache is None:
                baked = session._queued(m, opts, qos)
            else:
                bkey = (
                    id(m), qos,
                    m.retain and opts.retain_as_published,
                    opts.subid,
                )
                baked = bake_cache.get(bkey)
                if baked is None:
                    baked = bake_cache[bkey] = session._queued(
                        m, opts, qos
                    )
            dropped = session.mqueue.insert(baked)
            if dropped is not None:
                mloc["delivery.dropped.queue_full"] += 1
                self.hooks.run("delivery.dropped", clientid, dropped, "queue_full")
            replicated.append(baked)
            flags[k] = 1
        if replicated and replicate and self.external is not None:
            from ..cluster.node import msg_to_wire

            self.external.replicate_queued(
                clientid, [msg_to_wire(m) for m in replicated]
            )
        return flags

    def _slow_scan_run(
        self, clientid: str, run_msgs, now: float, floor: float
    ) -> None:
        """Record slow deliveries for one client run (the caller has
        already pre-checked the window's oldest timestamp against the
        floor).  A sampled slow delivery records its trace id, so the
        slow-subs board links straight to the offending message's
        full lifecycle trace.  ONE implementation serves the scalar
        and columns paths — the threshold semantics and trace linkage
        cannot diverge."""
        slow = self.slow_subs
        for m in run_msgs:
            if m.timestamp and m.timestamp < floor:
                tctx = getattr(m, "_trace_ctx", None)
                slow.record(
                    clientid, m.topic,
                    (now - m.timestamp) * 1000.0,
                    trace_id=(
                        tctx.trace_id if tctx is not None else ""
                    ),
                )

    def _slow_scan_window(
        self, msgs, sra, sm_a, served, now: float, floor: float
    ) -> None:
        """`_slow_scan_run` for a whole window of the columns path:
        the latency of every delivery of the ``served`` rows (None:
        all) in one numpy pass, `SlowSubs.slowest` picks the few that
        a record a delivery would have left on the board, and only
        those are recorded, in the runs' order: same board, same
        trace linkage, no per-delivery Python."""
        slow = self.slow_subs
        ts = np.fromiter(
            (m.timestamp or 0.0 for m in msgs), np.float64, len(msgs)
        )
        lat = np.where(
            (ts > 0.0) & (ts < floor), (now - ts) * 1000.0, -1.0
        )[sm_a]
        if served is not None:
            lat = np.where(served, lat, -1.0)
        client_of = self.router.client_of_row
        for t in slow.slowest(lat).tolist():
            m = msgs[sm_a[t]]
            tctx = getattr(m, "_trace_ctx", None)
            slow.record(
                client_of(int(sra[t])), m.topic, float(lat[t]),
                trace_id=tctx.trace_id if tctx is not None else "",
            )

    def _deliver_span(
        self, clientid: str, deliveries: List[Tuple[Message, SubOpts]]
    ) -> None:
        """ONE aggregated ``message.deliver`` span per (window, client)
        — parented to the first traced message's publish span — instead
        of a span per delivery (the reference's message.deliver trace
        point, amortized so observability stops dominating fan-out)."""
        tracer = self.tracer
        pub_span = None
        topic = ""
        for m, _opts in deliveries:
            s = getattr(m, "_otel_span", None)
            if s is not None:
                pub_span, topic = s, m.topic
                break
        if pub_span is None:
            return
        attrs = {
            "messaging.system": "mqtt",
            "messaging.destination.name": topic,
            "messaging.client_id": clientid,
        }
        if len(deliveries) > 1:
            attrs["messaging.batch.message_count"] = len(deliveries)
        tracer.end(tracer.start(
            "message.deliver",
            parent=pub_span,
            attrs=attrs,
            kind=4,  # PRODUCER: broker pushing to subscriber
        ))

    # -------------------------------------------------- delayed wills

    def schedule_will(self, clientid: str, will: Message, delay: float) -> None:
        """Queue a will for will_delay_interval seconds
        ([MQTT-3.1.3.2.2]); a reconnect before the deadline cancels."""
        self._pending_wills[clientid] = (time.time() + delay, will)

    def cancel_will(self, clientid: str) -> None:
        self._pending_wills.pop(clientid, None)

    def tick(self, now: Optional[float] = None) -> None:
        """Periodic housekeeping: fire due wills, expire detached
        sessions (driven by BrokerServer's timer, or manually in
        tests)."""
        now = now if now is not None else time.time()
        due = [
            cid
            for cid, (at, _) in self._pending_wills.items()
            if now >= at
        ]
        for cid in due:
            _, will = self._pending_wills.pop(cid)
            self.publish(will)
        self.delayed.tick(now)
        self.topic_metrics.tick(now)
        self.olp.tick(now)
        # flight housekeeping: watchdog heartbeat, occupancy samplers,
        # failpoint drain, per-stage p99 SLO checks; also poll the
        # match service for its counters/histograms (fire-and-forget —
        # the pong lands on the client's reader thread)
        self.flight.tick(now, self.profiler)
        poll = getattr(self.router.engine, "poll_service", None)
        if poll is not None:
            poll()
        self.alarms.tick(now)
        self.slow_subs.tick(now)
        self.ft.tick(now)
        self.cm.expire_sessions(now)
        if self.durable is not None:
            self.durable.purge_expired(now)
            cfg = self.config.durable
            if now - self._last_ds_sync >= cfg.sync_interval:
                self._last_ds_sync = now
                self.durable.checkpoint_meta()  # census/index + progress
                self.durable.gc(
                    int((now - cfg.retention_hours * 3600.0) * 1e6)
                )
            if cfg.fsync != "never":
                # interval-mode group flush (and the `always` mode's
                # backstop for appends no dispatch barrier covered).
                # olp L1+ stretches the cadence 2x — fewer disk stalls
                # while shedding — but a parked-ack flush is the
                # gate's own worker and is NEVER skipped.
                eff = cfg.fsync_interval * (
                    2.0 if self.olp.level >= 1 else 1.0
                )
                if now - self._last_ds_fsync >= eff:
                    self._last_ds_fsync = now
                    self.durable.sync_soon()

    # ---------------------------------------------- engine breaker

    def _on_loop(self, fn) -> None:
        """Run `fn` on the broker's event loop when one is live (the
        breaker callbacks fire from executor/probe threads; a full
        $SYS publish must not run off-loop), else inline (unit tests
        driving the engine synchronously)."""
        loop = self._loop
        if loop is not None and not loop.is_closed():
            try:
                loop.call_soon_threadsafe(fn)
                return
            except RuntimeError:
                pass
        fn()

    # ------------------------------------------------ ds durability

    def _ds_corruption(self, evt: Dict) -> None:
        """Detected DS corruption (quarantined log suffix / unreadable
        metadata sidecar): counter + $SYS alarm.  The store already
        fell back conservatively (intact prefix keeps serving, replay
        restarts from the checkpoint) — this is the 'never silent'
        half of the contract."""
        kind = evt.get("kind", "meta")
        if kind == "storage":
            self.metrics.inc(
                "ds.storage.corrupt_records",
                int(evt.get("records", 1)),
            )
            name = "ds_storage_corruption"
            msg = "dslog quarantined unreadable records"
        else:
            self.metrics.inc("ds.meta.corruption")
            name = "ds_meta_corruption"
            msg = ("DS metadata sidecar unreadable; recovered "
                   "conservatively (at-least-once)")
        self._on_loop(lambda: self.alarms.activate(
            name, details=dict(evt), message=msg,
        ))

    def _ds_rebuild(self, evt: Dict) -> None:
        """Census-rebuild lifecycle: alarm up while a background
        rebuild runs (the store serves correct-but-wider reads from
        the log meanwhile), cleared when the scan lands.  An aborted
        rebuild (fault/shutdown) leaves the alarm up — the next open
        retries and ops can see the store is still unpruned."""
        event = evt.get("event")
        if event == "start":
            self.metrics.inc("ds.meta.rebuild")
            self._on_loop(lambda: self.alarms.activate(
                "ds_meta_rebuild", details=dict(evt),
                message=("DS census rebuilding in background; "
                         "reads serve unpruned from the log"),
            ))
        elif event == "done":
            self._on_loop(
                lambda: self.alarms.deactivate("ds_meta_rebuild")
            )

    def _ds_synced(self, dur_s: float) -> None:
        self.metrics.inc("ds.sync.count")
        self.profiler.stage("ds_sync", dur_s)
        self.flight.fsync(dur_s)

    def _ds_sync_error(self, exc: BaseException) -> None:
        self.metrics.inc("ds.sync.errors")

    def _engine_breaker_trip(self, info: Dict) -> None:
        self.metrics.inc("engine.breaker.trip")
        self.flight.breaker_edge(True, info)
        self._on_loop(lambda: self.alarms.activate(
            "engine_device_path",
            details=info,
            message="device match path tripped; serving host-only",
        ))

    def _engine_breaker_clear(self, info: Dict) -> None:
        self.metrics.inc("engine.breaker.clear")
        self.flight.breaker_edge(False, info)
        self._on_loop(
            lambda: self.alarms.deactivate("engine_device_path")
        )

    def shutdown(self) -> None:
        """Flush and close durable state (called by BrokerServer.stop)."""
        self.flight.stop()
        self.trace.stop_all()
        if self.durable is not None:
            self.durable.close()
        close = getattr(self.router.engine, "close", None)
        if close is not None:
            # multicore worker: detach from the match service and
            # unlink this worker's shm window ring
            close()

    def resume_home_shard(self, clientid: str) -> bool:
        """Is this worker the durable home for ``clientid``?  True in
        single-process brokers (shard_count 1); in a multicore pool,
        the client-id hash picks exactly one worker whose data dir
        holds the session's checkpoint + captures."""
        rcfg = self.config.durable.resume
        if int(rcfg.shard_count) <= 1:
            return True
        from .resume import shard_of

        return shard_of(
            clientid, int(rcfg.shard_count)
        ) == int(rcfg.shard_index)

    def node_info(self) -> Dict:
        """This node's row for ``GET /api/v5/nodes`` — also served to
        peers over the cluster ``node_info`` RPC so a multicore pool's
        merged view carries every worker's olp level and durability
        surface (the PR 13/PR 15 riders)."""
        node: Dict = {
            "node": self.config.node_name,
            "uptime": int(time.time() - self.metrics.start_time),
            "connections": len(self.cm),
            "node_status": "running",
        }
        if self.resume is not None:
            # resume-queue depth (mass-reconnect admission control)
            node["resume"] = self.resume.info()
        if self.olp.enabled:
            node["olp_level"] = self.olp.level
        if self.durable is not None:
            # durability contract surface: fsync mode, group-commit
            # flush counters, unsynced/parked backlog, corruption
            node["durability"] = self.durable.sync_stats()
        if self.flight.armed:
            node["flight"] = self.flight.status()
        egress = self.resources.summary()
        if egress["sinks"]:
            # sink-egress roll-up (PR 20 windowed pipeline): buffered
            # depth, batch count, deferral + breaker state at a glance
            node["egress"] = egress
        mc = self.config.multicore
        if mc.service_socket or mc.n_workers:
            node["multicore"] = {
                "worker_id": mc.worker_id,
                "n_workers": mc.n_workers,
            }
            svc_info = getattr(self.router.engine, "service_info", None)
            if svc_info is not None:
                node["multicore"]["service"] = svc_info()
        return node

    # -------------------------------------------------- config updates

    def apply_config(self, path: str, value) -> None:
        """Apply one dotted-path config update to the live config tree
        (the emqx_config_handler runtime-update role; cluster-wide
        ordering is the ClusterNode's conf-txn journal).  Raises
        ValueError for any unknown path segment, and for a shared-sub
        strategy outside `shared.STRATEGIES` (switching to one starts
        the manager's round-robin and sticky state over)."""
        if path == "mqtt.shared_subscription_strategy":
            self.router.shared.strategy = value
        parts = path.split(".")
        obj = self.config
        for part in parts[:-1]:
            if isinstance(obj, dict):
                if part not in obj:
                    raise ValueError(f"unknown config key: {path}")
                obj = obj[part]
            else:
                if not hasattr(obj, part):
                    raise ValueError(f"unknown config key: {path}")
                obj = getattr(obj, part)
        leaf = parts[-1]
        if isinstance(obj, dict):
            obj[leaf] = value
        else:
            if not hasattr(obj, leaf):
                raise ValueError(f"unknown config key: {path}")
            old = getattr(obj, leaf)
            # coerce to the existing leaf's type (JSON loses int/float)
            if old is not None and not isinstance(value, type(old)):
                value = type(old)(value)
            setattr(obj, leaf, value)
        self.hooks.run("config.updated", path, value)

    # ----------------------------------------------------- sys info

    def info(self) -> Dict[str, object]:
        return {
            "connections": len(self.cm),
            "subscriptions": self._sub_count(),
            "retained": len(self.retainer),
            "metrics": self.metrics.all(),
        }


class PublishBatcher:
    """Micro-batching front of `Broker.publish_many`: concurrent
    producers enqueue, one drain task flushes every ``window``
    seconds or ``batch_max`` messages — the reference's per-publish
    route lookup amortized into one XLA step (SURVEY §7).

    Queuing is PER SOURCE with round-robin window assembly: one
    flooding connection fills its own lane and gets read-paused at
    its own watermark, while a light client's publish rides the very
    next window — the fairness the reference gets from per-connection
    processes + scheduler credits (emqx_connection's activation
    budget).  A single global FIFO let one flooder put seconds of
    queueing in front of every other client (r4
    broker_loaded_probe_p99 2.3 s)."""

    def __init__(
        self,
        broker: Broker,
        window: float = 0.001,
        batch_max: int = 4096,
        pipeline_windows: int = 4,
    ) -> None:
        self.broker = broker
        self.window = window
        self.batch_max = batch_max
        self.pipeline_windows = max(pipeline_windows, 1)
        # per-source lanes + round-robin order; source None = shared
        # lane (gateways, mgmt, wills)
        self._queues: Dict[object, deque] = {}
        self._rr: deque = deque()
        self._total = 0
        self._arrival = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self._dispatch_task: Optional[asyncio.Task] = None
        self._inflight_q: Optional[asyncio.Queue] = None
        # real count of messages popped from the lanes but not yet
        # dispatched (collector batch + pipelined windows).  Bounded:
        # the pipeline exists to hide the device round-trip (needs
        # ~throughput x RTT messages in flight, ~1.5k at 14k msg/s over
        # a 110 ms link), and anything beyond that is pure queueing
        # delay in front of every message — the loaded-probe p99.
        self._inflight_count = 0
        # cap = 4 windows of limit-size each: window collection uses
        # inflight_max // 4, so the pipeline keeps real depth (hiding
        # the device RTT) while total in-flight stays bounded — an
        # inflight_max equal to the window size would serialize the
        # round-trips at depth 1
        self.inflight_max = max(batch_max // 2, 512)
        self._inflight_drain = asyncio.Event()
        # a source's read loop pauses above ITS lane's high watermark,
        # or — when the TOTAL crosses the global bound — above its
        # FAIR SHARE of it, so a hundred moderate flooders throttle
        # while a light client's reads never pause.  Resumes below the
        # matching low marks.
        self.high_watermark = batch_max
        self.low_watermark = batch_max // 4
        self.global_high = batch_max * 2
        self._uncongested = Gate()
        self._uncongested.set()
        self._source_waits: Dict[object, Gate] = {}

    def depth(self) -> int:
        return self._total + self._inflight_msgs()

    def _inflight_msgs(self) -> int:
        return self._inflight_count

    def _lane_depth(self, source: object = None) -> int:
        q = self._queues.get(source)
        return len(q) if q is not None else 0

    def _fair_share(self) -> int:
        return max(32, self.global_high // max(len(self._queues), 1))

    def congested(self, source: object = None) -> bool:
        lane = self._lane_depth(source)
        if lane >= self.high_watermark or (
            self._total >= self.global_high
            and lane >= self._fair_share()
        ):
            # activate() is a cheap no-op while already active, and an
            # operator-cleared alarm re-raises while congestion persists
            self.broker.alarms.activate(
                "publish_queue_congested",
                details={"depth": self.depth()},
                message="publish micro-batch queue above high watermark",
            )
            ev = self._source_waits.get(source)
            if ev is None:
                ev = self._source_waits[source] = Gate()
            ev.clear()
            self._uncongested.clear()
            return True
        return False

    def _release_gate(self, source: object) -> Gate:
        ev = self._source_waits.get(source)
        return ev if ev is not None else self._uncongested

    async def wait_uncongested(self, source: object = None) -> None:
        await self._release_gate(source).wait()

    def when_uncongested(self, source: object, resume) -> None:
        """`wait_uncongested` for a reader with no coroutine to park
        (`Connection.data_received`): ``resume()`` runs inside the
        release that would wake the waiter."""
        self._release_gate(source).call(resume)

    def _maybe_release(self) -> None:
        """Dispatch-side: wake paused sources whose lanes drained to
        half their fair share (or whose lane pressure cleared)."""
        if self._source_waits:
            share = self._fair_share()
            for source, ev in list(self._source_waits.items()):
                lane = self._lane_depth(source)
                if not ev.is_set() and lane < self.high_watermark and (
                    self._total < self.global_high // 2
                    or lane <= share // 2
                ):
                    ev.set()
                if lane == 0:
                    del self._source_waits[source]
        if not self._uncongested.is_set() and (
            self._total <= self.low_watermark
        ):
            self._uncongested.set()
            self.broker.alarms.deactivate("publish_queue_congested")

    async def start(self) -> None:
        if self._task is None:
            self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        if self._task is not None:
            await cancel_and_wait(self._task)
            self._task = None

    def _enqueue(self, source: object, entry: tuple) -> None:
        q = self._queues.get(source)
        if q is None:
            q = self._queues[source] = deque()
            self._rr.append(source)
        q.append(entry)
        self._total += 1
        self._arrival.set()

    def _rr_pop(self) -> tuple:
        src = self._rr[0]
        q = self._queues[src]
        entry = q.popleft()
        self._total -= 1
        if q:
            self._rr.rotate(-1)  # next source's turn
        else:
            self._rr.popleft()
            del self._queues[src]
        return entry

    async def _landed(self) -> bool:
        """Give the loop the turns a readable socket needs to land its
        publishes in the lanes (`data_received` and its `handle_in`;
        where a coroutine reads, the reader's wake-up and the read
        task between them); True if any did.  For a window whose
        deadline ran out while the loop was elsewhere (a predecessor's
        dispatch holds it for a whole window's writes): no socket was
        read meanwhile, so what the publishers sent since is still
        unread.  Closing at once splits one burst over two windows,
        and a closed loop then repeats those sizes forever (each
        window's acks refill as one burst of its size).  Bounded, so
        a lone publish on a busy loop still closes its window."""
        for _ in range(3):
            await asyncio.sleep(0)
            if self._total:
                return True
        return False

    def _window_limit(self) -> int:
        """Max messages collected into one window: the pipeline-depth
        bound, capped by the olp ladder's L1 window shrink (smaller
        windows = shorter event-loop holds per dispatch while the
        broker is overloaded)."""
        limit = min(self.batch_max, max(self.inflight_max // 4, 256))
        cap = self.broker.olp.window_cap_now
        return min(limit, cap) if cap else limit

    def publish(
        self, msg: Message, source: object = None
    ) -> "asyncio.Future[int]":
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._enqueue(source, (msg, fut, source))
        return fut

    def publish_nowait(
        self, msg: Message, source: object = None
    ) -> None:
        """Fire-and-forget enqueue (QoS 0): no future is created, so a
        failed window can't leave unobserved exceptions behind."""
        self._enqueue(source, (msg, None, source))

    async def _run(self) -> None:
        """Collector: fills windows and launches their device match,
        keeping up to ``pipeline_windows`` kernels in flight so e2e
        throughput amortizes the host<->device round-trip instead of
        serializing on it; `_dispatch_loop` consumes results strictly
        in window order (session/publisher ordering)."""
        loop = asyncio.get_running_loop()
        clock = time.perf_counter
        inflight: asyncio.Queue = asyncio.Queue(
            maxsize=self.pipeline_windows
        )
        self._inflight_q = inflight
        self._dispatch_task = loop.create_task(
            self._dispatch_loop(inflight)
        )
        try:
            while True:
                while self._total == 0:
                    self._arrival.clear()
                    await self._arrival.wait()
                while self._inflight_count >= self.inflight_max:
                    self._inflight_drain.clear()
                    await self._inflight_drain.wait()
                limit = self._window_limit()
                # flight-recorder entry opens at collection start so
                # the accumulation wait shows up as its own stage
                rec = self.broker.profiler.begin(0, source="batcher")
                # ``collect``: the collector's own work between its
                # awaits (the pops and the batch list), a sub-stage of
                # ``batch_wait``.  The stretch running since the record
                # began ends at the lateness reading before an await
                # (or at the lap), one more reading after each await:
                # a flood's window, filled in one stretch, adds none
                collect = 0.0
                t_on = rec.t0 if rec is not None else 0.0
                batch = [self._rr_pop()]
                # adaptive window: with nothing else queued and the
                # pipeline idle, flush IMMEDIATELY — a lone publish on
                # a quiet broker pays ~0 window latency instead of the
                # full accumulation wait (VERDICT r4: attack p99)
                if not (
                    self._total == 0 and self._inflight_count == 0
                ):
                    deadline = clock() + self.window
                    while len(batch) < limit:
                        if self._total:
                            batch.append(self._rr_pop())
                            continue
                        now = clock()
                        late = now - deadline
                        collect += now - t_on
                        if late >= 0:
                            # a deadline the loop answered more than
                            # a window LATE has not been waited out:
                            # see `_landed`
                            if late > self.window:
                                landed = await self._landed()
                                now = clock()
                                if landed:
                                    t_on = now
                                    deadline = now + self.window
                                    continue
                            t_on = now
                            break
                        self._arrival.clear()
                        try:
                            await asyncio.wait_for(
                                self._arrival.wait(), -late
                            )
                        except asyncio.TimeoutError:
                            pass
                        t_on = clock()
                msgs = [m for m, _fut, _src in batch]
                if rec is not None:
                    rec.n_msgs = len(batch)
                    rec.sub("collect", collect + rec.lap("batch_wait") - t_on)
                self._inflight_count += len(batch)
                # throughput-mode hint for the engine's auto policy:
                # another window's worth already queued means windows
                # pipeline back-to-back and wall latency is hidden
                congested = self._total >= self.batch_max // 4
                try:
                    # hooks/retain/persist mutate broker state: loop
                    # thread only, and in window order (IO-backed
                    # publish hooks await off-loop inside)
                    live, results = (
                        await self.broker.publish_prepare_async(msgs)
                    )
                    if rec is not None:
                        rec.lap("prepare")
                    # submit ONLY (encode + async kernel dispatch, no
                    # wait): the device crunches this window while the
                    # collector fills and submits the next ones — the
                    # wait happens once, in _dispatch_loop's executor
                    # call, where it overlaps the other windows
                    match_fut = loop.run_in_executor(
                        None,
                        self.broker.publish_match_submit,
                        live,
                        congested,
                        rec,
                    )
                except Exception as exc:
                    self._inflight_count -= len(batch)
                    self._inflight_drain.set()
                    for _, fut, _src in batch:
                        if fut is not None and not fut.done():
                            fut.set_exception(exc)
                    log.exception(
                        "publish window of %d failed in prepare",
                        len(batch),
                    )
                    # failure paths must still wake paused read loops:
                    # if this was the LAST window, nothing else will
                    self._maybe_release()
                    continue
                # blocks when pipeline_windows are already in flight —
                # natural backpressure onto the collector
                await inflight.put((batch, live, results, match_fut, rec))
        finally:
            await cancel_and_wait(self._dispatch_task)
            self._dispatch_task = None
            # fail the futures of windows abandoned in flight: their
            # callers (mgmt publish, QoS ack callbacks) must not hang
            # past shutdown
            exc = ConnectionError("broker stopping")
            while not inflight.empty():
                batch, _live, _res, match_fut, _rec = inflight.get_nowait()
                match_fut.cancel()
                for _, fut, _src in batch:
                    if fut is not None and not fut.done():
                        fut.set_exception(exc)
            # entries still in the per-source lanes were never
            # collected: their futures must not hang past shutdown
            for q in self._queues.values():
                for _msg, fut, _src in q:
                    if fut is not None and not fut.done():
                        fut.set_exception(exc)
            self._queues.clear()
            self._rr.clear()
            self._total = 0
            self._inflight_q = None
            self._inflight_count = 0

    async def _dispatch_loop(self, inflight: asyncio.Queue) -> None:
        while True:
            batch, live, results, match_fut, rec = await inflight.get()
            counts = None
            try:
                try:
                    handle = await match_fut
                    matched, remote = await asyncio.get_running_loop(
                    ).run_in_executor(
                        None, self.broker.publish_match_finish, handle
                    )
                finally:
                    # leave the congestion ledger on every path
                    # (success, match failure, cancellation) or depth
                    # never drains below the low watermark
                    self._inflight_count -= len(batch)
                    self._inflight_drain.set()
                counts = self.broker.publish_dispatch(
                    live, matched, remote, results, rec
                )
                ext = self.broker.external
                if ext is not None and getattr(
                    ext, "raft_ds", None
                ) is not None:
                    # quorum barrier BEFORE resolving futures: a QoS1
                    # PUBACK then implies the persistent-session copy
                    # (local AND forwarded) is majority-replicated and
                    # survives any single node death — the reference's
                    # ack-after-ra-commit (emqx_ds_replication_layer
                    # store_batch).  Leadership churn mid-window DELAYS
                    # the acks (bounded retries) rather than failing
                    # the window: clients see slow acks during a
                    # failover, not disconnects.
                    for attempt in range(10):
                        try:
                            await ext.quorum_barrier()
                            break
                        except Exception:
                            if attempt == 9:
                                raise
                            await asyncio.sleep(0.2)
                dur = self.broker.durable
                if (
                    dur is not None
                    and dur.fsync_mode == "always"
                    and dur.gate.dirty
                ):
                    # group-commit barrier: a QoS>=1 PUBACK to a
                    # publisher whose message the persistence gate
                    # captured parks here until the covering
                    # dslog_sync lands — ONE fsync amortized per
                    # dispatch window, concurrent windows coalesced by
                    # the gate's worker.  A sync fault keeps the acks
                    # parked and retries (never an un-durable ack);
                    # with nothing unsynced this is one integer
                    # compare, so non-captured traffic pays nothing.
                    await dur.wait_durable()
            except asyncio.CancelledError:
                raise
            except Exception as exc:  # resolve futures either way
                log.exception("publish window of %d failed", len(batch))
                for _, fut, _src in batch:
                    if fut is not None and not fut.done():
                        fut.set_exception(exc)
                try:
                    # failure must still wake paused read loops: a
                    # failed FINAL window would otherwise leave them
                    # in wait_uncongested() forever
                    self._maybe_release()
                except Exception:
                    log.exception("congestion release failed")
                continue
            # the tail is protected too: an exception here (e.g. the
            # alarm deactivation re-entering publish) must not kill
            # this task — a dead dispatcher fills the inflight queue
            # and wedges ALL publishing silently
            try:
                # cork each distinct publisher channel before resolving
                # its futures: set_result schedules the PUBACK/PUBREC
                # callbacks via call_soon, and the uncork scheduled
                # AFTER them (FIFO) flushes a window's worth of acks as
                # one transport.write per connection.  The turn clock's
                # ``acks`` is this pass and, in the next iteration, the
                # callbacks and the uncork between two marks queued
                # around them (what runs in between is not the acks')
                corked: List = []
                seen: Set[int] = set()
                soon = asyncio.get_running_loop().call_soon
                lc = self.broker.profiler.loop
                if lc is not None:
                    lc.mark(lc.ACKS)
                    soon(lc.mark, lc.ACKS)
                try:
                    for _m, fut, src in batch:
                        if fut is None or src is None or id(src) in seen:
                            continue
                        cork = getattr(src, "cork", None)
                        if cork is None:
                            continue
                        seen.add(id(src))
                        cork()
                        corked.append(src)
                    for (_, fut, _src), n in zip(batch, counts):
                        if fut is not None and not fut.done():
                            fut.set_result(n)
                finally:
                    if corked:
                        soon(self._uncork_all, corked, self.broker.sender)
                    if lc is not None:
                        soon(lc.mark, lc.TAIL)  # behind the uncork
                        lc.mark(lc.TAIL)  # the pass ends here
                self._maybe_release()
            except asyncio.CancelledError:
                raise
            except Exception:
                log.exception("publish window post-dispatch failed")

    @staticmethod
    def _uncork_all(channels: List, sender=None) -> None:
        # (one flush scope: the acks of every publisher of the window
        # reach the sender thread in one hand-over)
        if sender is not None:
            sender.begin()
        try:
            for ch in channels:
                try:
                    ch.uncork()
                except Exception:
                    log.exception("ack uncork failed")
        finally:
            if sender is not None:
                sender.end()
