"""Typed broker configuration with runtime update handlers.

A deliberately small analogue of the reference's HOCON config system
(`emqx_config` persistent-term cache + per-path update handlers,
/root/reference/apps/emqx/src/emqx_config.erl, emqx_config_handler.erl):
typed dataclasses with defaults, dotted-path get/update, and validating
change listeners.  Zone overrides collapse to per-listener overrides.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple


@dataclass
class MqttConfig:
    max_packet_size: int = 1024 * 1024
    max_clientid_len: int = 65535
    max_topic_levels: int = 128
    # NODE-aggregate ingress limits shared by every connection of
    # every listener (the hierarchical limiter's zone level); 0 = off
    zone_messages_rate: float = 0.0
    zone_bytes_rate: float = 0.0
    max_qos_allowed: int = 2
    max_topic_alias: int = 65535
    retain_available: bool = True
    wildcard_subscription: bool = True
    shared_subscription: bool = True
    # how a $share group picks its member for a message
    # (emqx_shared_sub's strategies, `broker.shared.STRATEGIES`);
    # EMQX 5's shipped default
    shared_subscription_strategy: str = "round_robin"
    exclusive_subscription: bool = False
    max_inflight: int = 32
    max_awaiting_rel: int = 100
    await_rel_timeout: float = 300.0
    max_mqueue_len: int = 1000
    mqueue_priorities: Dict[str, int] = field(default_factory=dict)
    mqueue_default_priority: str = "lowest"  # lowest | highest
    mqueue_store_qos0: bool = True
    upgrade_qos: bool = False
    keepalive_multiplier: float = 1.5
    session_expiry_interval: float = 7200.0
    server_keepalive: Optional[int] = None
    retry_interval: float = 30.0
    idle_timeout: float = 15.0
    # per-connection OUTBOUND high watermark (bytes buffered in the
    # transport toward one subscriber): past it, QoS0 deliveries for
    # that connection drop (``delivery.dropped.out_buffer``) and
    # QoS>0 falls back to the mqueue path, so a stalled subscriber's
    # corked wire blobs stay bounded.  0 = disabled.
    outbound_high_watermark: int = 4 * 1024 * 1024


@dataclass
class ListenerConfig:
    name: str = "tcp_default"
    type: str = "tcp"  # tcp | ssl | ws | wss
    bind: str = "0.0.0.0"
    port: int = 1883
    max_connections: int = 1024000
    mountpoint: Optional[str] = None
    enable: bool = True
    # SO_REUSEPORT accept sharding: multiple worker PROCESSES bind the
    # same port and the kernel spreads accepted connections across
    # them (the multi-core launcher's esockd-acceptor-pool analogue)
    reuse_port: bool = False
    # TLS options (ssl/wss listeners; emqx_tls_lib's core knobs)
    certfile: Optional[str] = None
    keyfile: Optional[str] = None
    cacertfile: Optional[str] = None
    verify: bool = False  # require + verify client certificates
    # PEM CRL checked against client leaf certs (emqx_crl_cache);
    # the file is watched and hot-reloaded on change
    crlfile: Optional[str] = None
    # per-connection rate limits (emqx_limiter); 0 = unlimited
    messages_rate: float = 0.0  # PUBLISH packets per second
    bytes_rate: float = 0.0  # inbound bytes per second
    # listener-AGGREGATE limits shared by all its connections
    # (the hierarchical limiter's listener level); 0 = unlimited
    max_messages_rate: float = 0.0
    max_bytes_rate: float = 0.0


@dataclass
class AuthConfig:
    allow_anonymous: bool = True
    authz_default: str = "allow"  # allow | deny
    deny_action: str = "ignore"  # ignore | disconnect


@dataclass
class RetainerConfig:
    enable: bool = True
    max_retained_messages: int = 0  # 0 = unlimited
    max_payload_size: int = 1024 * 1024
    msg_expiry_interval: float = 0.0  # 0 = never
    deliver_rate: int = 1000  # per batch flush


@dataclass
class BrokerEngineConfig:
    """Knobs for the TPU match engine + batch dispatcher."""

    use_device: Optional[bool] = None  # None = auto
    max_levels: int = 16
    f_width: int = 16
    m_cap: int = 128
    rebuild_threshold: int = 4096
    background_rebuild: bool = True  # fold deltas off-thread (no stall)
    batch_publish: bool = True  # route live publishes via PublishBatcher
    batch_window_ms: float = 1.0  # micro-batch accumulation window
    batch_max: int = 4096
    # windows matched concurrently on the device: the collector keeps
    # filling window N+1..N+k while window N's kernel runs, so e2e
    # throughput stops serializing on the host<->device round-trip
    # (dispatch stays strictly in window order)
    pipeline_windows: int = 4
    # rule-engine WHERE predicates evaluate as one rules x window
    # boolean matrix over shared column planes (False pins the
    # per-rule interpreter walk; EMQX_TPU_NO_RULES_MATRIX=1 is the
    # env-level kill switch)
    rules_matrix: bool = True


@dataclass
class SysConfig:
    enable: bool = True
    interval: float = 60.0  # $SYS heartbeat publish interval


@dataclass
class FlappingConfig:
    """Flapping-client detection (emqx_flapping defaults)."""

    enable: bool = True
    max_count: int = 15
    window: float = 60.0
    ban_time: float = 300.0


@dataclass
class SlowSubsConfig:
    """Slow-subscriber top-K table (emqx_slow_subs): deliveries slower
    than ``threshold_ms`` enter a top-K board; entries expire after
    ``expire_interval`` seconds (the reference's expire_interval) so a
    one-off stall from last week stops shadowing today's slowest."""

    enable: bool = True
    threshold_ms: float = 500.0
    top_k: int = 10
    expire_interval: float = 300.0


@dataclass
class ProfilerConfig:
    """Hot-path window profiler (observability.py): stage-latency
    histograms + a flight-recorder ring of the last ``ring_size``
    dispatch windows, always on by default (near-free: ~2
    perf_counter reads per stage, one lock per window)."""

    enable: bool = True
    ring_size: int = 256
    events_cap: int = 256


@dataclass
class FlightConfig:
    """Flight recorder (flightrec.py): always-on black-box event ring
    in every process, frozen + dumped atomically on anomaly triggers
    and correlated across workers / match service by one trigger id.
    Recording is O(1) and allocation-free (brokerlint OBS602), so the
    default is armed."""

    enable: bool = True
    # bounded preallocated event ring (numeric events)
    ring_size: int = 4096
    # bounded annotation ring (cold-path notes with payloads)
    notes_cap: int = 512
    # shared directory dumps are persisted into ("" = in-memory only;
    # the multicore launcher points every worker + the service at one
    # directory so correlated dumps land together)
    dump_dir: str = ""
    # in-memory dumps kept per process
    max_dumps: int = 16
    # trigger debounce: a second trigger inside this window is counted
    # and suppressed (a p99 breach storm yields ONE dump, not N)
    min_dump_interval: float = 30.0
    # event-loop-lag watchdog threshold (0 disables the thread)
    watchdog_stall_ms: float = 5000.0
    # per-profiler-stage p99 SLO triggers, e.g. {"dispatch": 50.0}
    # (ms, checked over 1 Hz delta windows); empty = no SLO triggers
    slo_p99_ms: Dict[str, float] = field(default_factory=dict)
    # note fsync calls slower than this (ms; 0 disables)
    fsync_stall_ms: float = 500.0
    # record GC pauses longer than this (ms; 0 disables the observer)
    gc_stall_ms: float = 100.0
    # olp level that triggers a dump when entered from below (and
    # 0 disables the olp trigger entirely)
    trigger_olp_level: int = 2
    trigger_on_breaker: bool = True
    trigger_on_restart: bool = True
    trigger_on_fault: bool = True


@dataclass
class TracingConfig:
    """Message-lifecycle tracing (tracecontext.py): head-sampled trace
    contexts carried through the batched hot path and across cluster /
    multicore boundaries.  ``sample_rate`` is the head-sampling
    probability; ``topic_filters`` always-sample matching topics
    (debug a specific flow at rate 0); ``seed`` makes sampling
    decisions reproducible (chaos runs); ``store_max`` bounds the
    in-process trace store (whole-trace FIFO eviction)."""

    enable: bool = False
    sample_rate: float = 0.0
    topic_filters: List[str] = field(default_factory=list)
    store_max: int = 512
    seed: Optional[int] = None


@dataclass
class OlpConfig:
    """Coordinated overload protection (olp.py): one broker-wide load
    level 0-3 sampled from the event loop, batcher, mqueues, profiler
    p99s and sysmon, driving a degradation ladder (park resume
    admissions / defer retained catch-up + rebuilds / shrink windows
    at L1; shed QoS0 deliveries + clamp listener buckets + budget
    CONNECTs at L2; drop QoS0 at ingress + force-close the slowest
    subscribers at L3).  Shedding is QoS0-only — zero QoS>=1 loss for
    admitted traffic — and every shed unit is counted and alarmed.

    Each signal carries an (L1, L2, L3) enter-threshold triple; exit
    is enter * ``exit_factor`` and the ladder steps down one level at
    a time after ``min_hold`` seconds (hysteresis).  Disabled by
    default, like the reference's ``overload_protection``."""

    enable: bool = False
    sample_interval: float = 1.0
    min_hold: float = 5.0
    exit_factor: float = 0.8
    # signal enter thresholds, one per level (non-decreasing)
    loop_lag_ms: List[float] = field(
        default_factory=lambda: [100.0, 500.0, 2000.0]
    )
    # PublishBatcher depth as a fraction of its global high watermark
    batcher_fill: List[float] = field(
        default_factory=lambda: [0.75, 1.5, 3.0]
    )
    # aggregate mqueue backlog (messages) across all sessions
    mqueue_backlog: List[float] = field(
        default_factory=lambda: [50_000.0, 200_000.0, 1_000_000.0]
    )
    # EWMA of the profiler's interval publish->delivery p99 (ms)
    e2e_p99_ms: List[float] = field(
        default_factory=lambda: [500.0, 2000.0, 8000.0]
    )
    # sysmon watermarks: system memory used fraction, process RSS
    # fraction of total, 1-min loadavg per core
    sysmem: List[float] = field(
        default_factory=lambda: [0.90, 0.95, 0.98]
    )
    procmem: List[float] = field(
        default_factory=lambda: [0.40, 0.55, 0.70]
    )
    cpu: List[float] = field(
        default_factory=lambda: [2.0, 4.0, 8.0]
    )
    # L1: max dispatch-window size while the ladder is raised
    window_cap: int = 1024
    # L2: listener/zone shared-bucket rate factor while clamped
    limiter_clamp: float = 0.5
    # L2: CONNECTs admitted per second (over budget -> server-busy)
    connect_budget: float = 100.0
    # L1: deferred retained-catch-up queue bound + flush pacing
    # (MESSAGES per recovery tick; a huge filter chunks across ticks)
    retained_defer_cap: int = 10_000
    retained_flush_per_tick: int = 256
    # L3: slow-subscriber force-close batch + re-check cadence
    slow_kill_max: int = 10
    slow_kill_interval: float = 10.0
    # $SYS alarm flap damping (AlarmRegistry): min seconds between
    # re-raise publishes, and the deactivate hysteresis hold
    alarm_min_reraise: float = 10.0
    alarm_hold: float = 5.0


@dataclass
class ApiConfig:
    """Management REST + Prometheus endpoint (emqx_management slice).

    Authentication is always on (emqx_mgmt_auth): a default admin is
    bootstrapped on first start from default_username/default_password
    (the reference ships admin/public the same way); set
    ``default_password`` to None to disable bootstrap entirely (then
    seed users via MgmtAuth directly)."""

    enable: bool = False
    bind: str = "127.0.0.1"
    port: int = 18083
    data_dir: str = "data/mgmt"
    default_username: str = "admin"
    default_password: Optional[str] = "public"
    token_ttl: float = 3600.0
    # whether /metrics (Prometheus scrape) also requires credentials;
    # the reference leaves the scrape endpoint open by default
    prometheus_auth: bool = False


@dataclass
class FtConfig:
    """MQTT file transfer (emqx_ft)."""

    enable: bool = False
    storage_dir: str = "data/ft"
    max_file_size: int = 256 * 1024 * 1024
    transfer_ttl: float = 3600.0
    # optional S3 export of assembled files (emqx_ft's s3 storage
    # backend): {"endpoint", "bucket", "access_key", "secret_key",
    # "region"} — empty dict disables
    s3: Dict[str, str] = field(default_factory=dict)


@dataclass
class ResumeConfig:
    """Resume admission control (the mass-reconnect scheduler): a
    bounded number of sessions replay their durable backlog
    concurrently, each scheduler round reads at most
    ``replay_byte_budget`` payload bytes before yielding the event
    loop back to live traffic, and reconnects beyond
    ``max_concurrent`` park in a FIFO (CONNACK-then-drain: the client
    is connected and live immediately, its backlog streams in when a
    replay slot frees).  Past ``park_queue_cap`` the broker answers
    CONNACK server-busy so the client backs off — a 100k-session
    storm degrades to bounded latency and bounded memory instead of
    event-loop starvation."""

    # sessions replaying concurrently (active replay slots)
    max_concurrent: int = 64
    # payload bytes read per scheduler round before yielding
    replay_byte_budget: int = 4 * 1024 * 1024
    # parked (admitted-but-waiting) sessions beyond the active slots;
    # past this, reconnects get CONNACK server-busy (client backoff)
    park_queue_cap: int = 4096
    # messages pulled per session per round (cursor-batch granular)
    chunk_msgs: int = 1024
    # windowed replay: batch DS reads across resuming sessions and
    # dispatch backlogs through the window pipeline (decide columns +
    # encode-once + native splice).  False pins the scalar per-session
    # mqueue path — the property-tested referee.
    windowed: bool = True
    # multicore resume sharding: this worker admits resume for client
    # ids with ``crc32(client_id) % shard_count == shard_index`` and
    # parks/redirects the rest, so a mass reconnect spreads its replay
    # floor over the pool instead of stampeding one worker.
    # (1, 0) = shard-all (the single-process default).
    shard_index: int = 0
    shard_count: int = 1


@dataclass
class DurableConfig:
    """Durable storage + persistent sessions (emqx_durable_storage)."""

    enable: bool = False
    data_dir: str = "data/ds"
    # storage layout: "lts" (learned topic structure + bitmask keys —
    # wildcard replay scans only overlapping structures) or "hash"
    # (2-level topic-prefix hash shards); pinned per data directory
    layout: str = "lts"
    n_streams: int = 16  # hash layout only
    # physical store shards: each shard is an independent segment log
    # + fsync barrier + metadata journal (append throughput scales
    # with shards in `always` mode; restart recovery parallelizes
    # naturally).  Pinned per data directory like the layout — it
    # decides WHERE records live.
    n_shards: int = 1
    store_qos0: bool = False
    # durability mode — what "acked" means for a captured QoS>=1
    # publish (the PR 15 group-commit contract):
    #   never    no fsync: a power cut may take everything since the
    #            OS last flushed (process crashes still lose nothing
    #            the log absorbed — appends are write()-complete)
    #   interval periodic group flush off the broker tick every
    #            `fsync_interval` s: a power cut loses at most that
    #            window (olp L1 stretches the interval 2x, never
    #            skips a flush a parked ack waits on)
    #   always   group-commit: the PUBACK parks until the covering
    #            dslog_sync lands — ONE fsync amortized per dispatch
    #            window ("acked means durable", crash-tested by
    #            tools/crashsim)
    fsync: str = "interval"
    fsync_interval: float = 5.0
    sync_interval: float = 5.0  # metadata checkpoint + gc cadence
    retention_hours: float = 168.0  # segment GC horizon (7 days)
    # mass-reconnect admission control + windowed replay
    resume: ResumeConfig = field(default_factory=ResumeConfig)


@dataclass
class OtelConfig:
    """OpenTelemetry export (emqx_opentelemetry): OTLP/JSON over HTTP."""

    enable: bool = False
    endpoint: str = "http://127.0.0.1:4318"
    interval: float = 10.0
    export_logs: bool = False
    # distributed trace spans (emqx_otel_trace): publish/deliver spans
    # with W3C traceparent propagation through MQTT 5 user properties
    export_traces: bool = False
    trace_sample_ratio: float = 1.0


@dataclass
class LogConfig:
    """Structured logging (emqx_logger + emqx_log_throttler)."""

    format: str = "text"  # text | json
    level: str = "info"
    throttle_window_s: float = 0.0  # 0 disables throttling


@dataclass
class MulticoreConfig:
    """Multicore topology (the layer-1/layer-2 split): this worker's
    half of the N-workers x one-match-service arrangement.  Populated
    by `broker.multicore.worker_configs`; all-defaults means a
    single-process broker (no service, engine owns its own device
    policy)."""

    # pool size as the SUPERVISOR sees it (workers carry it for
    # introspection; 0 = not part of a pool)
    n_workers: int = 0
    # unix control socket of the shared match service; "" disables the
    # service client entirely (workers match in-process)
    service_socket: str = ""
    # this worker's index in the pool (= resume shard index)
    worker_id: int = 0
    # shared-memory window ring geometry (per worker): slots bound the
    # in-flight windows, slot_bytes bound one window's payload
    ring_slots: int = 8
    ring_slot_bytes: int = 1 << 18
    # ship decide windows to the service only at/above this fanout and
    # only when the service owns a device (small windows aren't worth
    # the round-trip; the local numpy twin is bit-identical)
    decide_min: int = 64
    # per-window service RPC deadline before the in-process fallback
    rpc_timeout: float = 2.0


@dataclass
class BrokerConfig:
    mqtt: MqttConfig = field(default_factory=MqttConfig)
    listeners: List[ListenerConfig] = field(
        default_factory=lambda: [ListenerConfig()]
    )
    auth: AuthConfig = field(default_factory=AuthConfig)
    retainer: RetainerConfig = field(default_factory=RetainerConfig)
    engine: BrokerEngineConfig = field(default_factory=BrokerEngineConfig)
    sys: SysConfig = field(default_factory=SysConfig)
    api: ApiConfig = field(default_factory=ApiConfig)
    flapping: FlappingConfig = field(default_factory=FlappingConfig)
    slow_subs: SlowSubsConfig = field(default_factory=SlowSubsConfig)
    olp: OlpConfig = field(default_factory=OlpConfig)
    profiler: ProfilerConfig = field(default_factory=ProfilerConfig)
    flight: FlightConfig = field(default_factory=FlightConfig)
    tracing: TracingConfig = field(default_factory=TracingConfig)
    # server-side auto-subscribe on connect (emqx_auto_subscribe):
    # entries {"topic": ..., "qos": 0}; %c/%u placeholders supported
    auto_subscribe: List[Dict[str, Any]] = field(default_factory=list)
    # protocol gateways (emqx_gateway): {"type": "stomp", "bind", "port"}
    gateways: List[Dict[str, Any]] = field(default_factory=list)
    # plugin names loaded at boot, in order (emqx_plugins)
    plugins: List[str] = field(default_factory=list)
    plugin_dir: str = "plugins"
    ft: FtConfig = field(default_factory=FtConfig)
    # GCP IoT-Core compat device registry (emqx_gcp_device): devices
    # keep their projects/.../devices/D clientids and JWT-per-connect
    # credentials after migrating off Google IoT Core
    gcp_device_enable: bool = False
    gcp_device_file: str = "data/gcp_devices.json"
    # opt-in anonymous usage telemetry (emqx_telemetry); off by default
    telemetry_enable: bool = False
    telemetry_url: str = ""
    telemetry_interval: float = 7 * 24 * 3600.0
    durable: DurableConfig = field(default_factory=DurableConfig)
    multicore: MulticoreConfig = field(default_factory=MulticoreConfig)
    node_name: str = "emqx_tpu@127.0.0.1"
    # cluster linking (emqx_cluster_link): this cluster's name plus
    # links [{"name", "host", "port", "topics": [...]}, ...]
    cluster_name: str = "emqx_tpu"
    cluster_links: List[Dict[str, Any]] = field(default_factory=list)
    # exhook CLIENT servers this broker calls out to (emqx_exhook):
    # [{"name", "url", "timeout", "failure_action": "deny"|"ignore"}]
    exhooks: List[Dict[str, Any]] = field(default_factory=list)
    # cluster membership (the ekka static-seeds shape): when enabled,
    # this node joins peers over the inter-node transport; the
    # multi-core launcher uses the same mechanism to cluster its
    # worker processes on loopback
    cluster: Dict[str, Any] = field(default_factory=dict)
    # {"enable": bool, "bind": str, "port": int,
    #  "seeds": [[name, host, port], ...],
    #  "consensus": "lww"|"raft", "raft_data_dir": str,
    #  "transport_mode": "tcp"|"quic"|"auto" (inter-node link layer:
    #   quic = in-repo QUIC peer transport, auto = QUIC with graceful
    #   per-peer TCP degradation + re-probe),
    #  "quic_psk": str (shared cluster secret for the QUIC PSK
    #   integrity profile),
    #  "fwd_inflight_max": int (at-least-once forward replay buffer,
    #   frames per peer), "fwd_ack_timeout": float (seconds before a
    #   frame retransmits)}
    # data-integration sinks started at boot, addressable from rule
    # SinkActions by id (the emqx_bridge config role):
    # [{"id", "type": "http"|"kafka", ...type-specific fields}]
    # kafka: {"bootstrap": [[host, port], ...], "topic", "acks"}
    sinks: List[Dict[str, Any]] = field(default_factory=list)
    otel: OtelConfig = field(default_factory=OtelConfig)
    log: LogConfig = field(default_factory=LogConfig)


class ConfigHandler:
    """Dotted-path get/update with validating listeners
    (`emqx_config_handler` analogue)."""

    def __init__(self, cfg: Optional[BrokerConfig] = None) -> None:
        self.root = cfg or BrokerConfig()
        self._handlers: Dict[str, List[Callable[[Any, Any], None]]] = {}

    def get(self, path: str) -> Any:
        obj: Any = self.root
        for part in path.split("."):
            if isinstance(obj, dict):
                obj = obj[part]
            else:
                obj = getattr(obj, part)
        return obj

    def update(self, path: str, value: Any) -> Any:
        """Set `path` to `value`, running registered handlers first;
        a handler raising aborts the update (validation)."""
        old = self.get(path)
        for prefix, fns in self._handlers.items():
            if path == prefix or path.startswith(prefix + "."):
                for fn in fns:
                    fn(old, value)
        parts = path.split(".")
        obj: Any = self.root
        for part in parts[:-1]:
            obj = obj[part] if isinstance(obj, dict) else getattr(obj, part)
        if isinstance(obj, dict):
            obj[parts[-1]] = value
        else:
            setattr(obj, parts[-1], value)
        return value

    def add_handler(
        self, path: str, fn: Callable[[Any, Any], None]
    ) -> None:
        self._handlers.setdefault(path, []).append(fn)

    # ---------------------------------------------------------- io

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self.root)

    @classmethod
    def from_dict(cls, data: Dict[str, Any]) -> "ConfigHandler":
        root = BrokerConfig()
        _merge_dataclass(root, data)
        return cls(root)

    @classmethod
    def load(cls, path: str) -> "ConfigHandler":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def _merge_dataclass(obj: Any, data: Dict[str, Any]) -> None:
    for key, val in data.items():
        if not hasattr(obj, key):
            raise ValueError(f"unknown config key: {key}")
        cur = getattr(obj, key)
        if dataclasses.is_dataclass(cur) and isinstance(val, dict):
            _merge_dataclass(cur, val)
        elif key == "listeners" and isinstance(val, list):
            setattr(obj, key, [ListenerConfig(**item) for item in val])
        else:
            setattr(obj, key, val)


# -------------------------------------------------- env-var overrides

ENV_PREFIX = "EMQX_TPU_"

# a runtime switch that shares the prefix but is NOT a config path
# (read directly by broker/broker.py).  Without this carve-out a
# worker subprocess booted with it in its environment died with
# "unknown config path".
ENV_RESERVED = {"EMQX_TPU_NO_DECIDE"}


def apply_env_overrides(
    cfg: BrokerConfig, environ: Optional[Dict[str, str]] = None
) -> List[Tuple[str, Any]]:
    """The reference's ``EMQX_<PATH>__<KEY>`` environment overrides
    (/root/reference/bin/emqx env handling): every variable
    ``EMQX_TPU_A__B__C=value`` sets config path ``a.b.c`` BEFORE the
    broker boots.  Values parse as JSON when they can (numbers, bools,
    lists, objects) and fall back to plain strings; the target leaf
    must exist — unknown paths are a hard error, exactly like an
    unknown key in a config file.  Returns the applied (path, value)
    list for boot logging."""
    import os

    environ = dict(os.environ) if environ is None else environ
    applied: List[Tuple[str, Any]] = []
    for name in sorted(environ):
        if not name.startswith(ENV_PREFIX) or name in ENV_RESERVED:
            continue
        path = name[len(ENV_PREFIX):].lower().replace("__", ".")
        raw = environ[name]
        try:
            value: Any = json.loads(raw)
        except (json.JSONDecodeError, ValueError):
            value = raw
        parts = path.split(".")
        obj: Any = cfg
        for part in parts[:-1]:
            if isinstance(obj, dict):
                if part not in obj:
                    raise ValueError(f"unknown config path in {name}")
                obj = obj[part]
            else:
                if not hasattr(obj, part):
                    raise ValueError(f"unknown config path in {name}")
                obj = getattr(obj, part)
        leaf = parts[-1]
        if isinstance(obj, dict):
            obj[leaf] = value
        else:
            if not hasattr(obj, leaf):
                raise ValueError(f"unknown config path in {name}")
            old = getattr(obj, leaf)
            if old is not None and value is not None \
                    and not isinstance(value, type(old)) \
                    and not isinstance(old, (dict, list)):
                try:
                    value = type(old)(value)
                except (TypeError, ValueError) as exc:
                    raise ValueError(
                        f"{name}: cannot coerce {raw!r} to "
                        f"{type(old).__name__}"
                    ) from exc
            setattr(obj, leaf, value)
        applied.append((path, value))
    return applied


# ---------------------------------------------------- boot-time check

def check_config(cfg: BrokerConfig) -> List[str]:
    """Boot-time validation (the `bin/emqx check_config` role): returns
    a list of problems, empty = boots cleanly.  Checks the enum-valued
    and cross-field constraints a typo would silently break."""
    problems: List[str] = []

    def bad(msg: str) -> None:
        problems.append(msg)

    for i, lst in enumerate(cfg.listeners):
        if lst.type not in ("tcp", "ssl", "ws", "wss", "quic"):
            bad(f"listeners[{i}].type: unknown type {lst.type!r}")
        if lst.type in ("ssl", "wss", "quic") and not (
            getattr(lst, "certfile", None)
            and getattr(lst, "keyfile", None)
        ):
            bad(f"listeners[{i}]: {lst.type} requires certfile+keyfile")
        if not (0 <= int(lst.port) <= 65535):
            bad(f"listeners[{i}].port: {lst.port} out of range")
    if cfg.mqtt.max_qos_allowed not in (0, 1, 2):
        bad(f"mqtt.max_qos_allowed: {cfg.mqtt.max_qos_allowed}")
    if cfg.mqtt.mqueue_default_priority not in ("lowest", "highest"):
        bad("mqtt.mqueue_default_priority must be lowest|highest")
    from .broker.shared import STRATEGIES
    if cfg.mqtt.shared_subscription_strategy not in STRATEGIES:
        bad(
            "mqtt.shared_subscription_strategy: "
            f"{cfg.mqtt.shared_subscription_strategy!r} "
            f"({'|'.join(STRATEGIES)})"
        )
    if cfg.durable.layout not in ("lts", "hash"):
        bad(f"durable.layout: {cfg.durable.layout!r} (lts|hash)")
    if not 1 <= int(cfg.durable.n_shards) <= 64:
        bad("durable.n_shards must be in [1, 64]")
    if cfg.durable.fsync not in ("never", "interval", "always"):
        bad(
            f"durable.fsync: {cfg.durable.fsync!r} "
            "(never|interval|always)"
        )
    if not 0.05 <= float(cfg.durable.fsync_interval) <= 3600.0:
        bad("durable.fsync_interval must be in [0.05, 3600]")
    res = cfg.durable.resume
    if int(res.max_concurrent) < 1:
        bad("durable.resume.max_concurrent must be >= 1")
    if int(res.replay_byte_budget) < 4096:
        bad("durable.resume.replay_byte_budget must be >= 4096")
    if int(res.park_queue_cap) < 0:
        bad("durable.resume.park_queue_cap must be >= 0")
    if int(res.chunk_msgs) < 1:
        bad("durable.resume.chunk_msgs must be >= 1")
    if cfg.cluster.get("enable"):
        if cfg.cluster.get("consensus", "raft") not in ("raft", "lww"):
            bad("cluster.consensus must be raft|lww")
        if cfg.cluster.get("transport_mode", "tcp") not in (
            "tcp", "quic", "auto"
        ):
            bad("cluster.transport_mode must be tcp|quic|auto")
        if not 1 <= int(cfg.cluster.get("fwd_inflight_max", 512)) \
                <= 32768:
            # upper bound keeps the sender's outstanding seq span well
            # inside the receiver's 64k dedup window
            bad("cluster.fwd_inflight_max must be in [1, 32768]")
        if float(cfg.cluster.get("fwd_ack_timeout", 1.0)) <= 0:
            bad("cluster.fwd_ack_timeout must be > 0")
        for j, s in enumerate(cfg.cluster.get("seeds", ())):
            if len(s) != 3:
                bad(f"cluster.seeds[{j}]: expected [name, host, port]")
    for j, sink in enumerate(cfg.sinks):
        if "id" not in sink:
            bad(f"sinks[{j}]: missing id")
        stype = sink.get("type", "http")
        if stype == "kafka" and not (
            sink.get("bootstrap") and sink.get("topic")
        ):
            bad(f"sinks[{j}]: kafka sink needs bootstrap + topic")
        if stype == "http" and not sink.get("url"):
            bad(f"sinks[{j}]: http sink needs url")
        if stype not in ("http", "kafka"):
            bad(f"sinks[{j}]: unknown type {stype!r}")
    if not 0 <= float(cfg.otel.trace_sample_ratio) <= 1:
        bad("otel.trace_sample_ratio must be in [0, 1]")
    if not 0 <= float(cfg.tracing.sample_rate) <= 1:
        bad("tracing.sample_rate must be in [0, 1]")
    if int(cfg.tracing.store_max) < 1:
        bad("tracing.store_max must be >= 1")
    if cfg.engine.use_device not in (None, True, False):
        bad("engine.use_device must be null|true|false")
    olp = cfg.olp
    if float(olp.sample_interval) <= 0:
        bad("olp.sample_interval must be > 0")
    if float(olp.min_hold) < 0:
        bad("olp.min_hold must be >= 0")
    if not 0 < float(olp.exit_factor) <= 1:
        bad("olp.exit_factor must be in (0, 1]")
    for name in ("loop_lag_ms", "batcher_fill", "mqueue_backlog",
                 "e2e_p99_ms", "sysmem", "procmem", "cpu"):
        t = list(getattr(olp, name))
        if len(t) != 3:
            bad(f"olp.{name} must be an [L1, L2, L3] triple")
            continue
        if any(float(v) <= 0 for v in t):
            bad(f"olp.{name} thresholds must be > 0")
        if not (t[0] <= t[1] <= t[2]):
            bad(f"olp.{name} thresholds must be non-decreasing")
    if int(olp.window_cap) < 1:
        bad("olp.window_cap must be >= 1")
    if not 0 < float(olp.limiter_clamp) <= 1:
        bad("olp.limiter_clamp must be in (0, 1]")
    if float(olp.connect_budget) < 0:
        bad("olp.connect_budget must be >= 0")
    if int(olp.retained_defer_cap) < 0:
        bad("olp.retained_defer_cap must be >= 0")
    if int(olp.retained_flush_per_tick) < 1:
        bad("olp.retained_flush_per_tick must be >= 1")
    if int(olp.slow_kill_max) < 0:
        bad("olp.slow_kill_max must be >= 0")
    if float(olp.slow_kill_interval) <= 0:
        bad("olp.slow_kill_interval must be > 0")
    if float(olp.alarm_min_reraise) < 0 or float(olp.alarm_hold) < 0:
        bad("olp alarm damping intervals must be >= 0")
    if int(cfg.mqtt.outbound_high_watermark) < 0:
        bad("mqtt.outbound_high_watermark must be >= 0")
    fl = cfg.flight
    if int(fl.ring_size) < 64:
        bad("flight.ring_size must be >= 64")
    if int(fl.notes_cap) < 16:
        bad("flight.notes_cap must be >= 16")
    if int(fl.max_dumps) < 1:
        bad("flight.max_dumps must be >= 1")
    if float(fl.min_dump_interval) < 0:
        bad("flight.min_dump_interval must be >= 0")
    if float(fl.watchdog_stall_ms) < 0:
        bad("flight.watchdog_stall_ms must be >= 0 (0 disables)")
    if not 0 <= int(fl.trigger_olp_level) <= 3:
        bad("flight.trigger_olp_level must be in [0, 3]")
    from .observability import Profiler as _prof
    for stage, limit in dict(fl.slo_p99_ms or {}).items():
        if stage not in _prof.STAGES:
            bad(f"flight.slo_p99_ms: unknown profiler stage {stage!r}")
        elif float(limit) <= 0:
            bad(f"flight.slo_p99_ms[{stage!r}] must be > 0")
    return problems
