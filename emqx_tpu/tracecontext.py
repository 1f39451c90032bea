"""End-to-end message-lifecycle tracing: sampled trace contexts
through the batched hot path, across cluster links and multicore
workers.

The `emqx_external_trace`/OTLP-spans half of the reference's
observability story (emqx_opentelemetry's emqx_otel_trace behavior),
done the way Dapper-style tracers survive high-volume paths: a seeded
HEAD sampler decides at publish ingress, the decision rides the
message as a tiny ``TraceContext`` (a parallel column through the
batched pipeline — unsampled messages allocate NOTHING), and spans are
emitted once per window from the profiler's existing ``WindowRecord``
stage timestamps, so the dispatch loops take zero additional clock
reads.

Three boundaries the per-process window profiler (PR 4) cannot see
across are covered by context propagation:

  * cluster forwards — ``ClusterNode.forward`` stamps the context into
    the forwarded copy's MQTT 5 user properties (key ``TRACE_PROP``),
    so the peer's forwarded-dispatch span parents to the origin's
    ``message.forward`` span;
  * cluster links — the ``$LINK/msg`` wrapper carries the same field
    end-to-end, closed locally even when the link's failpoint eats the
    egress (chaos attribution);
  * multicore workers — worker processes cluster over loopback using
    the ordinary inter-node transport, so a cross-worker hop is traced
    exactly like a cross-node one, with per-worker process tracks in
    the merged Perfetto timeline.

Spans land in a bounded in-process ``TraceStore`` (queryable over
``GET /api/v5/tracing/...`` by trace id AND by message id, and from
``ctl tracing``) and flow out through the existing OTLP exporter
(otel.py) when one is configured.  ``chrome_trace`` renders any set of
span dicts — one node's store or several nodes' merged — as a
Perfetto-loadable timeline with one PROCESS per node/worker and flow
events linking each forward hop to its remote dispatch span.

Spans hold only ids, names and scalar attributes — never the message
or its payload — so the store cannot keep window buffers alive.
"""

from __future__ import annotations

import random
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import topic as T

# v5-user-property-shaped carrier: ("emqx-tp-trace", "<trace32>-<span16>")
# injected into the FORWARDED copy's properties at each egress seam and
# stripped at the peer's ingress, so subscriber-visible bytes never
# change (the chaos/property suites pin this down)
TRACE_PROP = "emqx-tp-trace"


def encode_ctx(trace_id: str, span_id: str) -> str:
    return f"{trace_id}-{span_id}"


def decode_ctx(value: str) -> Optional[Tuple[str, str]]:
    trace_id, _, span_id = value.partition("-")
    if len(trace_id) == 32 and len(span_id) == 16:
        return trace_id, span_id
    return None


def inject_props(properties: Dict, trace_id: str, span_id: str) -> None:
    """Append the context pair to ``user_property`` (any stale copy of
    the key is dropped first)."""
    ups = [
        (k, v)
        for k, v in (properties.get("user_property", ()) or ())
        if k != TRACE_PROP
    ]
    ups.append((TRACE_PROP, encode_ctx(trace_id, span_id)))
    properties["user_property"] = ups


def extract_strip(properties: Dict) -> Optional[Tuple[str, str]]:
    """Pop the context pair out of ``user_property`` and return
    (trace_id, span_id), or None.  Pairs may be tuples OR 2-lists (the
    binary cluster wire round-trips them through JSON)."""
    ups = properties.get("user_property")
    if not ups:
        return None
    found = None
    kept = []
    for pair in ups:
        k, v = pair
        if k == TRACE_PROP:
            found = decode_ctx(v)
        else:
            kept.append(pair)
    if found is not None:
        if kept:
            properties["user_property"] = kept
        else:
            del properties["user_property"]
    return found


class TraceContext:
    """One sampled message's context: the trace it belongs to, the
    span id its children parent to, and (for a message that crossed a
    boundary) the remote parent span id."""

    __slots__ = ("trace_id", "span_id", "parent_id", "remote")

    def __init__(self, trace_id: str, span_id: str,
                 parent_id: Optional[str] = None,
                 remote: bool = False) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.remote = remote


class TraceStore:
    """Bounded in-process span store, indexed by trace id AND by
    message id.  Eviction is whole-trace FIFO: when the ``max_traces``
    cap is hit the oldest trace goes, taking its message-id index
    entries with it — the store can never grow without bound no matter
    how chaotic the traffic (the link-drop chaos suite asserts this)."""

    def __init__(self, max_traces: int = 512) -> None:
        self.max_traces = max(int(max_traces), 1)
        self._traces: "OrderedDict[str, List[Dict]]" = OrderedDict()
        self._by_mid: Dict[str, str] = {}
        self._lock = threading.Lock()
        self.stats = {"spans": 0, "evicted": 0}

    def add(self, span: Dict) -> None:
        tid = span["trace_id"]
        mid = span.get("mid") or ""
        with self._lock:
            spans = self._traces.get(tid)
            if spans is None:
                spans = self._traces[tid] = []
                while len(self._traces) > self.max_traces:
                    old_tid, old_spans = self._traces.popitem(last=False)
                    self.stats["evicted"] += 1
                    for s in old_spans:
                        m = s.get("mid") or ""
                        if m and self._by_mid.get(m) == old_tid:
                            del self._by_mid[m]
            spans.append(span)
            self.stats["spans"] += 1
            if mid and mid not in self._by_mid:
                self._by_mid[mid] = tid

    def get(self, trace_id: str) -> List[Dict]:
        with self._lock:
            return list(self._traces.get(trace_id, ()))

    def by_mid(self, mid: str) -> Optional[str]:
        with self._lock:
            return self._by_mid.get(mid)

    def spans(self) -> List[Dict]:
        with self._lock:
            out: List[Dict] = []
            for spans in self._traces.values():
                out.extend(spans)
            return out

    def traces(self, limit: int = 64) -> List[Dict]:
        """Newest-first trace summaries."""
        with self._lock:
            items = list(self._traces.items())
        out = []
        for tid, spans in reversed(items[-max(limit, 0):]):
            first = min(s["start_ns"] for s in spans)
            last = max(s["end_ns"] for s in spans)
            root = next(
                (s for s in spans if not s.get("parent_id")), spans[0]
            )
            out.append({
                "trace_id": tid,
                "start_ns": first,
                "duration_ms": round((last - first) / 1e6, 3),
                "n_spans": len(spans),
                "topic": root.get("attrs", {}).get("topic", ""),
                "nodes": sorted({s.get("node", "") for s in spans}),
            })
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._traces)

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()
            self._by_mid.clear()
            self.stats = {"spans": 0, "evicted": 0}


class HeadSampler:
    """Seeded head sampler: a message is sampled when the coin lands
    under ``rate`` OR its topic matches one of the configured topic
    filters (operators pin the flows they are debugging).  ``seed``
    makes chaos runs reproduce their sampling decisions bit-for-bit."""

    def __init__(self, rate: float = 0.0,
                 topic_filters: Sequence[str] = (),
                 seed: Optional[int] = None) -> None:
        self.configure(rate, topic_filters, seed)

    def configure(self, rate: float,
                  topic_filters: Sequence[str] = (),
                  seed: Optional[int] = None) -> None:
        self.rate = min(max(float(rate), 0.0), 1.0)
        self.topic_filters = [str(f) for f in topic_filters]
        self.seed = seed
        self._rng = random.Random(seed)

    @property
    def active(self) -> bool:
        return self.rate > 0.0 or bool(self.topic_filters)

    def decide(self, topic: str) -> bool:
        # rate-sampling skips $-reserved topics ($SYS heartbeats, the
        # $LINK egress wrapper, $delayed) — their traffic is broker
        # plumbing, and the wrapper hop is already covered by the
        # ORIGINAL message's link.forward span.  An explicit topic
        # filter still pins them when an operator asks.
        if topic[:1] != "$":
            if self.rate >= 1.0:
                return True
            if self.rate > 0.0 and self._rng.random() < self.rate:
                return True
        for flt in self.topic_filters:
            if T.match(topic, flt):
                return True
        return False

    def span_id(self) -> str:
        return f"{self._rng.getrandbits(64):016x}"

    def trace_id(self) -> str:
        return f"{self._rng.getrandbits(128):032x}"


class PendingForward:
    """A forward span opened at an egress seam, closed when the flush
    learns the outcome (cast done, sync reply, failpoint drop, dead
    peer).  Holds ONLY the tracer and scalar fields — never the
    message — and emits at most once, so an egress path that reports
    twice (retry after re-queue) cannot double-count."""

    __slots__ = ("_tracer", "span")

    def __init__(self, tracer: "LifecycleTracer", span: Dict) -> None:
        self._tracer = tracer
        self.span = span

    @property
    def span_id(self) -> str:
        return self.span["span_id"]

    def end(self, ok: bool, detail: str = "") -> None:
        tracer, self._tracer = self._tracer, None
        if tracer is None:
            return
        span = self.span
        span["end_ns"] = time.time_ns()
        span["attrs"]["ok"] = bool(ok)
        if detail:
            span["attrs"]["detail"] = detail
        tracer.emit(span)


class LifecycleTracer:
    """The broker's per-message lifecycle tracer: head sampling at
    publish ingress, context extraction at every boundary ingress,
    window-level span emission from ``WindowRecord`` timestamps, and
    forward spans at the egress seams.

    Everything per-message is gated on ``active`` (rate 0 with no
    topic filters = every hot-path call site short-circuits on one
    attribute load) and on the message CARRYING a context — an
    unsampled window does no per-message work beyond the attribute
    probe the e2e profiler loop already pays."""

    def __init__(self, cfg=None, node: str = "emqx_tpu",
                 store: Optional[TraceStore] = None) -> None:
        rate = getattr(cfg, "sample_rate", 0.0) if cfg is not None else 0.0
        filters = getattr(cfg, "topic_filters", ()) if cfg is not None \
            else ()
        seed = getattr(cfg, "seed", None) if cfg is not None else None
        enable = bool(getattr(cfg, "enable", False)) if cfg is not None \
            else False
        self.node = node
        self.sampler = HeadSampler(rate, filters, seed)
        self.store = store or TraceStore(
            getattr(cfg, "store_max", 512) if cfg is not None else 512
        )
        self.enable = enable
        # wired by the OtelExporter when trace export is on: called
        # with each finished span dict (OTLP fan-out)
        self.on_export: Optional[Callable[[Dict], None]] = None
        self.stats = {"sampled": 0, "remote": 0, "forwards": 0}
        self._recompute()

    # ------------------------------------------------------- config

    def _recompute(self) -> None:
        # active == enabled, NOT enabled-and-sampling: a node with
        # rate 0 must still ADOPT upstream contexts (the natural
        # deployment samples at the ingress edge and enables
        # everywhere else).  Fresh sampling is separately gated by the
        # sampler's own rate/filters inside ingress().
        self.active = bool(self.enable)

    def configure(self, enable: Optional[bool] = None,
                  sample_rate: Optional[float] = None,
                  topic_filters: Optional[Sequence[str]] = None,
                  seed: Optional[int] = None) -> None:
        if enable is not None:
            self.enable = bool(enable)
        self.sampler.configure(
            self.sampler.rate if sample_rate is None else sample_rate,
            self.sampler.topic_filters if topic_filters is None
            else topic_filters,
            self.sampler.seed if seed is None else seed,
        )
        self._recompute()

    def info(self) -> Dict:
        return {
            "enable": self.enable,
            "active": self.active,
            "sampling": self.sampler.active,
            "sample_rate": self.sampler.rate,
            "topic_filters": list(self.sampler.topic_filters),
            "seed": self.sampler.seed,
            "node": self.node,
            "traces": len(self.store),
            "store_max": self.store.max_traces,
            **self.stats,
            **self.store.stats,
        }

    # ------------------------------------------------------ ingress

    def ingress(self, msg, sample: bool = True) -> None:
        """Publish-ingress decision for one message: honor an upstream
        context (the message crossed a boundary already sampled), else
        flip the head-sampler coin.  ``sample=False`` (forwarded-frame
        ingress) only adopts upstream contexts — the head decision is
        made ONCE, at the origin node.  Idempotent — the async prepare
        path may funnel through the sync one."""
        if getattr(msg, "_trace_ctx", None) is not None:
            return
        remote = extract_strip(msg.properties) if msg.properties else None
        if remote is None:
            hdr = msg.headers.pop("trace_ctx", None) if msg.headers \
                else None
            if hdr:
                remote = decode_ctx(str(hdr))
        if remote is not None:
            trace_id, parent_id = remote
            msg._trace_ctx = TraceContext(
                trace_id, self.sampler.span_id(), parent_id, remote=True
            )
            self.stats["remote"] += 1
            return
        if not sample or msg.sys:
            return
        if self.sampler.decide(msg.topic):
            msg._trace_ctx = TraceContext(
                self.sampler.trace_id(), self.sampler.span_id()
            )
            self.stats["sampled"] += 1

    # ------------------------------------------------------- windows

    def window_spans(self, msgs: Sequence, counts: Sequence[int],
                     rec=None, n_clients: int = 0,
                     clients: Optional[Dict] = None) -> None:
        """Emit one span per SAMPLED message of a finished dispatch
        window, timed entirely from the window's flight-recorder entry
        (``rec``): span = ingress→flush for a local publish, window
        start→flush for a forwarded hop, with one span event per
        pipeline stage and the engine path / breaker state / failpoint
        fires attached — no clock was read for any of this beyond what
        the profiler already recorded.  Called once per window, OUTSIDE
        the dispatch loops."""
        ctxs = [
            (i, ctx) for i, m in enumerate(msgs)
            for ctx in (getattr(m, "_trace_ctx", None),)
            if ctx is not None
        ]
        if not ctxs:
            return
        if rec is not None and rec.spans:
            w_start = rec.wall0
            last = rec.spans[-1]
            w_end = rec.wall0 + last[1] + last[2]
            stage_events = [
                {
                    "name": "stage." + name,
                    "ts_ns": int((rec.wall0 + off + dur) * 1e9),
                    "attrs": {"dur_us": round(dur * 1e6, 1)},
                }
                for name, off, dur in rec.spans
            ] + [
                {
                    "name": "stage." + name,
                    "ts_ns": int(w_end * 1e9),
                    "attrs": {"dur_us": round(dur * 1e6, 1)},
                }
                for name, _off, dur in rec.subs
            ]
            path = rec.path
            breaker = rec.breaker_open
            source = rec.source
        else:
            # profiler disabled: one clock read per WINDOW, never per
            # message, and only here (off the dispatch loops)
            w_end = time.time()
            w_start = min(
                (msgs[i].timestamp for i, _ in ctxs
                 if msgs[i].timestamp), default=w_end,
            )
            stage_events = []
            path = ""
            breaker = False
            source = "publish"
        fp_events = _failpoint_events(w_start, w_end)
        forwarded = source == "forwarded"
        for i, ctx in ctxs:
            msg = msgs[i]
            start = w_start if forwarded or not msg.timestamp \
                else min(msg.timestamp, w_start)
            span = {
                "trace_id": ctx.trace_id,
                "span_id": ctx.span_id,
                "parent_id": ctx.parent_id,
                "name": ("message.dispatch" if forwarded
                         else "message.publish"),
                "node": self.node,
                "start_ns": int(start * 1e9),
                "end_ns": int(w_end * 1e9),
                "mid": msg.mid.hex(),
                "attrs": {
                    "topic": msg.topic,
                    "qos": msg.qos,
                    "deliveries": counts[i],
                    "n_clients": n_clients,
                    "source": source,
                    "path": path,
                    "breaker_open": breaker,
                },
                "events": stage_events + fp_events,
            }
            if clients is not None:
                # delivering client ids for this sampled message
                # (recorded by the columns dispatch ONLY for runs that
                # carried a sampled message — capped so a fanout-10k
                # span stays bounded)
                cl = clients.get(id(msg))
                if cl:
                    span["attrs"]["clients"] = cl[:32]
                    span["attrs"]["clients_total"] = len(cl)
            self.emit(span)

    # ------------------------------------------------------ forwards

    def begin_forward(self, ctx: TraceContext, kind: str,
                      target: str, topic: str = "",
                      mid: str = "") -> PendingForward:
        """Open a forward span at an egress seam (cluster forward,
        link egress).  The returned handle is closed by whatever
        learns the outcome; its span id is what the peer's dispatch
        span parents to."""
        self.stats["forwards"] += 1
        span = {
            "trace_id": ctx.trace_id,
            "span_id": self.sampler.span_id(),
            "parent_id": ctx.span_id,
            "name": kind,
            "node": self.node,
            "start_ns": time.time_ns(),
            "end_ns": 0,
            "mid": mid,
            "attrs": {"target": target, "topic": topic},
            "events": [],
        }
        return PendingForward(self, span)

    def forward_copy(self, msg, ctx: TraceContext, target: str):
        """One traced forwarded copy of ``msg`` for ``target``: opens a
        ``message.forward`` span, injects (trace_id, forward span id)
        into a COPY of the properties (the local original — retained
        copies, detached-queue bakes, redeliveries — stays untouched),
        and rides the pending span on the clone for the flush loop to
        close.  Only sampled messages ever reach this."""
        import dataclasses

        pend = self.begin_forward(
            ctx, "message.forward", target,
            topic=msg.topic, mid=msg.mid.hex(),
        )
        props = dict(msg.properties) if msg.properties else {}
        inject_props(props, ctx.trace_id, pend.span_id)
        clone = dataclasses.replace(msg, properties=props)
        clone._trace_fwd = pend
        return clone

    # --------------------------------------------------------- emit

    def emit(self, span: Dict) -> None:
        self.store.add(span)
        exp = self.on_export
        if exp is not None:
            try:
                exp(span)
            except Exception:
                pass  # export must never affect dispatch


def _failpoint_events(w_start: float, w_end: float) -> List[Dict]:
    """Failpoint fires that landed inside the window, as span events —
    chaos runs attribute an anomalous window to the fault that caused
    it without correlating logs by hand."""
    from . import failpoints

    if not failpoints.RECENT_FIRES:
        return []
    out = []
    for ts, name, action, key in list(failpoints.RECENT_FIRES):
        if w_start <= ts <= w_end:
            out.append({
                "name": f"failpoint.{name}",
                "ts_ns": int(ts * 1e9),
                "attrs": {"action": action, "key": key or ""},
            })
    return out


# ------------------------------------------------------ perfetto export

def chrome_trace(spans: Sequence[Dict]) -> Dict[str, object]:
    """Render span dicts — one node's store or several nodes' dumps
    concatenated — as Chrome trace-event JSON (Perfetto-loadable):

      * one PROCESS per distinct ``node`` (explicit ``process_name``
        metadata, stable pids), so merged multi-node/multi-worker
        timelines keep each broker on its own row group;
      * one thread track per (node, trace), named by the trace id;
      * each span is a complete ("X") event; its span events ride as
        instant ("i") events on the same track;
      * every forward hop gets a FLOW (s→f) from the forward span to
        the remote span that parents to it — the visual thread
        connecting a publish on node A to its delivery on node B.

    Timestamps are exported relative to the earliest span (float64 µs
    at absolute epoch magnitude quantizes ~0.25 µs — same fix as the
    profiler's export)."""
    spans = [s for s in spans if s.get("end_ns")]
    if not spans:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    nodes: List[str] = []
    for s in spans:
        n = s.get("node", "?")
        if n not in nodes:
            nodes.append(n)
    pid_of = {n: i + 1 for i, n in enumerate(nodes)}
    epoch_ns = min(s["start_ns"] for s in spans)
    events: List[Dict[str, object]] = []
    for n, pid in pid_of.items():
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": f"emqx_tpu {n}"},
        })
        events.append({
            "name": "process_sort_index", "ph": "M", "pid": pid,
            "tid": 0, "args": {"sort_index": pid},
        })
    tids: Dict[Tuple[str, str], int] = {}
    named: set = set()
    # forward spans indexed by span id: flow sources
    fwd = {
        s["span_id"]: s for s in spans
        if s["name"] in ("message.forward", "link.forward")
    }
    for s in spans:
        node = s.get("node", "?")
        pid = pid_of[node]
        key = (node, s["trace_id"])
        tid = tids.setdefault(key, len(tids) + 1)
        if key not in named:
            named.add(key)
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid,
                "tid": tid,
                "args": {"name": f"trace {s['trace_id'][:8]}"},
            })
        ts = (s["start_ns"] - epoch_ns) / 1e3
        dur = max((s["end_ns"] - s["start_ns"]) / 1e3, 0.001)
        events.append({
            "name": s["name"], "ph": "X", "pid": pid, "tid": tid,
            "ts": ts, "dur": dur,
            "args": {
                "trace_id": s["trace_id"],
                "span_id": s["span_id"],
                "parent_id": s.get("parent_id") or "",
                "mid": s.get("mid", ""),
                **s.get("attrs", {}),
            },
        })
        for ev in s.get("events", ()):
            events.append({
                "name": ev["name"], "ph": "i", "pid": pid, "tid": tid,
                "ts": (ev["ts_ns"] - epoch_ns) / 1e3, "s": "t",
                "args": dict(ev.get("attrs", ())),
            })
    # flow events: forward span -> the (possibly remote) span that
    # parents to it.  53-bit ids keep JSON number-safe.
    for s in spans:
        parent = s.get("parent_id")
        src = fwd.get(parent) if parent else None
        if src is None or src is s:
            continue
        flow_id = int(parent[:13], 16)
        src_pid = pid_of[src.get("node", "?")]
        src_tid = tids[(src.get("node", "?"), src["trace_id"])]
        dst_pid = pid_of[s.get("node", "?")]
        dst_tid = tids[(s.get("node", "?"), s["trace_id"])]
        src_ts = (src["start_ns"] - epoch_ns) / 1e3
        events.append({
            "name": "hop", "ph": "s", "cat": "forward", "id": flow_id,
            "pid": src_pid, "tid": src_tid, "ts": src_ts,
        })
        events.append({
            "name": "hop", "ph": "f", "bp": "e", "cat": "forward",
            "id": flow_id, "pid": dst_pid, "tid": dst_tid,
            "ts": max((s["start_ns"] - epoch_ns) / 1e3, src_ts),
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}
