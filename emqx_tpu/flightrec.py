"""Flight recorder: always-on black-box capture with anomaly-triggered,
cross-process correlated diagnostic dumps.

The reference broker ships its observability as live surfaces ($SYS
heartbeats, `emqx_slow_subs`, `emqx_prometheus`) — good for watching a
healthy broker, useless for the post-hoc question "what was happening
in the 60 seconds BEFORE the p99 spike?".  Since PR 18 the broker is a
topology of processes (N workers x one match service x cluster peers)
and the evidence for exactly the failures the multicore scaling gate
will produce is scattered across per-process in-memory rings that
evaporate when a process dies or a deque rolls over.

This module is the black box:

``FlightRecorder``
    One per process (broker worker, match service, standalone node).
    Continuously records structured events into a bounded,
    PREALLOCATED numeric ring — window records (via
    ``Profiler.commit``), olp level transitions, shm-ring occupancy
    samples, breaker and alarm edges, failpoint fires, fsync/GC
    stalls, and an event-loop-lag watchdog.  Recording is O(1) and
    allocation-free: six scalar stores into preallocated numpy arrays
    under one lock, no per-message work for unsampled traffic
    (enforced by brokerlint OBS602 over the dispatch loops and by the
    interleaved A/B bench criterion in ``bench.run_flightrec_bench``).

Triggers
    A configurable anomaly — per-stage p99 SLO breach, breaker open,
    ``multicore.service.restart``, olp jump to L2+, watchdog stall,
    unhandled dispatch fault, or a manual ``ctl flight dump`` —
    freezes the ring and persists a dump atomically through
    ``ds.atomicio`` (same torn-write contract as the DS metadata
    sidecars: a crash mid-dump leaves the previous state, and the
    crashsim hooks can prove it).  Triggers debounce
    (``min_dump_interval``) so a breach storm yields ONE dump, not N.

Correlation
    The trigger mints one id; ``on_trigger`` broadcasts "dump now,
    correlated by this id" over the worker<->service control stream
    (see matchclient/matchsvc), so one anomaly in any process yields
    one merged capture: every live process persists its ring under the
    SAME id into the shared ``dump_dir``.  ``merge_dumps`` renders the
    set as a single Chrome trace-event timeline (Perfetto-loadable)
    with one track group per process — the ``tracecontext`` /
    ``Profiler.chrome_trace`` idiom, applied across processes.
"""

from __future__ import annotations

import gc
import logging
import os
import re
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import failpoints
from .ds.atomicio import atomic_write_json, try_load_json

log = logging.getLogger("emqx_tpu.flightrec")

# ------------------------------------------------------ event taxonomy
#
# Fixed numeric kinds: hot-path appends carry (ts, kind, a, b, c, d)
# and nothing else; the meaning of a..d is per-kind, documented here
# and in README "Flight recorder".

EV_WINDOW = 1      # dispatch window committed: a=n_msgs b=dur_us c=seq d=n_deliveries
EV_OLP = 2         # olp transition: a=from b=to c=loop_lag_ms
EV_RING = 3        # shm-ring occupancy sample: a=in_flight b=hwm c=full_total d=free
EV_RING_FULL = 4   # ring-full degrade: a=slots b=full_total
EV_BREAKER = 5     # engine breaker edge: a=1 open / 0 clear
EV_ALARM = 6       # alarm edge: a=1 up / 0 down
EV_FAILPOINT = 7   # failpoint fired (name/action in the note ring)
EV_FSYNC = 8       # ds fsync: a=dur_ms
EV_GC = 9          # gc pause over threshold: a=dur_ms b=generation
EV_WATCHDOG = 10   # event-loop stall: a=lag_ms
EV_TRIGGER = 11    # trigger fired here: a=reason code
EV_SLO = 12        # stage p99 breach: a=p99_ms b=limit_ms (stage in note)
EV_FWD = 13        # cluster forward flush: a=n_msgs b=peer_row
EV_SHED = 14       # olp shed: a=n (kind in counters)
EV_SVC_WINDOW = 15 # match-service window served: a=n_topics b=dur_us

EVENT_NAMES: Dict[int, str] = {
    EV_WINDOW: "window", EV_OLP: "olp_transition", EV_RING: "ring_sample",
    EV_RING_FULL: "ring_full", EV_BREAKER: "breaker", EV_ALARM: "alarm",
    EV_FAILPOINT: "failpoint", EV_FSYNC: "fsync", EV_GC: "gc_pause",
    EV_WATCHDOG: "watchdog_stall", EV_TRIGGER: "trigger", EV_SLO: "slo_breach",
    EV_FWD: "fwd_flush", EV_SHED: "shed", EV_SVC_WINDOW: "svc_window",
}

# trigger reasons -> EV_TRIGGER codes (stable for dump readers)
TRIGGER_REASONS = (
    "slo_breach", "breaker_open", "service_restart", "olp_level",
    "watchdog_stall", "dispatch_fault", "manual", "remote",
)
_REASON_CODE = {r: i + 1 for i, r in enumerate(TRIGGER_REASONS)}

_SAFE = re.compile(r"[^A-Za-z0-9_.@-]")


def _safe(label: str) -> str:
    return _SAFE.sub("_", label) or "proc"


def dump_filename(trig_id: str, label: str, pid: int) -> str:
    return f"flight-{_safe(trig_id)}--{_safe(label)}-{pid}.json"


class _Ring:
    """Preallocated fixed-capacity event ring: six parallel numpy
    columns and a monotonically increasing cursor.  ``append`` is the
    ONLY hot-path entry: six scalar stores + one increment under one
    lock — no dict, no list, no string, no per-event allocation."""

    __slots__ = ("cap", "ts", "kind", "a", "b", "c", "d", "n", "_lk")

    def __init__(self, cap: int) -> None:
        cap = max(int(cap), 64)
        self.cap = cap
        self.ts = np.zeros(cap, np.float64)
        self.kind = np.zeros(cap, np.uint16)
        self.a = np.zeros(cap, np.float64)
        self.b = np.zeros(cap, np.float64)
        self.c = np.zeros(cap, np.float64)
        self.d = np.zeros(cap, np.float64)
        self.n = 0
        self._lk = threading.Lock()

    def append(self, ts: float, kind: int, a: float, b: float,
               c: float, d: float) -> None:
        with self._lk:
            i = self.n % self.cap
            self.ts[i] = ts
            self.kind[i] = kind
            self.a[i] = a
            self.b[i] = b
            self.c[i] = c
            self.d[i] = d
            self.n += 1

    def snapshot(self) -> List[List[float]]:
        """Events oldest->newest as [ts, kind, a, b, c, d] rows."""
        with self._lk:
            n = self.n
            if n == 0:
                return []
            ts = self.ts.copy()
            kind = self.kind.copy()
            cols = (self.a.copy(), self.b.copy(), self.c.copy(),
                    self.d.copy())
        cap = self.cap
        lo = max(n - cap, 0)
        out: List[List[float]] = []
        for seq in range(lo, n):
            i = seq % cap
            out.append([
                float(ts[i]), int(kind[i]), float(cols[0][i]),
                float(cols[1][i]), float(cols[2][i]), float(cols[3][i]),
            ])
        return out


class FlightRecorder:
    """The per-process black box.  Construct once, wire event sources,
    call ``tick`` at ~1 Hz; triggers freeze + persist.  Thread-safe:
    events arrive from the event loop, batcher executors, breaker
    probes, the service reader thread and the watchdog thread."""

    def __init__(
        self,
        enable: bool = True,
        ring_size: int = 4096,
        notes_cap: int = 512,
        dump_dir: str = "",
        max_dumps: int = 16,
        min_dump_interval: float = 30.0,
        watchdog_stall_ms: float = 5000.0,
        slo_p99_ms: Optional[Dict[str, float]] = None,
        fsync_stall_ms: float = 500.0,
        gc_stall_ms: float = 100.0,
        trigger_olp_level: int = 2,
        trigger_on_breaker: bool = True,
        trigger_on_restart: bool = True,
        trigger_on_fault: bool = True,
        process_label: str = "emqx_tpu",
        role: str = "broker",
        pid: Optional[int] = None,
        metrics=None,
    ) -> None:
        self.armed = bool(enable)
        self.process_label = process_label
        self.role = role
        self.pid = pid if pid is not None else os.getpid()
        self.dump_dir = dump_dir
        self.min_dump_interval = float(min_dump_interval)
        self.watchdog_stall_ms = float(watchdog_stall_ms)
        self.slo_p99_ms = dict(slo_p99_ms or {})
        self.fsync_stall_ms = float(fsync_stall_ms)
        self.gc_stall_ms = float(gc_stall_ms)
        self.trigger_olp_level = int(trigger_olp_level)
        self.metrics = metrics
        self._gates = {
            "breaker_open": bool(trigger_on_breaker),
            "service_restart": bool(trigger_on_restart),
            "dispatch_fault": bool(trigger_on_fault),
            "olp_level": self.trigger_olp_level >= 1,
        }
        self._ring = _Ring(ring_size)
        # cold-path annotations (olp snapshots, alarm names, failpoint
        # detail): allocation here is fine — none of these sit in a
        # dispatch loop
        self._notes: deque = deque(maxlen=max(int(notes_cap), 16))
        self._tlock = threading.Lock()
        self._last_trigger = 0.0
        self._suppressed = 0
        self._trigger_count = 0
        self._dumps: deque = deque(maxlen=max(int(max_dumps), 1))
        self._dumped_ids: set = set()
        self._last_id: Optional[str] = None
        self._samplers: List[Callable[["FlightRecorder"], None]] = []
        self._slo_prev: Dict[str, object] = {}
        self._fp_last = 0.0
        self._hb = time.monotonic()
        self._wd_thread: Optional[threading.Thread] = None
        self._wd_stop: Optional[threading.Event] = None
        self._gc_t0 = 0.0
        self._gc_registered = False
        # every collection since the callback was armed: seconds
        # paused, collections (`gc_clock`)
        self._gc_s = 0.0
        self._gc_n = 0
        # cross-process broadcast hook: called as on_trigger(id, reason)
        # AFTER the local dump lands (matchclient.flight_broadcast /
        # MatchService relay)
        self.on_trigger: Optional[Callable[[str, str], None]] = None
        # extra per-process payload folded into dumps (profiler windows
        # and summaries; set by the owner, read at freeze time)
        self.profiler = None

    @classmethod
    def from_config(cls, cfg, **over) -> "FlightRecorder":
        """Build from a ``config.FlightConfig`` dataclass (or any
        object with the same attributes)."""
        kw = dict(
            enable=cfg.enable, ring_size=cfg.ring_size,
            notes_cap=cfg.notes_cap, dump_dir=cfg.dump_dir,
            max_dumps=cfg.max_dumps,
            min_dump_interval=cfg.min_dump_interval,
            watchdog_stall_ms=cfg.watchdog_stall_ms,
            slo_p99_ms=dict(cfg.slo_p99_ms or {}),
            fsync_stall_ms=cfg.fsync_stall_ms,
            gc_stall_ms=cfg.gc_stall_ms,
            trigger_olp_level=cfg.trigger_olp_level,
            trigger_on_breaker=cfg.trigger_on_breaker,
            trigger_on_restart=cfg.trigger_on_restart,
            trigger_on_fault=cfg.trigger_on_fault,
        )
        kw.update(over)
        return cls(**kw)

    # --------------------------------------------------- hot-path ring

    def record(self, kind: int, a: float = 0.0, b: float = 0.0,
               c: float = 0.0, d: float = 0.0) -> None:
        """THE O(1) append helper — the only flight call brokerlint
        OBS602 admits inside a dispatch loop.  Scalar args only: no
        dict/list/str may be built in the call's arg tree."""
        if not self.armed:
            return
        self._ring.append(time.time(), kind, a, b, c, d)

    def note(self, kind: str, **fields) -> None:
        """Cold-path annotated event (never call from a dispatch
        loop — OBS602 rejects it there by design)."""
        if not self.armed:
            return
        fields["at"] = time.time()
        fields["kind"] = kind
        self._notes.append(fields)

    # ------------------------------------------------- event sources

    def on_window(self, rec) -> None:
        """One committed ``WindowRecord`` (wired into
        ``Profiler.commit``: one attribute load + one append per
        window; the record itself stays in the profiler ring and rides
        into dumps from there)."""
        if not self.armed:
            return
        self._ring.append(
            rec.wall0, EV_WINDOW, float(rec.n_msgs),
            (rec._t_last - rec.t0) * 1e6, float(rec.seq),
            float(rec.n_deliveries),
        )

    def olp_transition(self, old: int, new: int, lag_ms: float,
                       signals: Optional[Dict] = None) -> None:
        self.record(EV_OLP, float(old), float(new), float(lag_ms))
        self.note("olp_transition", frm=old, to=new,
                  signals=dict(signals or {}))
        if new > old and self._gates["olp_level"] and \
                new >= self.trigger_olp_level:
            self.trigger("olp_level",
                         {"from": old, "to": new,
                          "signals": dict(signals or {})})

    def breaker_edge(self, is_open: bool, info: Optional[Dict] = None) -> None:
        self.record(EV_BREAKER, 1.0 if is_open else 0.0)
        self.note("breaker", open=bool(is_open), info=dict(info or {}))
        if is_open and self._gates["breaker_open"]:
            self.trigger("breaker_open", dict(info or {}))

    def alarm_edge(self, name: str, is_up: bool) -> None:
        self.record(EV_ALARM, 1.0 if is_up else 0.0)
        self.note("alarm", name=name, up=bool(is_up))

    def fsync(self, dur_s: float) -> None:
        dur_ms = dur_s * 1e3
        self.record(EV_FSYNC, dur_ms)
        if self.fsync_stall_ms > 0 and dur_ms >= self.fsync_stall_ms:
            self.note("fsync_stall", dur_ms=round(dur_ms, 2))

    def service_restart(self, detail: Optional[Dict] = None,
                        key: Optional[str] = None) -> None:
        self.note("service_restart", **(detail or {}))
        if self._gates["service_restart"]:
            self.trigger("service_restart", detail, key=key)

    def dispatch_fault(self, where: str, exc: BaseException) -> None:
        self.note("dispatch_fault", where=where, error=repr(exc))
        if self._gates["dispatch_fault"]:
            self.trigger("dispatch_fault",
                         {"where": where, "error": repr(exc)})

    def add_sampler(self, fn: Callable[["FlightRecorder"], None]) -> None:
        """Register a 1 Hz occupancy sampler (shm ring, batcher depth):
        called from ``tick`` with this recorder."""
        self._samplers.append(fn)

    # ---------------------------------------------------- 1 Hz tick

    def tick(self, now: Optional[float] = None, profiler=None) -> None:
        """Housekeeping-cadence work: watchdog heartbeat, registered
        occupancy samplers, failpoint-fire drain, and the per-stage
        p99 SLO check (delta snapshots, so a breach reflects THIS
        interval's traffic, not history)."""
        if not self.armed:
            return
        self._hb = time.monotonic()
        for fn in self._samplers:
            try:
                fn(self)
            except Exception:
                log.exception("flight sampler failed")
        if failpoints.enabled or failpoints.RECENT_FIRES:
            self._drain_failpoints()
        prof = profiler if profiler is not None else self.profiler
        if self.slo_p99_ms and prof is not None:
            self._check_slo(prof)

    def heartbeat(self) -> None:
        self._hb = time.monotonic()

    def _drain_failpoints(self) -> None:
        last = self._fp_last
        newest = last
        for ts, name, action, key in failpoints.fires_since(last):
            self.record(EV_FAILPOINT)
            self.note("failpoint", name=name, action=action, key=key)
            if ts > newest:
                newest = ts
        self._fp_last = newest

    def _check_slo(self, prof) -> None:
        from .observability import HistogramSnapshot

        snaps = prof.snapshots()
        for stage, limit in self.slo_p99_ms.items():
            snap = snaps.get(stage)
            if snap is None:
                continue
            prev = self._slo_prev.get(stage)
            self._slo_prev[stage] = snap
            if prev is None:
                continue
            d_count = snap.count - prev.count
            if d_count <= 0:
                continue
            delta = HistogramSnapshot(
                tuple(a - b for a, b in zip(snap.counts, prev.counts)),
                snap.sum - prev.sum, d_count,
            )
            p99_ms = delta.percentile(99) / 1e3  # recorded in µs
            if p99_ms > float(limit):
                self.record(EV_SLO, p99_ms, float(limit))
                self.note("slo_breach", stage=stage,
                          p99_ms=round(p99_ms, 3), limit_ms=float(limit),
                          windows=d_count)
                self.trigger("slo_breach", {
                    "stage": stage, "p99_ms": round(p99_ms, 3),
                    "limit_ms": float(limit),
                })

    # ----------------------------------------------------- watchdog

    def arm_watchdog(self) -> None:
        """Start the event-loop-lag watchdog thread (and the GC-pause
        observer).  Explicitly armed by serving processes only —
        short-lived test brokers never spawn the thread or touch the
        process-global ``gc.callbacks``."""
        if not self.armed or self._wd_thread is not None:
            return
        if self.gc_stall_ms > 0 and not self._gc_registered:
            self._gc_s, self._gc_n = 0.0, 0
            gc.callbacks.append(self._gc_cb)
            self._gc_registered = True
            self._attach_gc(self.gc_clock)
        if self.watchdog_stall_ms <= 0:
            return
        self._hb = time.monotonic()
        self._wd_stop = threading.Event()
        t = threading.Thread(
            target=self._wd_main,
            name=f"flightrec-watchdog-{self.pid}", daemon=True,
        )
        self._wd_thread = t
        t.start()

    def stop(self) -> None:
        stop = self._wd_stop
        if stop is not None:
            stop.set()
        t = self._wd_thread
        if t is not None:
            t.join(timeout=2.0)
        self._wd_thread = None
        self._wd_stop = None
        if self._gc_registered:
            try:
                gc.callbacks.remove(self._gc_cb)
            except ValueError:
                pass
            self._gc_registered = False
            self._attach_gc(None)

    def _wd_main(self) -> None:
        stall_s = self.watchdog_stall_ms / 1e3
        interval = max(stall_s / 4.0, 0.05)
        stalled = False
        stop = self._wd_stop
        while not stop.wait(interval):
            lag = time.monotonic() - self._hb
            if lag >= stall_s:
                if not stalled:
                    stalled = True  # one trigger per stall episode
                    lag_ms = lag * 1e3
                    self.record(EV_WATCHDOG, lag_ms)
                    self.note("watchdog_stall", lag_ms=round(lag_ms, 1))
                    self.trigger("watchdog_stall",
                                 {"lag_ms": round(lag_ms, 1)})
            else:
                stalled = False

    def _gc_cb(self, phase: str, info: Dict) -> None:
        """The process's one ``gc.callbacks`` entry: every collection's
        pause goes to the totals the window profiler takes the growth
        of (`gc_clock`: ring fields ``gc_us`` / ``gc_collections``); a
        pause over the threshold is an event of this ring and an
        interval of the profiler's trace export (``gc_pause``)."""
        if phase == "start":
            self._gc_t0 = time.monotonic()
            return
        dur_s = time.monotonic() - self._gc_t0
        self._gc_s += dur_s
        self._gc_n += 1
        if dur_s * 1e3 >= self.gc_stall_ms:
            generation = info.get("generation", 0)
            self.record(EV_GC, dur_s * 1e3, float(generation))
            prof = self.profiler
            if prof is not None:
                prof.note("gc_pause", dur_s, generation=generation)

    def gc_clock(self) -> Tuple[float, int]:
        """Seconds paused in collections and their count since the
        callback was armed; both only grow."""
        return (self._gc_s, self._gc_n)

    def _attach_gc(self, clock) -> None:
        loop_clock = getattr(self.profiler, "loop", None)
        if loop_clock is not None:
            loop_clock.attach_gc(clock)

    # ----------------------------------------------------- triggers

    def trigger(self, reason: str, detail: Optional[Dict] = None,
                force: bool = False,
                key: Optional[str] = None) -> Optional[str]:
        """Freeze + dump, debounced: a second trigger inside
        ``min_dump_interval`` is counted and dropped (the storm rule).
        Returns the minted correlation id, or None when suppressed.
        ``force`` bypasses the debounce (manual ``ctl flight dump``).

        ``key`` makes the id deterministic (``{reason}-{key}``) instead
        of time+pid minted: independent observers of the SAME fault —
        e.g. every worker noticing the death of service incarnation N
        while the relay hub that would correlate them is itself the
        thing that died — converge on one id, and per-id idempotence
        collapses their captures into one."""
        if not self.armed:
            return None
        now = time.time()
        with self._tlock:
            if key is not None:
                trig_id = f"{_safe(reason)}-{_safe(str(key))}"
                if trig_id in self._dumped_ids:
                    self._suppressed += 1
                    if self.metrics is not None:
                        self.metrics.inc("flight.triggers.suppressed")
                    return None
            if not force and (
                now - self._last_trigger < self.min_dump_interval
            ):
                self._suppressed += 1
                if self.metrics is not None:
                    self.metrics.inc("flight.triggers.suppressed")
                return None
            self._last_trigger = now
            self._trigger_count += 1
            if key is None:
                trig_id = (
                    f"{int(now * 1e3):x}-{self.pid:x}-{_safe(reason)}"
                )
        if self.metrics is not None:
            self.metrics.inc("flight.triggers")
        self.record(EV_TRIGGER, float(_REASON_CODE.get(reason, 0)))
        self._dump(trig_id, reason, detail, now)
        cb = self.on_trigger
        if cb is not None:
            try:
                cb(trig_id, reason)
            except Exception:
                log.exception("flight trigger broadcast failed")
        return trig_id

    def dump_remote(self, trig_id: str, reason: str = "") -> bool:
        """Honor a cross-process "dump now" request: persist THIS
        process's ring under the initiator's id.  Idempotent per id,
        and arms the local debounce so the anomaly's local echo (e.g.
        the detach a service restart also causes here) does not mint a
        second id."""
        if not self.armed or not trig_id:
            return False
        now = time.time()
        with self._tlock:
            if trig_id in self._dumped_ids:
                return False
            self._last_trigger = now
        if self.metrics is not None:
            self.metrics.inc("flight.remote_requests")
        self._dump(trig_id, f"remote:{reason or 'dump'}", None, now)
        return True

    def _dump(self, trig_id: str, reason: str,
              detail: Optional[Dict], now: float) -> None:
        doc = self._freeze(trig_id, reason, detail, now)
        with self._tlock:
            self._dumps.append(doc)
            self._dumped_ids.add(trig_id)
            self._last_id = trig_id
        if self.dump_dir:
            path = os.path.join(
                self.dump_dir,
                dump_filename(trig_id, self.process_label, self.pid),
            )
            try:
                os.makedirs(self.dump_dir, exist_ok=True)
                atomic_write_json(path, doc)
                if self.metrics is not None:
                    self.metrics.inc("flight.dumps")
            except Exception:
                if self.metrics is not None:
                    self.metrics.inc("flight.dump.errors")
                log.exception("flight dump write failed: %s", path)
        else:
            if self.metrics is not None:
                self.metrics.inc("flight.dumps")
        log.warning("flight recorder dump %s (%s) [%s pid=%d]",
                    trig_id, reason, self.process_label, self.pid)

    def _freeze(self, trig_id: str, reason: str,
                detail: Optional[Dict], now: float) -> Dict:
        doc: Dict = {
            "v": 1,
            "id": trig_id,
            "reason": reason,
            "node": self.process_label,
            "role": self.role,
            "pid": self.pid,
            "at": now,
            "detail": dict(detail or {}),
            "event_names": {str(k): v for k, v in EVENT_NAMES.items()},
            "events": self._ring.snapshot(),
            "notes": list(self._notes),
            "failpoints": [
                {"at": ts, "name": name, "action": action, "key": key}
                for ts, name, action, key in list(failpoints.RECENT_FIRES)
            ],
        }
        prof = self.profiler
        if prof is not None:
            try:
                doc["windows"] = prof.windows(64)
                doc["profiler"] = prof.summary()
            except Exception:
                log.exception("flight dump profiler fold failed")
        if self.metrics is not None:
            try:
                doc["counters"] = {
                    k: v for k, v in self.metrics.all().items() if v
                }
            except Exception:
                pass
        return doc

    # --------------------------------------------------- exposition

    def status(self) -> Dict:
        with self._tlock:
            dumps = [
                {"id": d["id"], "reason": d["reason"], "at": d["at"]}
                for d in self._dumps
            ]
            return {
                "armed": self.armed,
                "node": self.process_label,
                "role": self.role,
                "pid": self.pid,
                "ring_size": self._ring.cap,
                "events_recorded": self._ring.n,
                "dump_dir": self.dump_dir,
                "triggers": self._trigger_count,
                "triggers_suppressed": self._suppressed,
                "last_id": self._last_id,
                "min_dump_interval": self.min_dump_interval,
                "watchdog_stall_ms": self.watchdog_stall_ms,
                "slo_p99_ms": dict(self.slo_p99_ms),
                "dumps": dumps,
            }

    def local_dumps(self, trig_id: Optional[str] = None) -> List[Dict]:
        with self._tlock:
            docs = list(self._dumps)
        if trig_id is None:
            return docs
        return [d for d in docs if d.get("id") == trig_id]


# ------------------------------------------------- dump collection/merge

def list_dump_ids(dump_dir: str) -> List[Dict]:
    """Dump ids present on disk, newest first: one row per id with the
    process files that share it."""
    ids: Dict[str, Dict] = {}
    try:
        names = os.listdir(dump_dir) if dump_dir else []
    except OSError:
        names = []
    for name in sorted(names):
        if not (name.startswith("flight-") and name.endswith(".json")):
            continue
        body = name[len("flight-"):-len(".json")]
        trig_id, sep, proc = body.partition("--")
        if not sep:
            continue
        row = ids.setdefault(trig_id, {"id": trig_id, "files": []})
        row["files"].append(name)
    out = list(ids.values())
    out.sort(key=lambda r: r["id"], reverse=True)
    return out


def collect_dumps(
    recorder: Optional[FlightRecorder], trig_id: str,
    dump_dir: Optional[str] = None,
) -> Tuple[List[Dict], int]:
    """Every process's dump for ``trig_id``: files in the shared
    ``dump_dir`` (torn/corrupt files are SKIPPED and counted — the
    atomicio contract means a torn dump self-identifies) merged with
    the local in-memory snapshots.  Deduped per (node, role, pid),
    disk copy preferred."""
    docs: Dict[Tuple, Dict] = {}
    torn = 0
    d = dump_dir if dump_dir is not None else (
        recorder.dump_dir if recorder is not None else ""
    )
    if d:
        prefix = f"flight-{_safe(trig_id)}--"
        try:
            names = sorted(os.listdir(d))
        except OSError:
            names = []
        for name in names:
            if not (name.startswith(prefix) and name.endswith(".json")):
                continue
            doc, status = try_load_json(os.path.join(d, name), None)
            if status != "ok" or not isinstance(doc, dict):
                torn += 1
                continue
            docs[(doc.get("node"), doc.get("role"), doc.get("pid"))] = doc
    if recorder is not None:
        for doc in recorder.local_dumps(trig_id):
            key = (doc.get("node"), doc.get("role"), doc.get("pid"))
            docs.setdefault(key, doc)
    out = list(docs.values())
    out.sort(key=lambda r: (r.get("role", ""), r.get("node", "")))
    return out, torn


def merge_dumps(docs: Sequence[Dict]) -> Dict:
    """Render one correlated capture as Chrome trace-event JSON
    (Perfetto-loadable): one process track group per dump (real pid +
    node label + role), windows as complete ("X") slices, numeric ring
    events and annotations as instants.  Timestamps are relative to
    the capture's own epoch for full float64 precision — the
    ``Profiler.chrome_trace`` rule, applied across processes."""
    starts: List[float] = []
    for doc in docs:
        for row in doc.get("events") or []:
            starts.append(float(row[0]))
        for w in doc.get("windows") or []:
            starts.append(float(w.get("at", 0.0)))
        for n in doc.get("notes") or []:
            starts.append(float(n.get("at", 0.0)))
    epoch = min(starts) if starts else 0.0
    events: List[Dict] = []
    for sort, doc in enumerate(docs):
        pid = int(doc.get("pid", 0)) or (10_000 + sort)
        label = doc.get("node", "proc")
        role = doc.get("role", "")
        names = {
            int(k): v for k, v in (doc.get("event_names") or {}).items()
        } or EVENT_NAMES
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": f"{label} [{role} pid={pid}]"},
        })
        events.append({
            "name": "process_sort_index", "ph": "M", "pid": pid,
            "tid": 0, "args": {"sort_index": sort},
        })
        events.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": "flight events"},
        })
        events.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": 1,
            "args": {"name": "windows"},
        })
        for row in doc.get("events") or []:
            ts, kind = float(row[0]), int(row[1])
            name = names.get(kind, f"ev{kind}")
            ph = "i"
            ev: Dict = {
                "name": name, "ph": ph, "pid": pid, "tid": 0,
                "ts": (ts - epoch) * 1e6, "s": "t",
                "args": {"a": row[2], "b": row[3], "c": row[4],
                         "d": row[5]},
            }
            events.append(ev)
        for w in doc.get("windows") or []:
            stages = w.get("stages_us") or {}
            dur_us = sum(float(v) for v in stages.values())
            events.append({
                "name": f"window {w.get('seq')} ({w.get('source')})",
                "ph": "X", "pid": pid, "tid": 1,
                "ts": (float(w.get("at", epoch)) - epoch) * 1e6,
                "dur": max(dur_us, 1.0),
                "args": {
                    "n_msgs": w.get("n_msgs"),
                    "n_deliveries": w.get("n_deliveries"),
                    "path": w.get("path"),
                    "stages_us": stages,
                },
            })
        for n in doc.get("notes") or []:
            args = {k: v for k, v in n.items() if k not in ("at", "kind")}
            events.append({
                "name": n.get("kind", "note"), "ph": "i", "pid": pid,
                "tid": 0, "ts": (float(n.get("at", epoch)) - epoch) * 1e6,
                "s": "t", "args": args,
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


__all__ = [
    "EVENT_NAMES", "EV_ALARM", "EV_BREAKER", "EV_FAILPOINT", "EV_FSYNC",
    "EV_FWD", "EV_GC", "EV_OLP", "EV_RING", "EV_RING_FULL", "EV_SHED",
    "EV_SLO", "EV_SVC_WINDOW", "EV_TRIGGER", "EV_WATCHDOG", "EV_WINDOW",
    "FlightRecorder", "TRIGGER_REASONS", "collect_dumps",
    "dump_filename", "list_dump_ids", "merge_dumps",
]
