"""Hot-path window profiler: stage-latency histograms, a flight
recorder of recent dispatch windows, and Chrome trace-event export.

The reference ships its observability as first-class subsystems —
`emqx_prometheus` exposition, `emqx_opentelemetry` OTLP metrics/spans,
`emqx_slow_subs` — but its hot path is per-message, so per-hook
counters suffice.  This broker's hot path is *batched* (window
assembly → trie-automaton match → CSR expand → encode-once → corked
flush), and a flat counter cannot say **which stage** of the window
pipeline a stall lives in.  Three pieces close that gap:

``Histogram``
    Fixed log2-bucket latency histogram: precomputed bounds, O(1)
    ``int.bit_length`` bucket index, mergeable snapshots.  Recording
    is lock-amortized the way ``Metrics.inc_bulk`` is — the profiler
    takes ONE lock per committed window for all of the window's stage
    samples, not one per sample.

``Profiler`` / ``WindowRecord``
    Per-window stage spans (batch-wait, prepare, match submit/wait
    with host-vs-device path + breaker state, CSR expand, deliver,
    cork flush, end-to-end publish→delivery) collected by the broker
    with two ``perf_counter`` calls per stage, plus engine lifecycle
    events (XLA shape compiles, ``device_put`` transfer bytes, delta
    folds) recorded from the builder threads.  Inside a span, named
    sub-spans with a start (the queue, device and host parts of the two
    match laps, the device round trips of ``decide`` and ``rules``) say
    what the lap was made of, and the engine's sections on the
    executor threads carry their thread's CPU seconds beside the wall
    time (``<name>_cpu``); ``LoopClock`` counts what the event loop
    does between the windows' stages, socket reads and writes, and
    cuts the loop thread's whole wall time into the phases of its turn.

Flight recorder
    A fixed ring of the last N ``WindowRecord``s, always on and
    near-free, dumpable over REST (``/api/v5/profiler``) and as
    Chrome trace-event JSON (``/api/v5/profiler/trace``) that loads
    directly in Perfetto — a stall is diagnosable post-hoc without a
    reproducer.
"""

from __future__ import annotations

import os
import re
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

# log2 bucket upper bounds (inclusive), shared by every Histogram:
# bucket i holds integer values v with bit_length(v) == i, i.e.
# v <= 2**i - 1; the last bucket is +Inf.  31 finite bounds cover one
# microsecond to ~35 minutes when values are recorded in µs.
N_BUCKETS = 32
BOUNDS: Tuple[int, ...] = tuple((1 << i) - 1 for i in range(N_BUCKETS - 1))


class HistogramSnapshot:
    """Immutable point-in-time copy of a Histogram; snapshots merge
    (per-bucket add) so per-shard / per-process histograms aggregate
    without losing percentile fidelity."""

    __slots__ = ("counts", "sum", "count")

    def __init__(self, counts: Sequence[int], total: float, count: int):
        self.counts = tuple(counts)
        self.sum = total
        self.count = count

    def merge(self, other: "HistogramSnapshot") -> "HistogramSnapshot":
        return HistogramSnapshot(
            tuple(a + b for a, b in zip(self.counts, other.counts)),
            self.sum + other.sum,
            self.count + other.count,
        )

    def percentile(self, q: float) -> float:
        """Estimated q-quantile (q in [0, 100]): linear interpolation
        inside the containing bucket.  0.0 with no samples."""
        if self.count == 0:
            return 0.0
        target = self.count * min(max(q, 0.0), 100.0) / 100.0
        cum = 0
        for i, c in enumerate(self.counts):
            if c == 0:
                continue
            if cum + c >= target:
                lo = 0 if i == 0 else BOUNDS[i - 1] + 1
                hi = (
                    BOUNDS[i]
                    if i < len(BOUNDS)
                    # open-ended last bucket: cap at the mean of what
                    # landed there (sum bounds it) or 2x the last edge
                    else max(BOUNDS[-1] * 2, lo)
                )
                frac = (target - cum) / c
                return lo + (hi - lo) * frac
            cum += c
        return float(BOUNDS[-1])

    def raw_dict(self) -> Dict[str, object]:
        """Lossless wire form (counts included) — the match service
        ships these over the control socket so the broker side can
        re-expose REAL histograms (prometheus buckets, mergeable
        snapshots), not just point percentiles."""
        return {
            "counts": list(self.counts),
            "sum": self.sum,
            "count": self.count,
        }

    @classmethod
    def from_dict(cls, d: Dict) -> "HistogramSnapshot":
        counts = list(d.get("counts") or [])
        counts = (counts + [0] * N_BUCKETS)[:N_BUCKETS]
        return cls(counts, float(d.get("sum", 0.0)),
                   int(d.get("count", 0)))

    def to_dict(self) -> Dict[str, object]:
        return {
            "count": self.count,
            "sum": round(self.sum, 3),
            "p50": round(self.percentile(50), 3),
            "p95": round(self.percentile(95), 3),
            "p99": round(self.percentile(99), 3),
            "max_bucket_le": (
                BOUNDS[min(
                    max(i for i, c in enumerate(self.counts) if c),
                    len(BOUNDS) - 1,
                )]
                if self.count else 0
            ),
        }


class Histogram:
    """Fixed log2-bucket histogram.  ``record`` is O(1): the bucket
    index is ``int(value).bit_length()`` against precomputed bounds —
    no search, no allocation.  Thread-safe via its own lock unless the
    owner passes a shared one (the Profiler amortizes ONE lock across
    every histogram it owns, one acquisition per window)."""

    __slots__ = ("_counts", "_sum", "_count", "_lock")

    def __init__(self, lock: Optional[threading.Lock] = None) -> None:
        self._counts = [0] * N_BUCKETS
        self._sum = 0.0
        self._count = 0
        self._lock = lock if lock is not None else threading.Lock()

    @staticmethod
    def bucket_index(value: float) -> int:
        v = int(value)
        if v <= 0:
            return 0
        i = v.bit_length()
        return i if i < N_BUCKETS else N_BUCKETS - 1

    def _record_locked(self, value: float) -> None:
        """Caller holds the lock (bulk paths)."""
        self._counts[Histogram.bucket_index(value)] += 1
        self._sum += value
        self._count += 1

    def record(self, value: float) -> None:
        with self._lock:
            self._record_locked(value)

    def record_many(self, values: Sequence[float]) -> None:
        """Bulk record under ONE lock acquisition — per-window use."""
        if not values:
            return
        with self._lock:
            for v in values:
                self._record_locked(v)

    def snapshot(self) -> HistogramSnapshot:
        with self._lock:
            return HistogramSnapshot(
                list(self._counts), self._sum, self._count
            )

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * N_BUCKETS
            self._sum = 0.0
            self._count = 0


_TraceAnnotation = None  # jax.profiler.TraceAnnotation, on first use


def annotation(name: str, seq: int):
    """A ``jax.profiler.TraceAnnotation`` named ``emqx/<name>`` that
    carries the window's ``seq``: a host span on the device trace's own
    clock.  None while no `jax.profiler` trace runs (one static call
    says so), so an untraced window makes no annotation object.  For
    synchronous sections only (one thread, no ``await`` inside)."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    if not _TraceAnnotation.is_enabled():
        return None
    return _TraceAnnotation("emqx/" + name, seq=seq)


class Laps:
    """Contiguous named laps on ``perf_counter``: ``lap`` closes the
    span running since the previous lap under a name, and where the
    section is synchronous ``mark`` (or ``lap(..., then=)``) opens its
    trace annotation first.  The engine times its own sections with
    one and hands the spans back; a window's record is one."""

    __slots__ = ("seq", "t0", "_t_last", "spans", "_ann")

    def __init__(self, seq: int) -> None:
        self.seq = seq
        self.t0 = self._t_last = time.perf_counter()
        self.spans: List[Tuple[str, float, float]] = []  # (name, off, dur)
        self._ann = None  # the open trace annotation, if any

    def mark(self, name: str) -> None:
        """Open the trace annotation of the synchronous section that
        the next ``lap`` (on this thread) closes."""
        ann = annotation(name, self.seq)
        if ann is not None:
            self._ann = ann
            ann.__enter__()

    def lap(self, name: str, then: Optional[str] = None) -> float:
        """Close the span running since the previous lap (or since
        construction) under ``name`` — two perf_counter reads per
        stage, nothing else on the hot path.  ``then`` marks the
        section that starts here.  Returns the reading that closed
        the span."""
        now = time.perf_counter()
        self.spans.append((name, self._t_last - self.t0, now - self._t_last))
        self._t_last = now
        self.unmark()
        if then is not None:
            self.mark(then)
        return now

    def unmark(self) -> None:
        """Close the open annotation, if any (a section that raised
        before its lap)."""
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)

    def now(self) -> float:
        """A ``perf_counter`` reading to hand to `nest`."""
        return time.perf_counter()

    def nest(self, name: str, start: float) -> None:
        """A span from ``start`` (`now`) to this instant, nested inside
        the lap that is running: it comes out among `timings` beside
        the lap that holds it, and the chain of laps goes on
        untouched."""
        self.spans.append(
            (name, start - self.t0, time.perf_counter() - start)
        )

    def timings(self) -> List[Tuple[str, Optional[float], float]]:
        """The spans as ``(name, start, dur)`` with ``start`` on the
        perf_counter clock, for `WindowRecord.sub`."""
        return [(name, self.t0 + off, dur) for name, off, dur in self.spans]


class CpuLaps(Laps):
    """`Laps` of one thread's synchronous sections that reads that
    thread's CPU clock (``time.thread_time``) beside ``perf_counter``
    at every lap.  A lap that is work hands its CPU seconds back among
    `timings` as ``<name>_cpu`` with no start: a sub-stage of the
    window's record and a histogram, not an interval of the trace.  A
    lap whose name ends in ``_wait`` has none.  Wall less CPU of a
    section is the time its thread did not run: waiting for the GIL or
    for a core, or asleep in a call that blocks (a transfer to the
    device).  Made and lapped on one thread."""

    __slots__ = ("_c_last", "cpu")

    def __init__(self, seq: int) -> None:
        super().__init__(seq)
        self._c_last = time.thread_time()
        self.cpu: List[Tuple[str, float]] = []  # (name, seconds)

    def lap(self, name: str, then: Optional[str] = None) -> float:
        # (read inside the wall span's two readings, as at the start)
        cpu = time.thread_time()
        now = super().lap(name, then)
        if not name.endswith("_wait"):
            self.cpu.append((name + "_cpu", cpu - self._c_last))
        self._c_last = cpu
        return now

    def timings(self) -> List[Tuple[str, Optional[float], float]]:
        # (the wall spans first: `WindowRecord.lap_parts` takes the
        # first one's start as the instant the work was entered)
        return super().timings() + [
            (name, None, dur) for name, dur in self.cpu
        ]


class _NoLaps:
    """`Laps` with the profiler off: reads no clock, keeps nothing."""

    __slots__ = ()

    def mark(self, name: str) -> None:
        pass

    def lap(self, name: str, then: Optional[str] = None) -> float:
        return 0.0

    def unmark(self) -> None:
        pass

    def now(self) -> float:
        return 0.0

    def nest(self, name: str, start: float) -> None:
        pass

    def timings(self) -> Tuple:
        return ()


NO_LAPS = _NoLaps()


class WindowRecord(Laps):
    """One dispatch window's flight-record entry: stage spans plus
    sizes, the match path taken and the breaker state.  Mutated by
    exactly one window's happens-before chain (collector → executor →
    dispatch loop), so it needs no lock of its own."""

    __slots__ = (
        "wall0", "n_msgs", "n_deliveries", "n_clients", "n_clips",
        "path", "breaker_open", "source", "subs", "e2e_ms", "loop",
        "loop_cpu", "decide_rows", "decide_rows_padded", "sender", "reader",
        "rules_firings", "rules_firings_run", "n_clients_plain",
        "n_host_rows", "n_shared", "n_shared_vector", "gc",
        "residual_filters",
    )

    def __init__(self, seq: int, n_msgs: int, source: str) -> None:
        super().__init__(seq)
        self.wall0 = time.time()
        self.n_msgs = n_msgs
        self.n_deliveries = 0
        self.n_clients = 0
        # of them, the runs the window's columnar pass served whole
        # (`Broker._dispatch_columns`: no exceptional branch taken)
        self.n_clients_plain = 0
        self.n_clips = 0  # compact clips re-matched on the dense kernel
        # rows of a ``dev`` window that a kernel flagged (frontier or
        # match cap passed) and the host trie matched instead
        self.n_host_rows = 0
        # the engine's residual filters (the delta since its last fold,
        # matched on the host trie) at a ``dev`` window's snapshot;
        # None where no snapshot was taken
        self.residual_filters: Optional[int] = None
        # shared-subscription rows the window picked a member for, and
        # those of them one window operation served (the rest went
        # through the scalar redispatch path)
        self.n_shared = 0
        self.n_shared_vector = 0
        # delivery rows the device decide step was given, and the
        # bucket it ran them in (both 0 where the host decided)
        self.decide_rows = 0
        self.decide_rows_padded = 0
        # rows that passed a WHERE on a rule with actions, and those
        # of them that a per-rule SELECT-and-actions run served (the
        # rest went firing by firing through the interpreter)
        self.rules_firings = 0
        self.rules_firings_run = 0
        self.path = ""  # "host" | "dev" | "host-fallback"
        self.breaker_open = False
        self.source = source  # "publish" | "batcher" | "forwarded"
        # nested sub-stages inside a parent span: (name, off, dur),
        # histogrammed like spans and exported nested inside the parent
        # (whose own B/E chain stays contiguous).  ``off`` is None for
        # a caller-accumulated total with no one start (the native
        # ``assemble`` share of ``deliver``); a name may repeat (base
        # and delta automaton) and counts as the sum
        self.subs: List[Tuple[str, Optional[float], float]] = []
        self.e2e_ms: List[float] = []
        # LoopClock growth since the previously committed window, and
        # the loop thread's CPU seconds since the previous window began
        self.loop: Optional[Tuple] = None
        self.loop_cpu = 0.0
        # the native sender thread's own clock over the same stretch:
        # (seconds inside send(2), send calls), None without a sender
        self.sender: Optional[Tuple[float, int]] = None
        # the native reader thread's: (seconds inside recv(2), recv
        # calls, the loop's wake-ups for its batches), None without one
        self.reader: Optional[Tuple[float, int, int]] = None
        # the process's garbage collections over that stretch: (seconds
        # paused, collections), None where no `gc.callbacks` entry is
        # armed (`flightrec.FlightRecorder.arm_watchdog`)
        self.gc: Optional[Tuple[float, int]] = None

    def sub(self, name: str, dur_s: float,
            start: Optional[float] = None) -> None:
        """Record a nested sub-stage: ``start`` is its perf_counter
        start, or None for a caller-accumulated total."""
        self.subs.append(
            (name, None if start is None else start - self.t0, dur_s)
        )

    def nest(self, name: str, start: float) -> None:
        # a record's spans are its contiguous laps alone (the trace
        # export walks them end to end): what nests is a sub-stage
        self.sub(name, time.perf_counter() - start, start)

    def lap_parts(self, name: str, wait: str, entered: float,
                  timings: Sequence[Tuple[str, float, float]]) -> None:
        """Close the span ``name`` whose work another thread entered
        at ``entered`` and timed as ``timings`` (``(name, start, dur)``
        on the perf_counter clock, as `Laps.timings` gives them): the
        time before the first of them is the sub-stage ``wait``, the
        hop from the previous lap's thread to that work."""
        waited_from = self._t_last
        self.lap(name)
        if timings:
            entered = timings[0][1]
        self.sub(wait, entered - waited_from, waited_from)
        for part, start, dur in timings:
            # (a part with no start: a section's CPU seconds, `CpuLaps`)
            self.sub(part, dur, start)

    def sub_totals(self) -> Dict[str, float]:
        """Seconds by sub-stage name (a repeated name summed)."""
        out: Dict[str, float] = {}
        for name, _off, dur in self.subs:
            out[name] = out.get(name, 0.0) + dur
        return out

    def to_dict(self) -> Dict[str, object]:
        loop = {}
        if self.loop is not None:
            # (the turn fields follow where the loop's selector is
            # hooked: `LoopClock.take`)
            fields = LoopClock.FIELDS + LoopClock.TURN_FIELDS
            for field, v in zip(fields, self.loop):
                if field.endswith("_s"):
                    field, v = field[:-2] + "_us", round(v * 1e6, 1)
                loop["loop_" + field] = v
            loop["loop_cpu_us"] = round(self.loop_cpu * 1e6, 1)
        if self.sender is not None:
            loop["sender_send_us"] = round(self.sender[0] * 1e6, 1)
            loop["sender_writes"] = self.sender[1]
        if self.reader is not None:
            loop["reader_recv_us"] = round(self.reader[0] * 1e6, 1)
            loop["reader_recvs"] = self.reader[1]
            loop["reader_wakes"] = self.reader[2]
        if self.gc is not None:
            loop["gc_us"] = round(self.gc[0] * 1e6, 1)
            loop["gc_collections"] = self.gc[1]
        return {
            "seq": self.seq,
            "at": self.wall0,
            "source": self.source,
            "n_msgs": self.n_msgs,
            "n_deliveries": self.n_deliveries,
            "n_clients": self.n_clients,
            "n_clients_plain": self.n_clients_plain,
            "n_clips": self.n_clips,
            "n_host_rows": self.n_host_rows,
            "residual_filters": self.residual_filters,
            "n_shared": self.n_shared,
            "n_shared_vector": self.n_shared_vector,
            "decide_rows": self.decide_rows,
            "decide_rows_padded": self.decide_rows_padded,
            "rules_firings": self.rules_firings,
            "rules_firings_run": self.rules_firings_run,
            "path": self.path,
            "breaker_open": self.breaker_open,
            "stages_us": {
                **{
                    name: round(dur * 1e6, 1)
                    for name, _off, dur in self.spans
                },
                **{
                    name: round(dur * 1e6, 1)
                    for name, dur in self.sub_totals().items()
                },
            },
            "e2e_ms": [round(v, 3) for v in self.e2e_ms[:8]],
            **loop,
        }


def _growth(clock, base: Tuple):
    """An outside clock's ``(seconds, count, ...)`` since ``base`` and
    the reading that is the next base; ``(None, base)`` without a
    clock."""
    if clock is None:
        return None, base
    now = clock()
    return tuple(a - b for a, b in zip(now, base)), now


class LoopClock:
    """What the event loop does between the windows' stages.

    **Reads and writes.**  Every socket read (parse + channel,
    `Connection._read`) and every socket write
    (`Connection._send_packets`) adds its interval and counts here,
    two ``perf_counter`` reads each and none a packet.  A write handed
    to the native sender thread (``egress_writes_sender``) costs the
    loop its serialize and one list append, and each flush scope one
    hand-over (`egress_submit`): the ``send`` itself is on the
    thread's clock, `take_sender`.  ``egress_parked`` counts the
    hand-backs after a socket would not take a write.  A read the
    native reader thread did (``ingress_reads_native``) reached the
    loop in a batch: its ``recv`` is on the thread's clock,
    `take_reader`, with the loop's wake-ups for the batches.

    **The turn clock.**  Once `install` has wrapped the ``select`` of
    the loop's selector (`BrokerServer.start`; `uninstall` in
    ``stop``), the loop thread's wall time is cut, exactly, into five
    phases: at every instant one is running, and `mark` is one
    ``perf_counter`` read that closes the running phase into its total
    and opens the next, so the phases of a stretch add up to its wall
    time by construction.  A clock read a turn or a window, never a
    read, a packet or a message:

    ``poll``   inside ``select``: blocked in the poll, or paying for
               it.  A loop that never waits there is saturated.
    ``recv``   from the turn's first ``data_received`` (`ReadTurn.add`:
               a test a read, a clock read a turn) to the next
               ``select``: the turn's ``recv`` calls and
               ``data_received``s.  The first ``recv`` of a turn is
               before the edge, in ``tail`` (``recv_turns`` counts the
               turns that had the phase, so a reader can correct by a
               ``recv`` a turn); a timer or a write-ready callback
               that the turn runs after its first read is billed here.
               Where the native reader thread reads
               (`ops.sockreader.SockReader`), the phase is its wake-up
               callback, from its start to the last read handed to a
               `ReadTurn`, and the re-arm call behind the turn's run:
               what the loop still pays to receive.
    ``reads``  `ReadTurn._run`: the previous turn's reads handled in a
               row.  ``ingress_s`` is inside it (but for a read that an
               EOF or a limiter's pay-off flushed); the difference is
               the run's own loop.
    ``acks``   a window's publisher acknowledgements, in two parts
               (`PublishBatcher._dispatch_loop` marks both): the cork
               and ``set_result`` pass, and, an iteration later, the
               futures' done-callbacks (`Channel._publish_acked`) and
               the uncork, from a handle queued just ahead of the
               first callback to one queued just behind `_uncork_all`.
               What the loop runs between the two parts (the rest of
               the dispatch task's step, the handles queued before the
               pass: the collector's wake-up among them) keeps its
               own phase.
    ``tail``   everything else: tasks (the collector,
               `_dispatch_loop` and the laps it runs on the loop),
               timers, write-ready callbacks, executor wake-ups, and a
               WebSocket connection's reads (the coroutine path has no
               `ReadTurn`).

    ``turns`` counts the ``select`` calls.  On a loop with no selector
    to wrap (uvloop, a proactor loop) nothing is installed and a
    record carries no turn field, never a zero.  For the trace,
    ``poll`` and ``recv`` intervals are the bursts ``loop_poll_wait``
    and ``loop_recv`` beside ``loop_ingress`` / ``loop_egress``.

    **Collections.**  `attach_gc` takes the clock of the process's one
    ``gc.callbacks`` entry (`flightrec.FlightRecorder`), as
    `attach_sender` takes the sender thread's.

    The totals only grow; `take` hands the growth since the previous
    take to the window being committed, and `stamp_cpu` the loop
    thread's CPU since the previous window began to the one beginning.
    For the trace, intervals less than ``BURST_GAP_S`` apart merge
    into one burst.  Loop thread only, so no lock."""

    FIELDS = (
        "ingress_s", "ingress_reads",
        "ingress_publishes", "ingress_acks", "ingress_acks_run",
        "ingress_publish_s", "ingress_publish_reads",
        "ingress_ack_s", "ingress_ack_reads",
        "egress_s", "egress_writes", "egress_packets",
        "egress_in_window_s",
        "egress_writes_sender", "egress_parked",
        "ingress_reads_direct", "ingress_reads_native",
    )
    # the turn clock's phases, in the order of `_spent`, and its two
    # counters: in a record only while the selector is hooked
    POLL, RECV, READS, ACKS, TAIL = range(5)
    TURN_FIELDS = (
        "poll_s", "recv_s", "reads_s", "acks_s", "tail_s",
        "turns", "recv_turns",
    )
    BURST_GAP_S = 200e-6
    BURSTS_CAP = 65536

    def __init__(self) -> None:
        for field in self.FIELDS:
            setattr(self, field, 0)
        self._base = (0,) * (len(self.FIELDS) + len(self.TURN_FIELDS))
        self.tid: Optional[int] = None  # the loop thread, once known
        self.cpu_s = 0.0  # that thread's CPU clock, as last read
        # a write inside a window's deliver / flush laps is inside
        # those laps too: the broker raises this around them
        self.in_window = False
        # clocks kept elsewhere, while they run, each with the reading
        # of the previous take: the native sender thread's and the
        # process's collections'
        self.sender_clock = None
        self._sender_base = (0.0, 0)
        self.reader_clock = None
        self._reader_base = (0.0, 0, 0)
        self.gc_clock = None
        self._gc_base = (0.0, 0)
        self._bursts: deque = deque(maxlen=self.BURSTS_CAP)
        self._open: Dict[str, List[float]] = {}
        # the turn clock: the selector whose ``select`` is wrapped, the
        # running phase and when it opened, the seconds by phase
        self._sel = None
        self._phase = self.TAIL
        self._t = 0.0
        self._spent = [0.0] * 5
        self.turns = 0
        self.recv_turns = 0

    def ingress(self, t0: float, packets: int, publishes: int,
                acks: int, acks_run: int = 0,
                direct: bool = False, native: bool = False) -> None:
        """One socket read's parse + channel work, begun at ``t0``:
        ``acks_run`` of its ``acks`` crossed as `AckRun`s (a run
        counts as the packets it carries, everywhere here);
        ``direct``: handled in the transport's own callback
        (``ingress_reads_direct``), no task woken for it;
        ``native``: and its ``recv`` done on the reader thread
        (``ingress_reads_native``).  A read
        that held PUBLISH packets alone, or acknowledgements alone,
        is also the cost of its packet type (``ingress_publish_*``,
        ``ingress_ack_*``); a mixed or partial one is in neither."""
        now = time.perf_counter()
        if self.tid is None:
            self.tid = threading.get_ident()
        dt = now - t0
        self.ingress_s += dt
        self.ingress_reads += 1
        if direct:
            self.ingress_reads_direct += 1
            if native:
                self.ingress_reads_native += 1
        if publishes == packets:
            if packets:
                self.ingress_publish_s += dt
                self.ingress_publish_reads += 1
        elif acks == packets:
            self.ingress_ack_s += dt
            self.ingress_ack_reads += 1
        self.ingress_publishes += publishes
        self.ingress_acks += acks
        self.ingress_acks_run += acks_run
        self._burst("loop_ingress", t0, now)

    def egress(self, t0: float, packets: int,
               sender: bool = False) -> None:
        """One socket write, begun at ``t0``: serialize + write, or
        (``sender``) serialize + the append to the scope's batch."""
        now = time.perf_counter()
        self.egress_s += now - t0
        self.egress_writes += 1
        self.egress_packets += packets
        if sender:
            self.egress_writes_sender += 1
        if self.in_window:
            self.egress_in_window_s += now - t0
        self._burst("loop_egress", t0, now)

    def egress_submit(self, t0: float) -> None:
        """One hand-over of a scope's batch to the sender thread,
        begun at ``t0``: the loop's time, no write of its own."""
        now = time.perf_counter()
        self.egress_s += now - t0
        if self.in_window:
            self.egress_in_window_s += now - t0
        self._burst("loop_egress", t0, now)

    # ------------------------------------------------ outside clocks

    def attach_sender(self, clock) -> None:
        """A sender thread started (its clock begins at zero):
        ``clock() -> (seconds inside send(2), send calls)``, both only
        growing; or (None) stopped."""
        self.sender_clock = clock
        self._sender_base = (0.0, 0)

    def take_sender(self) -> Optional[Tuple[float, int]]:
        """The sender thread's clock, its growth since the previous
        take; None while no sender runs."""
        grown, self._sender_base = _growth(
            self.sender_clock, self._sender_base
        )
        return grown

    def attach_reader(self, clock) -> None:
        """A reader thread started (its clock begins at zero):
        ``clock() -> (seconds inside recv(2), recv calls, wake-ups
        taken)``, all only growing; or (None) stopped."""
        self.reader_clock = clock
        self._reader_base = (0.0, 0, 0)

    def take_reader(self) -> Optional[Tuple[float, int, int]]:
        """The reader thread's clock, its growth since the previous
        take; None while no reader runs."""
        grown, self._reader_base = _growth(
            self.reader_clock, self._reader_base
        )
        return grown

    def attach_gc(self, clock) -> None:
        """The process's ``gc.callbacks`` entry is armed (its clock
        begins at zero): ``clock() -> (seconds paused, collections)``;
        or (None) taken off."""
        self.gc_clock = clock
        self._gc_base = (0.0, 0)

    def take_gc(self) -> Optional[Tuple[float, int]]:
        """The collections' clock, its growth since the previous take;
        None while no callback is armed."""
        grown, self._gc_base = _growth(self.gc_clock, self._gc_base)
        return grown

    # ------------------------------------------------ the turn clock

    def install(self, loop) -> bool:
        """Wrap ``select`` of ``loop``'s selector, on the loop thread:
        the turn clock runs from here.  One wrapper a selector, however
        many clocks (two servers on one loop); False where the loop has
        no selector to wrap, and the turn fields stay out of every
        record."""
        sel = getattr(loop, "_selector", None)
        select = getattr(sel, "select", None)
        if select is None or self._sel is not None:
            return False
        clocks = getattr(select, "turn_clocks", None)
        if clocks is None:
            clocks = []

            def hooked(timeout=None, _inner=select):
                for clock in clocks:
                    clock._poll()
                try:
                    return _inner(timeout)
                finally:
                    for clock in clocks:
                        clock._polled()

            hooked.turn_clocks = clocks
            try:
                sel.select = hooked
            except AttributeError:
                return False
        clocks.append(self)
        self._sel = sel
        self.tid = threading.get_ident()
        self._phase = self.TAIL
        self._t = time.perf_counter()
        return True

    def uninstall(self) -> None:
        """Stop the turn clock; the last clock of a selector takes the
        wrapper off."""
        sel, self._sel = self._sel, None
        if sel is None:
            return
        clocks = sel.select.turn_clocks
        clocks.remove(self)
        if not clocks:
            del sel.select  # the class's own method again

    def mark(self, phase: int) -> None:
        """Close the running phase into its total and open ``phase``:
        one clock read.  Nothing while no selector is hooked."""
        if self._sel is None:
            return
        now = time.perf_counter()
        was = self._phase
        self._spent[was] += now - self._t
        if was == self.RECV:
            self._burst("loop_recv", self._t, now)
        self._phase = phase
        self._t = now

    def recv(self) -> None:
        """A ``data_received``, the first of its listener in this
        turn."""
        if self._phase != self.RECV and self._sel is not None:
            self.recv_turns += 1
            self.mark(self.RECV)

    def _poll(self) -> None:
        self.turns += 1
        self.mark(self.POLL)

    def _polled(self) -> None:
        t0 = self._t
        self.mark(self.TAIL)
        self._burst("loop_poll_wait", t0, self._t)

    # ---------------------------------------------------------------

    def _burst(self, name: str, t0: float, t1: float) -> None:
        cur = self._open.get(name)
        if cur is not None and t0 - cur[1] < self.BURST_GAP_S:
            cur[1] = t1
            return
        if cur is not None:
            self._bursts.append((name, cur[0], cur[1]))
        self._open[name] = [t0, t1]

    def stamp_cpu(self) -> float:
        """Read the loop thread's CPU clock, once a window as it
        begins: the seconds it grew since the previous reading (0.0
        off the loop thread, and at the first reading)."""
        if threading.get_ident() != self.tid:
            return 0.0
        cpu = time.thread_time()
        grown = cpu - self.cpu_s if self.cpu_s else 0.0
        self.cpu_s = cpu
        return grown

    def take(self) -> Tuple:
        """The totals' growth since the previous take, in the order of
        ``FIELDS``; while the selector is hooked the turn clock's, in
        the order of ``TURN_FIELDS``, behind them.  Taken on the loop
        thread it closes the running phase's part so far into its
        total (one clock read), so the phases of a record add up to
        the wall time since the record before."""
        hooked = self._sel is not None
        if hooked and threading.get_ident() == self.tid:
            self.mark(self._phase)
        now = tuple(getattr(self, f) for f in self.FIELDS) + tuple(
            self._spent) + (self.turns, self.recv_turns)
        grown = tuple(a - b for a, b in zip(now, self._base))
        self._base = now
        return grown if hooked else grown[:len(self.FIELDS)]

    def bursts(self) -> List[Tuple[str, float, float]]:
        """``(name, start, end)`` on the perf_counter clock, the
        bursts still open included."""
        return list(self._bursts) + [
            (name, cur[0], cur[1]) for name, cur in self._open.items()
        ]

    def reset(self) -> None:
        # (the CPU spent since the last window began is not the next
        # window's: read the clock afresh)
        self.stamp_cpu()
        self.take()
        self.take_sender()
        self.take_reader()
        self.take_gc()
        self._bursts.clear()
        self._open.clear()


def _nest(subs, b_ts: float, e_ts: float) -> List[Tuple[str, str, float]]:
    """B/E events ``(ph, name, ts)`` for one span's sub-stages
    ``(start, dur, name)`` sorted by start: each clamped into what
    encloses it (the span ``[b_ts, e_ts]``, or an earlier sub-stage
    still open), timestamps never decreasing, ends in LIFO order."""
    out: List[Tuple[str, str, float]] = []
    stack: List[Tuple[str, float]] = []  # (name, end) still open
    cursor = b_ts
    for start, dur, name in list(subs) + [(e_ts, 0.0, None)]:
        while stack and stack[-1][1] <= start:
            done, end = stack.pop()
            cursor = max(cursor, end)
            out.append(("E", done, cursor))
        if name is None:
            break
        limit = stack[-1][1] if stack else e_ts
        cursor = min(max(start, cursor), limit)
        out.append(("B", name, cursor))
        stack.append((name, min(cursor + dur, limit)))
    return out


class Profiler:
    """The broker's window profiler: named histograms (one shared
    lock, bulk-recorded per window), the flight-recorder ring, and an
    engine-event ring.  ``enabled=False`` turns the whole thing into
    a no-op (``begin`` returns None and every call site guards)."""

    # stage histograms pre-created so exposition order is stable
    STAGES = (
        "batch_wait", "prepare", "match_submit", "match_wait",
        "dispatch_wait", "replay_read", "expand", "decide", "deliver",
        "assemble", "flush", "rules", "tokenize", "ds_sync", "e2e",
    )

    def __init__(
        self,
        ring_size: int = 256,
        events_cap: int = 256,
        enabled: bool = True,
        process_label: str = "emqx_tpu",
        pid: Optional[int] = None,
    ) -> None:
        self.enabled = enabled
        # explicit process identity for the trace export: without a
        # real pid + node label every node/worker's tracks land under
        # one implicit process and merged multi-node timelines
        # interleave into a single row group
        self.process_label = process_label
        self.pid = pid if pid is not None else os.getpid()
        self._hlock = threading.Lock()  # ONE lock for all histograms
        self._hist: Dict[str, Histogram] = {
            name: Histogram(lock=self._hlock) for name in self.STAGES
        }
        self._ring: List[Optional[WindowRecord]] = [None] * max(ring_size, 1)
        self._ring_lock = threading.Lock()
        self._seq = 0
        # engine lifecycle events: (kind, wall_ts, dur_s, meta)
        self._events: deque = deque(maxlen=max(events_cap, 1))
        # the event loop's socket reads and writes and the phases of
        # its turn; None when disabled (every call site guards, as for
        # ``begin``)
        self.loop: Optional[LoopClock] = LoopClock() if enabled else None
        # one pair of readings puts the perf_counter stamps of the
        # loop's bursts on the wall clock of the windows' ``wall0``
        self._wall_at = (time.time(), time.perf_counter())
        # optional flightrec.FlightRecorder: every committed window is
        # mirrored into its numeric ring (one attribute load + one O(1)
        # append — the black box sees dispatch cadence without a
        # second instrumentation point in the dispatch loops)
        self.flight = None

    # ------------------------------------------------------- windows

    def begin(self, n_msgs: int, source: str = "publish"
              ) -> Optional[WindowRecord]:
        if not self.enabled:
            return None
        with self._ring_lock:
            self._seq += 1
            seq = self._seq
        rec = WindowRecord(seq, n_msgs, source)
        rec.loop_cpu = self.loop.stamp_cpu()
        return rec

    def commit(self, rec: WindowRecord) -> None:
        """Fold a finished window into the histograms (ONE lock for
        every stage sample + the e2e batch) and the ring."""
        rec.unmark()
        lc = self.loop
        if lc is not None:
            rec.loop = lc.take()
            rec.sender = lc.take_sender()
            rec.reader = lc.take_reader()
            rec.gc = lc.take_gc()
            lc.in_window = False
        hist = self._hist
        with self._hlock:
            samples = [(name, dur) for name, _off, dur in rec.spans]
            samples += rec.sub_totals().items()
            for name, dur in samples:
                h = hist.get(name)
                if h is None:
                    h = hist[name] = Histogram(lock=self._hlock)
                h._record_locked(dur * 1e6)
            if rec.e2e_ms:
                e2e = hist["e2e"]
                for v in rec.e2e_ms:
                    e2e._record_locked(v * 1e3)  # ms -> µs
        with self._ring_lock:
            self._ring[rec.seq % len(self._ring)] = rec
        fl = self.flight
        if fl is not None:
            fl.on_window(rec)

    # -------------------------------------------------- stages/events

    def stage(self, name: str, dur_s: float) -> None:
        """One standalone stage sample, of no window (``ds_sync``,
        the engine's lifecycle events)."""
        if not self.enabled:
            return
        with self._hlock:
            h = self._hist.get(name)
            if h is None:
                h = self._hist[name] = Histogram(lock=self._hlock)
            h._record_locked(dur_s * 1e6)

    def event(self, kind: str, dur_s: float, **meta) -> None:
        """Engine lifecycle event (XLA compile, device_put transfer,
        delta fold): histogrammed under ``engine_<kind>`` and kept in
        the event ring for the trace export.  Called from builder /
        fold daemon threads."""
        if not self.enabled:
            return
        self.stage("engine_" + kind, dur_s)
        self.note(kind, dur_s, **meta)

    def note(self, kind: str, dur_s: float, **meta) -> None:
        """An interval that ends now, for the trace export and
        `events` alone: no histogram, so no lock.  What a
        ``gc.callbacks`` entry may call (a ``gc_pause``): a collection
        starts inside any allocation, one made under the histograms'
        lock too."""
        if self.enabled:
            self._events.append((kind, time.time(), dur_s, meta))

    # ---------------------------------------------------- exposition

    def snapshots(self) -> Dict[str, HistogramSnapshot]:
        """Name -> snapshot for every histogram that saw samples,
        pre-created stage families included even when empty (stable
        scrape shape)."""
        with self._hlock:
            items = list(self._hist.items())
        out = {}
        for name, h in items:
            out[name] = h.snapshot()
        return out

    def summary(self) -> Dict[str, Dict[str, object]]:
        return {
            name: snap.to_dict()
            for name, snap in self.snapshots().items()
            if snap.count or name in self.STAGES
        }

    def windows(self, limit: int = 64) -> List[Dict[str, object]]:
        """Most recent committed windows, newest first."""
        return [r.to_dict() for r in self._recent(limit)]

    def _recent(self, limit: int) -> List[WindowRecord]:
        with self._ring_lock:
            recs = [r for r in self._ring if r is not None]
        recs.sort(key=lambda r: r.seq, reverse=True)
        return recs[: max(limit, 0)]

    def events(self, limit: int = 64) -> List[Dict[str, object]]:
        if limit <= 0:
            return []
        out = [
            {"kind": k, "at": ts, "dur_ms": round(d * 1e3, 3), **meta}
            for k, ts, d, meta in list(self._events)
        ]
        return out[-limit:][::-1]

    def reset(self) -> None:
        with self._hlock:
            for h in self._hist.values():
                h._counts = [0] * N_BUCKETS
                h._sum = 0.0
                h._count = 0
        with self._ring_lock:
            self._ring = [None] * len(self._ring)
        self._events.clear()
        if self.loop is not None:
            self.loop.reset()

    # -------------------------------------------------- chrome trace

    # the loop bursts' track: window tracks take their seq, the engine's
    # lifecycle events tid 0
    LOOP_TID = (1 << 31) - 1

    def chrome_trace(self, limit: Optional[int] = None) -> Dict[str, object]:
        """The flight recorder as Chrome trace-event JSON (the format
        Perfetto and chrome://tracing load natively): every window is
        its own thread track with paired B/E events per stage (windows
        pipeline, so tracks may overlap in time — per-track events
        stay strictly nested) and each sub-stage that has a start
        nested inside its parent; engine lifecycle events and
        collections over the stall threshold (``gc_pause``) ride tid 0
        and the event loop's bursts a track of their own
        (``loop_ingress`` / ``loop_egress``: reads and writes;
        ``loop_poll_wait``: inside ``select``; ``loop_recv``: a turn's
        ``recv`` calls), both as complete ("X") events."""
        recs = self._recent(limit if limit is not None else len(self._ring))
        recs.reverse()  # oldest first: ts ordering within each track
        wall_at, perf_at = self._wall_at
        others = [
            (kind, ts - dur, ts, 0, dict(meta))
            for kind, ts, dur, meta in list(self._events)
        ]
        if self.loop is not None:
            others += [
                (name, wall_at + (t0 - perf_at), wall_at + (t1 - perf_at),
                 self.LOOP_TID, {})
                for name, t0, t1 in self.loop.bursts()
            ]
        # export timestamps RELATIVE to the trace's own epoch: at
        # absolute epoch-µs magnitude (1.7e15) a float64 has ~0.25 µs
        # of quantization, enough to flip adjacent span edges out of
        # order; small relative values keep full sub-µs precision.
        # The epoch is the oldest exported window's start, so a reader
        # puts the export back on the wall clock from the ring alone:
        # what began earlier is clipped to it and never moves it
        if recs:
            epoch = recs[0].wall0
        else:
            epoch = min((o[1] for o in others), default=0.0)
        pid = self.pid
        events: List[Dict[str, object]] = [
            {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": (
                 f"emqx_tpu window pipeline [{self.process_label} "
                 f"pid={pid}]"
             )}},
            {"name": "process_sort_index", "ph": "M", "pid": pid,
             "tid": 0, "args": {"sort_index": pid}},
            {"name": "thread_name", "ph": "M", "pid": pid,
             "tid": self.LOOP_TID,
             "args": {"name": "event loop: poll, reads and writes"}},
        ]
        for rec in recs:
            tid = rec.seq
            base_us = (rec.wall0 - epoch) * 1e6
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": f"window {rec.seq} ({rec.source})"},
            })
            subs = sorted(
                ((base_us + off * 1e6, max(dur, 0.0) * 1e6, name)
                 for name, off, dur in rec.subs if off is not None),
                key=lambda s: (s[0], -s[1]),
            )
            k = 0
            # laps run contiguously from the record's start
            # (`Laps.lap`), so each boundary is computed ONCE: a lap's
            # E and the next lap's B are the same float.  (Taken from
            # offsets, the two disagree by an ulp now and then.)
            cursor = base_us
            for name, _off, dur in rec.spans:
                b_ts = cursor
                e_ts = b_ts + max(dur, 0.0) * 1e6
                args = {
                    "n_msgs": rec.n_msgs,
                    "path": rec.path,
                    "breaker_open": rec.breaker_open,
                }
                events.append({
                    "name": name, "ph": "B", "pid": pid, "tid": tid,
                    "ts": b_ts, "args": args,
                })
                # the sub-stages that start inside this span
                k1 = k
                while k1 < len(subs) and subs[k1][0] < e_ts:
                    k1 += 1
                for ph, sub_name, ts in _nest(subs[k:k1], b_ts, e_ts):
                    events.append({
                        "name": sub_name, "ph": ph, "pid": pid,
                        "tid": tid, "ts": ts,
                    })
                k = k1
                cursor = e_ts
                events.append({
                    "name": name, "ph": "E", "pid": pid, "tid": tid,
                    "ts": e_ts,
                })
        for name, start, end, tid, args in others:
            if end < epoch:
                continue
            start = max(start, epoch)
            events.append({
                "name": name, "ph": "X", "pid": pid, "tid": tid,
                "ts": (start - epoch) * 1e6, "dur": (end - start) * 1e6,
                "args": args,
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}


# ------------------------------------------------- prometheus helpers

_PROM_BAD = re.compile(r"[^a-zA-Z0-9_:]")


def prom_name(name: str) -> str:
    """Sanitize a dotted metric name into a valid Prometheus metric
    name: ``.``/``-`` and anything else outside [a-zA-Z0-9_:] become
    ``_``, and a leading digit gets a ``_`` prefix (counter names like
    ``5xx.responses`` would otherwise emit an unparseable family)."""
    out = _PROM_BAD.sub("_", name)
    if not out or out[0].isdigit():
        out = "_" + out
    return out


def prom_histogram_lines(
    family: str, snap: HistogramSnapshot, help_text: str = ""
) -> List[str]:
    """One Prometheus text-format histogram family: cumulative
    ``_bucket`` samples with ``le`` labels, then ``_sum``/``_count``."""
    lines = [
        f"# HELP {family} {help_text or family}",
        f"# TYPE {family} histogram",
    ]
    cum = 0
    for i, c in enumerate(snap.counts):
        cum += c
        le = str(BOUNDS[i]) if i < len(BOUNDS) else "+Inf"
        lines.append(f'{family}_bucket{{le="{le}"}} {cum}')
    lines.append(f"{family}_sum {snap.sum}")
    lines.append(f"{family}_count {snap.count}")
    return lines
