"""MatchEngine: the subscription-matching core, TPU-accelerated.

Mirrors the reference's v2 router split (/root/reference/apps/emqx/src/
emqx_router.erl:476-525): exact (non-wildcard) filters in an O(1) host
hash map (`?ROUTE_TAB` direct lookup), wildcard filters in an index —
here a device-resident array automaton batch-matched by
`ops.match_kernel`, not an ordered-set skip-scan.

Subscription churn vs XLA immutability (SURVEY §7 "hard parts") is
handled the way `emqx_router_syncer` batches route ops: mutations land
in a host-side *delta* trie immediately (correct from the next match on)
and are folded into a rebuilt device automaton once the delta passes a
threshold.  Deletions are masked out of stale device results by fid.

Any topic the kernel flags (frontier overflow, match-cap overflow, too
deep) is re-matched on the `HostTrie` oracle, so results are always
exact regardless of kernel capacity bounds.  Those rows are counted
(``stats()["host_rows"]``, a window's ``info["host_rows"]``) and timed
(the span ``overlay_host``), and a build whose table can need a wider
frontier than ``f_width`` (`Automaton.frontier_need`, also in
``stats()``) logs one warning: a window that reads ``dev`` may still
have had most of its rows matched here on the host.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Hashable, List, Optional, Sequence, Set, Tuple

import numpy as np

from . import failpoints
from . import topic as T
from .observability import NO_LAPS, CpuLaps
from .tp import tp
from .ops.automaton import Automaton, build_automaton
from .ops.dictionary import SENTINEL, TokenDict, encode_topics
from .ops.trie_host import HostTrie
from .ops.trie_native import make_trie


def _pow2_at_least(n: int, minimum: int) -> int:
    cap = minimum
    while cap < n:
        cap *= 2
    return cap


# `decide_batch` compiles one program a shape triple (attribute-column
# length, delivery rows, window messages).  The served path keeps that
# set small enough to compile before traffic: the message columns are
# padded to the one width `warmup()` was given, the delivery rows to a
# power of two from `_DEC_ROWS_MIN` (12 KB of indices: padding a small
# window up to it costs nothing against the round trip), the router's
# attribute columns to a rung of `_DEC_COLS_MIN` x 4^k.
_DEC_ROWS_MIN = 1024
_DEC_COLS_MIN = 4096
# rows warmed per message of the `warmup()` width: a full window at 32
# deliveries a publish; a wider window warms its successor as it runs
_DEC_ROWS_PER_MSG = 32
# triples this process has compiled (the jit cache is the process's,
# not an engine's)
_DEC_WARMED: Set[Tuple[int, int, int]] = set()


def _dec_cols_rung(n: int) -> int:
    cap = _DEC_COLS_MIN
    while cap < n:
        cap *= 4
    return cap


def _pad_to(a, cap: int, fill, dtype) -> np.ndarray:
    out = np.full(cap, fill, dtype=dtype)
    out[: len(a)] = a
    return out


def _pad_batch(tokens, lengths, dollar):
    """Pad the batch to a power-of-two bucket so XLA sees a bounded set
    of shapes (no recompile storm on ragged publish batches)."""
    b = tokens.shape[0]
    bp = _pow2_at_least(b, 16)
    if bp != b:
        pad = bp - b
        tokens = np.pad(tokens, ((0, pad), (0, 0)), constant_values=-4)
        lengths = np.pad(lengths, (0, pad))  # length 0 => inert row
        dollar = np.pad(dollar, (0, pad), constant_values=True)
    return tokens, lengths, dollar


def _pad_nodes_pow2(aut: Automaton, minimum: int = 16) -> None:
    """Pad the node table to a power-of-two capacity class: rebuild N ->
    N+delta then only crosses a traced-shape boundary when capacity
    doubles, so XLA reuses the compiled kernel instead of recompiling
    after every rebuild.  Padded rows are inert (no '+' child, no
    terminal flags) and unreachable (no edges point at them)."""
    n = aut.node_rows.shape[0]
    cap = _pow2_at_least(n, minimum)
    if cap != n:
        pad = np.zeros((cap - n, 8), np.int32)
        pad[:, 0] = int(SENTINEL)
        pad[:, 4] = -1  # no incoming edge: verification-dead
        pad[:, 5] = -1
        aut.node_rows = np.concatenate([aut.node_rows, pad])


# The device rules kernel keeps four [S, R, W] register planes (one
# f32, three bool: 7 bytes a cell) plus about half as much again in
# temporaries.  2^27 cells is ~1.4 GB of a 16 GB chip beside the
# resident automaton; the 10k-rule bucket at a full window (2^30
# cells, 11 GB of temporaries by the compiler's own account) is past
# it and evaluates on the host twin.
RULES_DEV_MAX_CELLS = 1 << 27


def _rules_cells(stack, w_n: int) -> int:
    """Cells of the padded register file `rules_eval_batch` would hold
    for ``stack`` over a ``w_n``-message window."""
    return (
        stack.n_steps * _pow2_at_least(stack.n_rules, 8)
        * _pow2_at_least(w_n, 16)
    )


# the persistent compile cache's home when JAX_COMPILATION_CACHE_DIR is
# unset: one absolute path inside the checkout, derived from the
# package's location — the directory is part of the cache key, so a
# path that moved with the cwd would never hit
_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "data", "xla_cache",
)


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache and return its
    directory (None when it could not be enabled).  A first-use XLA
    compile of a new automaton capacity class takes seconds and stalls
    concurrent matches on the backend; with the on-disk cache each
    shape class compiles once EVER (across restarts), so a production
    broker's rebuild ladder warms from disk in milliseconds.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and
    no directory is set in code.  Every program is kept, however short
    its compile: at JAX's 0.5 s floor a second run on the chip compiled
    11 of the 34 warmed match programs anew (the narrow batch buckets
    compile faster than that).  Safe to call repeatedly."""
    import jax

    try:
        path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not path:
            path = _COMPILE_CACHE_DIR
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        return path
    except Exception:
        import logging

        logging.getLogger("emqx_tpu.engine").warning(
            "persistent compilation cache unavailable", exc_info=True
        )
        return None


def _validate_filter(flt: str):
    """Fused split + validate + wildcard classification via C-speed
    string counts (a per-level Python loop was ~20% of the insert hot
    path): every '+'/'#' must be a WHOLE level — true iff its count in
    the string equals the count of levels that are exactly that
    character — and the single '#' must be the last level.  Returns
    ``(words, is_wildcard, n_hash)``; raises before any mutation."""
    ws = tuple(flt.split("/"))
    if (
        not flt
        or "\x00" in flt
        or len(flt) > 65535
        or (len(flt) > 16383 and len(flt.encode()) > 65535)
    ):
        raise ValueError(f"invalid topic filter: {flt!r}")
    n_hash = flt.count("#")
    n_plus = flt.count("+")
    wild = bool(n_hash or n_plus)
    if wild:
        if n_plus != ws.count("+"):
            raise ValueError(f"wildcard not a whole level: {flt!r}")
        if n_hash:
            if n_hash != 1 or ws[-1] != "#":
                raise ValueError(f"'#' not a whole last level: {flt!r}")
    return ws, wild, n_hash


def make_fid_arr(fids: List[Hashable]) -> np.ndarray:
    """Position -> fid, vectorized-indexable: int64 fast path when every
    fid is an int; object fallback (filled by assignment so tuple fids
    stay 1-D, not broadcast)."""
    if fids and all(type(f) is int for f in fids):
        return np.array(fids, np.int64)
    arr = np.empty(len(fids), object)
    arr[:] = fids
    return arr


class _EncArena:
    """Append-only encode arena: the incremental build cache.

    Row arrays (token matrix, body length, hash flag, fid) grow by
    doubling; a deleted or superseded filter's row is DEAD-MARKED
    (``blen = -1`` — ``blen == 0`` is a LIVE bare-'#' filter;
    `assemble_automaton` keeps rows with ``blen >= 0``) instead of
    compacted, so applying a delta is O(delta) with NO full-array
    copies (the previous keep-mask + ``np.concatenate`` scheme copied
    ~64 MB per rebuild at 1M filters while holding the GIL — a 40-50 ms
    publish-visible stall under churn).  Row positions are stable for
    the arena's lifetime, so a live automaton's ``code_idx``/``fid``
    views stay valid while later generations append.

    Single-writer: all mutation happens in whichever builder thread
    holds the engine's ``_enc_lock``; matching never touches the arena.
    """

    __slots__ = ("max_levels", "mat", "blen", "ish", "flist", "fids",
                 "rows", "dead")

    def __init__(self, max_levels: int, cap: int = 1024) -> None:
        from .ops.dictionary import PAD_TOK

        self.max_levels = max_levels
        self.mat = np.full((cap, max_levels), PAD_TOK, np.int32)
        self.blen = np.zeros(cap, np.int32)
        self.ish = np.zeros(cap, bool)
        self.flist: List[Tuple[Hashable, Tuple[str, ...]]] = []
        self.fids = np.zeros(cap, np.int64)
        self.rows: Dict[Hashable, int] = {}  # live fid -> row
        self.dead = 0

    @property
    def used(self) -> int:
        return len(self.flist)

    def _grow(self, need: int) -> None:
        from .ops.dictionary import PAD_TOK

        cap = len(self.blen)
        while cap < need:
            cap *= 2
        if cap == len(self.blen):
            return
        mat = np.full((cap, self.max_levels), PAD_TOK, np.int32)
        # chunked copy with yields: one big memcpy holds the GIL
        step = 1 << 16
        for i in range(0, self.used, step):
            j = min(i + step, self.used)  # dest is LARGER: clip both
            mat[i:j] = self.mat[i:j]
            time.sleep(0)
        self.mat = mat
        self.blen = np.resize(self.blen, cap)
        self.ish = np.resize(self.ish, cap)
        if self.fids.dtype == object:
            f2 = np.empty(cap, object)
            f2[: self.used] = self.fids[: self.used]
            self.fids = f2
        else:
            self.fids = np.resize(self.fids, cap)

    def _set_fid(self, row: int, fid: Hashable) -> None:
        if self.fids.dtype != object and type(fid) is not int:
            obj = np.empty(len(self.fids), object)
            obj[: self.used] = self.fids[: self.used].tolist()
            self.fids = obj
        self.fids[row] = fid

    def apply(self, items, dropped_fids, tdict) -> None:
        """Dead-mark ``dropped_fids`` and rows superseded by ``items``,
        then encode+append ``items``.  Yields the GIL every few
        thousand rows — this runs in a background builder thread and a
        long pure-Python burst would stall the insert/publish thread."""
        from .ops.dictionary import encode_filter

        for fid in dropped_fids:
            r = self.rows.pop(fid, None)
            if r is not None:
                self.blen[r] = -1  # dead marker (0 = live bare '#')
                self.dead += 1
        self._grow(self.used + len(items))
        u0 = self.used
        n_items = len(items)
        batch = n_items >= 64 and tdict.encode_filters_into(
            items, self.max_levels,
            self.mat[u0:u0 + n_items], self.blen[u0:u0 + n_items],
            self.ish[u0:u0 + n_items],
        )
        n = 0
        for fid, ws in items:
            r = self.rows.get(fid)
            if r is not None:  # re-insert supersedes the old row
                self.blen[r] = -1
                self.dead += 1
            row = u0 + n if batch else self.used
            if not batch:
                body, hsh = encode_filter(tdict, ws)
                if len(body) > self.max_levels:
                    raise ValueError(
                        f"filter deeper than max_levels="
                        f"{self.max_levels}: {ws}"
                    )
                if row >= len(self.blen):
                    self._grow(row + 1)
                self.mat[row, : len(body)] = body
                self.blen[row] = len(body)
                self.ish[row] = hsh
            self._set_fid(row, fid)
            self.flist.append((fid, ws))
            self.rows[fid] = row
            n += 1
            if n % 1024 == 0:
                time.sleep(0)  # let the insert thread breathe
        if self.dead > max(self.used // 2, 4096):
            self._compact(tdict)

    def _compact(self, tdict) -> None:
        """Occasional dead-row sweep (amortized by the 50% trigger):
        rebuilds the arena from its live rows so sustained
        insert+delete churn cannot grow it without bound."""
        live = sorted(self.rows.items(), key=lambda kv: kv[1])
        fresh = _EncArena(self.max_levels, cap=max(len(live) * 2, 1024))
        items = [(fid, self.flist[r][1]) for fid, r in live]
        fresh.apply(items, (), tdict)
        for name in ("mat", "blen", "ish", "flist", "fids", "rows"):
            setattr(self, name, getattr(fresh, name))
        self.dead = 0

    def views(self):
        """(mat, blen, ish, flist) views for `assemble_automaton` —
        zero-copy; positions align with `fid_view`."""
        u = self.used
        return self.mat[:u], self.blen[:u], self.ish[:u], self.flist

    def fid_view(self) -> np.ndarray:
        """Stable position->fid array for the CURRENT used span (valid
        even as later generations append, until a capacity doubling
        replaces the buffer — which leaves this view's buffer intact)."""
        return self.fids[: self.used]


_NO_DTIER = (None, None, None)  # no delta automaton folded yet

# the delta fold's capacity class: at least these node rows and
# buckets, and a step to this many times what a fold needs
# (`MatchEngine._fold_class_for`)
_FOLD_MIN_NODES = 4096
_FOLD_MIN_BUCKETS = 2048
_FOLD_ROOM = 4

# `stats()` counters of the route batches: batches applied and their
# ops, the applying thread's seconds waiting for ``_mlock`` and holding
# it, and what the caller reported of its acknowledgements
# (`note_route_acks`: seconds from a SUBSCRIBE / UNSUBSCRIBE to its
# ack, and its filters)
_ROUTE_STATS = ("route_batches", "route_ops", "route_wait_s",
                "route_apply_s", "route_ack_s", "route_ack_filters")
# the delta folds' phases, each timed on the fold's own thread (the
# capture on the thread that started it, under ``_mlock``)
_FOLD_PHASES = ("capture", "encode", "assemble", "upload", "warm",
                "commit")
# folds committed, all folds' wall seconds, the phases' seconds and the
# bytes the folds handed to the device
_FOLD_STATS = ("folds", "fold_s") + tuple(
    "fold_" + p + "_s" for p in _FOLD_PHASES) + ("fold_upload_bytes",)


class _ResidualView:
    """Read view of "wildcard filters inserted after the fold
    watermark", backed by the seq-tagged `_wild` trie — the overlay's
    stand-in for the residual trie that no longer exists.  `__len__`
    is the skip-check and must never under-count for THIS view's
    watermark (a fold adopting mid-batch moves the engine's live
    counter down, but entries between this snapshot's watermark and
    the new one are only covered by the NEW automaton, not the
    snapshot's) — so it reports the seq-span upper bound, which only
    inserts advance."""

    __slots__ = ("_wild", "_min_seq")

    def __init__(self, wild, watermark: int) -> None:
        self._wild = wild
        self._min_seq = watermark + 1

    def __len__(self) -> int:
        return max(self._wild.last_seq() - self._min_seq + 1, 0)

    def match_words(self, ws) -> Set[Hashable]:
        return self._wild.match_since_words(ws, self._min_seq)


class MatchEngine:
    """Mutable filter set with batched matching.

    ``use_device=None`` (default) auto-enables the JAX path when any
    wildcard filters exist; ``False`` forces pure-host matching (the
    reference-equivalent CPU path kept as fallback per BASELINE.json).
    """

    def __init__(
        self,
        max_levels: int = 16,
        f_width: int = 8,
        m_cap: int = 128,
        rebuild_threshold: int = 4096,
        use_device: Optional[bool] = None,
        background_rebuild: bool = False,
        delta_aut_threshold: int = 1024,
        delta_fold_factor: int = 2,
    ) -> None:
        self.max_levels = max_levels
        self.f_width = f_width
        self.m_cap = m_cap
        self.rebuild_threshold = rebuild_threshold
        self.use_device = use_device
        self.background_rebuild = background_rebuild
        # wired by the broker's overload ladder (olp L1): a truthy
        # return defers scheduling a background rebuild — the delta
        # tiers keep serving correctness, and the first post-recovery
        # mutation past the threshold triggers it.  Must be cheap and
        # non-raising; may be called with engine locks held.
        self.defer_rebuild = None
        self.delta_aut_threshold = delta_aut_threshold
        # fold when the residual reaches delta/factor: a smaller factor
        # folds less often (less background assemble stealing the GIL
        # from the insert thread), at the cost of a larger host-matched
        # residual between folds — profiled best at 2 for sustained
        # 100k-scale churn
        self.delta_fold_factor = delta_fold_factor
        self._exact: Dict[str, Set[Hashable]] = {}
        self._wild = make_trie()  # full wildcard set: fallback + rebuild source
        # wildcard filters added since last build: fid -> words.  A
        # plain dict (0.2 us insert), because matching against the delta
        # always goes through either the folded delta automaton or the
        # watermark residual view on _wild — never this map directly.
        self._delta: Dict[Hashable, Tuple[str, ...]] = {}
        self._deep = make_trie()  # filters too deep for the device index
        self._by_fid: Dict[Hashable, str] = {}
        # per-generation tombstones: a delete masks the fid only in the
        # snapshot(s) that still carry its stale entry.  Folds/rebuilds
        # REPLACE these sets (never mutate in place) so an in-flight
        # match's captured snapshot stays internally consistent.
        self._deleted_base: Set[Hashable] = set()
        self._deleted_daut: Set[Hashable] = set()
        self._tdict = TokenDict()
        self._aut: Optional[Automaton] = None
        self._dev: Optional[Tuple] = None  # device copies of table arrays
        self._n_base = 0  # live filters in the base snapshot
        # encode arena of the base builds: in-place incremental
        # re-encode of only the delta (`_EncArena`)
        self._build_cache: Optional[_EncArena] = None
        # device-resident DELTA automaton (VERDICT r3 task: the churn
        # fix).  The host delta overlay is O(delta) per topic — the
        # scaling cliff during a long base rebuild.  Instead the delta
        # folds into a SECOND, small automaton matched on-device next to
        # the base; only the residual since its last build stays
        # host-matched.  Rebuild cadence is geometric
        # (max(threshold, |delta|/4)) so build work amortizes O(1) per
        # insert, and tables pad to power-of-two capacity classes so
        # XLA re-uses a bounded set of compiled shapes instead of
        # recompiling per build.
        # (automaton, device tables, position->fid array): ONE
        # attribute, replaced in one store, so no reader can see the
        # parts of two generations
        self._dtier: Tuple = _NO_DTIER
        self._daut_fids: Set[Hashable] = set()
        self._fold_cache: Optional[_EncArena] = None  # fold encode arena
        # STICKY fold capacity class, (node rows, buckets) of the
        # committed folds (None before the first): each new shape costs
        # a compile or an executable load on the backend, behind which
        # concurrent matches queue; never shrinking the ladder across
        # rebuilds means each class loads once per process instead of
        # once per rebuild cycle (`_fold_class_for`)
        self._fold_class: Optional[Tuple[int, int]] = None
        # The residual ("delta since the last fold") is NOT a second
        # trie: `_wild` tags every insert with a monotonically
        # increasing sequence number, and the residual is simply the
        # view "seq > _fold_watermark" (`match_since_words`).  A fold
        # then costs one watermark bump instead of a residual-trie
        # rebuild, and each insert pays ONE native trie insert, not two.
        self._fold_watermark = 0
        self._residual_count = 0
        # append-only (fid, seq) log of inserts past the watermark; the
        # fold work-list derives from it in O(residual), and adopt
        # prunes it to the entries past the new watermark
        self._residual_log: List[Tuple[Hashable, int]] = []
        self._delta_seq: Dict[Hashable, int] = {}  # fid -> latest seq
        # async fold state: the assemble runs OFF the insert thread
        # (VERDICT r2 weak #4: a synchronous fold added ~170 ms stalls
        # to the insert path at 100k-delta scale).  `_fold_gen` guards
        # adoption — any base swap/rebuild bumps it, discarding an
        # in-flight fold whose inputs predate the new base.
        self._folding = False
        self._fold_async = True  # tests pin False for strict bounds
        self._fold_gen = 0
        self._fold_thread: Optional[threading.Thread] = None
        self._fold_deletes: Set[Hashable] = set()
        # background (double-buffered) rebuild state: the builder thread
        # assembles a new snapshot while matching continues on the live
        # one — the `emqx_router_syncer` no-stop-the-world property
        # (/root/reference/apps/emqx/src/emqx_router_syncer.erl:58)
        self._lock = threading.Lock()
        # serializes host-side mutation vs. the overlay/encode phases of
        # a match running on another thread (the PublishBatcher runs the
        # device step in an executor so the event loop keeps reading
        # sockets); the kernel call itself runs OUTSIDE this lock on an
        # immutable snapshot, so a SUBSCRIBE never waits on the device
        self._mlock = threading.RLock()
        # levels -> [ws->row-index dict, token matrix, lengths,
        # dollar, rows-used] (see _encode_rows)
        self._enc_cache: Dict[int, list] = {}
        # guards the encode cache: _encode_rows runs OUTSIDE _mlock
        # (the device step is deliberately lock-free), so two
        # concurrent match batches must not interleave row assignment
        self._enc_mutex = threading.Lock()
        self._enc_gen = 0
        # serializes TokenDict-mutating encodes (fold thread vs rebuild
        # snapshot): two concurrent encode_filters would interleave
        # TokenDict.add's check-then-act and could alias token ids
        self._enc_lock = threading.Lock()
        self._building = False
        self._rebuild_snap_seq = 0  # wild seq at the build snapshot
        self._built: Optional[Tuple] = None  # (aut, dev, fid_arr, base_fids)
        self._build_thread: Optional[threading.Thread] = None
        self._pending_inserts: List[Tuple[str, Hashable]] = []
        self._pending_deletes: Set[Hashable] = set()
        # ---- adaptive path policy (use_device=None, "auto") ----
        # The deployed broker must never be SLOWER with the device on
        # (VERDICT r4 weak #1): auto picks per window from measured
        # costs.  Latency mode (queue shallow) compares wall times —
        # a small window matches on the host trie in microseconds,
        # under one device round-trip, and the crossover falls as the
        # round-trip shortens.  Throughput mode (congested)
        # compares HOST-SIDE CPU only: pipelining hides the device
        # round-trip, so offloading the match frees the one resource a
        # saturated single-core broker is starved of.
        self._host_us: Optional[float] = None   # host µs/topic EWMA
        self._dev_cpu_us: Optional[float] = None  # device-path host CPU
        self._dev_window_s: Optional[float] = None  # device window wall
        self._auto_stats = {"host_windows": 0, "dev_windows": 0,
                            "probes": 0}
        self._auto_seq = 0
        self._warmup_force = False
        # out-of-band device probing: when the policy is choosing host,
        # a one-shot background thread re-measures the device path
        # every ~10 s over a sample of RECENT REAL topics — never as
        # head-of-line latency in the live window stream (an in-band
        # probe window delays the ordered dispatch of everything
        # behind it by a full device round-trip)
        self._probe_topics: List[str] = []
        # first refresh waits a full interval: warmup() seeds the
        # estimates at boot, and an immediate probe lands exactly in
        # the first traffic burst (measured: one background probe ate
        # ~40% of a 1.5s flood on a single-core host)
        self._probe_last = time.monotonic()
        self._probe_running = False
        # compact-transfer capacity multiplier (x unique topics in the
        # window); doubles whenever the buffer clips, never shrinks.
        # A step leaves the compact kernel cold at every batch bucket
        # (c_cap is part of its shape) until a window of that bucket
        # comes and compiles it in its own latency, so the ladder
        # starts where a window of full-width unique topics with a
        # fan-in of 8 fits: `warmup()` compiles that rung at every
        # bucket, and a fleet whose topics match two or three filters
        # each never leaves it (it climbed 2 -> 4 at a moment the
        # traffic chose, and the next wide window paid 0.3-0.9 s).
        # 32 B a padded row on the device->host link, against 512 B
        # for the dense layout
        self._ccap_mult = 8
        # rows of ``dev`` windows that a kernel flagged and `_overlay`
        # handed to the host trie (bumped under ``_mlock``)
        self._host_rows = 0
        # (nodes, buckets, levels, batch) classes already shape-warmed
        self._warmed_shapes: Set[Tuple[int, int, int, int]] = set()
        # widest batch bucket the background fold/build threads warm a
        # new automaton for; `warmup()` raises it to the served width
        self._warm_batch = 16
        # ---- window decide step (dispatch decision columns) --------
        # The dispatch half's per-delivery decisions compute as one
        # vectorized pass (ops.match_kernel.decide_batch + its numpy
        # twin); host-vs-device resolves per window from per-delivery
        # cost EWMAs the same way `_auto_choose` does for matching,
        # and device faults feed the SAME circuit breaker, so 100%
        # device failure degrades both steps to host together.
        self.decide_force: Optional[str] = None  # "host"/"dev" pin (tests)
        self._dec_host_us: Optional[float] = None  # µs/delivery EWMAs
        self._dec_dev_us: Optional[float] = None
        self._dec_stats = {"host_windows": 0, "dev_windows": 0,
                           "dev_errors": 0}
        # (rev, dev arrays padded to a `_dec_cols_rung`, the rung)
        self._dec_cols_cache: Optional[Tuple] = None
        # what `_warm_decide` keeps compiled: the attribute-column
        # rungs seen so far, and delivery-row buckets up to this many
        self._dec_rungs: Set[int] = {_DEC_COLS_MIN}
        self._dec_rows_warm = _DEC_ROWS_MIN
        self._dec_warm_thread: Optional[threading.Thread] = None
        # EWMA hygiene: the FIRST device decide window pays the JIT
        # compile and must not poison the cost estimate, and a rare
        # in-band re-probe keeps it fresh while host is winning (the
        # step is micro-scale, so no out-of-band probe thread is
        # warranted the way matching's is)
        self._dec_dev_warm = False
        self._dec_seq = 0
        self._dec_probe_seq = 0
        # ---- rules x window matrix step (rule-engine predicates) ---
        # The rule engine's stacked WHERE programs (rules/predicate.py
        # StackedRules) evaluate over the window's shared column
        # planes as one rules x window boolean matrix
        # (ops.match_kernel.rules_eval_host / rules_eval_batch).
        # Host-vs-device resolves per window from per-CELL (rule x
        # message) cost EWMAs, device faults feed the SAME PR 1
        # breaker, and the device path additionally gates on f32
        # safety (the kernel computes in float32; arith programs and
        # f32-lossy columns stay on the float64 host twin).
        self.rules_force: Optional[str] = None  # "host"/"dev" pin
        self._rul_host_us: Optional[float] = None  # µs/cell EWMAs
        self._rul_dev_us: Optional[float] = None
        self._rul_stats = {"host_windows": 0, "dev_windows": 0,
                           "dev_errors": 0, "dev_refused": 0}
        self._rul_prog_cache: Optional[Tuple] = None  # (rev, arrays)
        # installed by the broker: () -> (StackedRules, rev) | None,
        # the registry's current WHERE program, so `warmup()` can
        # compile the rules kernel before traffic the way it compiles
        # the match buckets
        self.rules_source = None
        self._rul_dev_warm = False
        self._rul_seq = 0
        self._rul_probe_seq = 0
        # ---- device-path circuit breaker (failure-driven degradation)
        # The auto policy above switches paths on measured COST; the
        # breaker switches on FAILURE: `breaker_threshold` consecutive
        # device-step exceptions (XLA compile/OOM, a lost device) — or a
        # window exceeding `breaker_deadline` seconds of wall, the
        # watchdog — trip matching to host-only.  A background probe
        # re-tries the device every `breaker_probe_interval` seconds
        # and re-closes the breaker on success.  The broker wires the
        # trip/clear callbacks into its AlarmRegistry ($SYS alarm) and
        # metrics.
        self.breaker_threshold = 3
        self.breaker_probe_interval = 5.0
        self.breaker_deadline: Optional[float] = 30.0
        self.on_breaker_trip = None  # callable(info_dict)
        self.on_breaker_clear = None  # callable(info_dict)
        self._brk_failures = 0  # consecutive device-step failures
        self._brk_open = False
        self._brk_opened_at = 0.0
        self._brk_probe_last = 0.0
        self._brk_probing = False
        self._brk_stats = {"trips": 0, "device_errors": 0,
                           "slow_windows": 0, "probes": 0}
        # observability.Profiler installed by the broker: lifecycle
        # events (XLA shape compiles, device_put transfer bytes, delta
        # folds, rebuilds) + the tokenize stage histogram.  None =
        # zero-cost no-op (standalone engines, benches)
        self.profiler = None
        # route batches (`apply_routes`) and folds, counted for
        # ``stats()``: seconds here, whole microseconds there
        self._route_stats = dict.fromkeys(_ROUTE_STATS, 0)
        self._fold_stats = dict.fromkeys(_FOLD_STATS, 0)

    # ------------------------------------------------------------- mutation

    def insert(self, flt: str, fid: Hashable) -> None:
        with self._mlock:
            # _mlock IS the mutation/snapshot serialization for the
            # native token matrix the call mutates with the GIL
            # released — holding it across the native span is the
            # design, not an accident
            # brokerlint: ignore[LOCK402]
            self._insert_locked(flt, fid)

    def insert_many(self, pairs: Sequence[Tuple[str, Hashable]]) -> None:
        """Windowed batch insert — the `emqx_router_syncer` shape
        (route ops land in batches of up to ?MAX_BATCH_SIZE,
        /root/reference/apps/emqx/src/emqx_router_syncer.erl:58): one
        lock acquisition and ONE GIL-released native trie call cover
        the whole window's fresh wildcard entries, with replacements /
        exact / deep filters peeling off to the single-item path.
        Validation still runs per item BEFORE any mutation."""
        with self._mlock:
            # same _mlock-serializes-the-native-matrix design as
            # `insert` # brokerlint: ignore[LOCK402]
            self._insert_many_locked(pairs)

    def _insert_many_locked(self, pairs) -> None:
        # last-wins within the window (same as per-item insert): a fid
        # listed twice must not have its FIRST filter batch-inserted
        # after the second took the replacement path
        if len({fid for _, fid in pairs}) != len(pairs):
            dedup: Dict[Hashable, str] = {}
            for flt, fid in pairs:
                dedup[fid] = flt
            pairs = [(flt, fid) for fid, flt in dedup.items()]
        # validate the WHOLE window before any mutation: a bad filter
        # mid-batch must not leave earlier entries half-applied
        parsed = [
            (flt, fid, *_validate_filter(flt)) for flt, fid in pairs
        ]
        if self._built is not None:
            self._poll_swap()
        batch: List[Tuple[str, Hashable, Tuple[str, ...]]] = []
        for flt, fid, ws, wild, n_hash in parsed:
            prev = self._by_fid.get(fid)
            if prev is not None:
                if prev == flt:
                    continue
                self._insert_locked(flt, fid)
                continue
            if not wild:
                self._by_fid[fid] = flt
                self._exact.setdefault(flt, set()).add(fid)
                continue
            if len(ws) - (1 if n_hash else 0) > self.max_levels:
                self._insert_locked(flt, fid)
                continue
            self._by_fid[fid] = flt
            batch.append((flt, fid, ws))
        if not batch:
            return
        seqs = self._wild.insert_batch(batch)
        delta = self._delta
        dseq = self._delta_seq
        log = self._residual_log
        fresh = 0
        for (flt, fid, ws), seq in zip(batch, seqs):
            delta[fid] = ws
            if seq:
                dseq[fid] = seq
                log.append((fid, seq))
                fresh += 1
        self._residual_count += fresh
        if self._building:
            self._pending_inserts.extend(
                (flt, fid) for flt, fid, _ in batch
            )
        self._grown()

    def _grown(self) -> None:
        """The delta grew: a rebuild past ``rebuild_threshold``, a
        fold past the residual's trigger (`_maybe_fold`)."""
        if len(self._delta) >= self.rebuild_threshold:
            if self.background_rebuild:
                if self.defer_rebuild is None or \
                        not self.defer_rebuild():
                    self._start_background_rebuild()
            else:
                # synchronous rebuild variant keeps _mlock across
                # the native sort on purpose: mutations must not
                # interleave with the table swap
                # brokerlint: ignore[LOCK402]
                self.rebuild()
        self._maybe_fold()

    def _maybe_fold(self) -> None:
        """Fold once the residual reaches ``max(delta_aut_threshold,
        len(delta) // delta_fold_factor)``, and never while a build is
        in flight: the build covers every filter up to its snapshot
        and its swap discards the delta automaton, so a fold then is
        work thrown away (a boot's table of a million filters would be
        folded whole beside its build).  What came after the snapshot
        is the residual the swap leaves, and `_poll_swap` asks here
        again."""
        if (
            self.use_device is not False
            and not self._building
            and self._residual_count
            >= max(self.delta_aut_threshold,
                   len(self._delta) // self.delta_fold_factor)
        ):
            self._fold_delta_aut()

    def _insert_locked(self, flt: str, fid: Hashable) -> None:
        if self._built is not None:
            self._poll_swap()
        prev = self._by_fid.get(fid)
        if prev is not None and prev == flt:
            return
        # engine-level filters are REAL topics ($share is stripped by
        # the router before it gets here); validation runs BEFORE any
        # mutation so a rejected insert cannot destroy the fid's
        # existing subscription
        ws, wild, n_hash = _validate_filter(flt)
        if prev is not None:
            self._delete_locked(fid)
        self._by_fid[fid] = flt
        if wild:
            seq = self._wild.insert(flt, fid, ws=ws)
            body_depth = len(ws) - (1 if n_hash else 0)
            if body_depth > self.max_levels:
                self._deep.insert(flt, fid, ws=ws)
            else:
                # Do NOT clear a tombstone here: if the fid previously
                # carried a *different* filter in the base snapshot, the
                # tombstone is what masks the stale device entry.  The
                # residual view serves the re-inserted filter until
                # rebuild (its seq is past the watermark, and set-union
                # across tiers dedups any daut/residual double-serve).
                self._delta[fid] = ws
                if seq:
                    self._delta_seq[fid] = seq
                    log = self._residual_log
                    log.append((fid, seq))
                    self._residual_count += 1
                    if len(log) > 1024 and len(log) > 4 * max(
                        self._residual_count, 1
                    ):
                        # amortized compaction: churn that never crosses
                        # the fold threshold (or runs with the device
                        # off) must not grow the log without bound
                        wm = self._fold_watermark
                        dseq = self._delta_seq
                        self._residual_log = [
                            e for e in log
                            if e[1] > wm and dseq.get(e[0]) == e[1]
                        ]
                if self._building:
                    self._pending_inserts.append((flt, fid))
                self._grown()
        else:
            self._exact.setdefault(flt, set()).add(fid)

    def delete(self, fid: Hashable) -> bool:
        with self._mlock:
            return self._delete_locked(fid)

    def delete_many(self, fids: Sequence[Hashable]) -> int:
        """Batch delete, the twin of `insert_many`: one lock
        acquisition, and the wildcard trie's share of the batch in ONE
        GIL-released native call.  Returns how many fids were held."""
        with self._mlock:
            # same _mlock design as `insert` # brokerlint: ignore[LOCK402]
            return self._delete_many_locked(fids)

    def _delete_locked(self, fid: Hashable) -> bool:
        flt = self._by_fid.pop(fid, None)
        if flt is None:
            return False
        if T.is_wildcard(flt):
            self._wild.delete_id(fid)
            self._deep.delete_id(fid)
            self._unseat(fid)
        else:
            self._drop_exact(flt, fid)
        return True

    def _delete_many_locked(self, fids) -> int:
        wild: List[Hashable] = []
        n = 0
        for fid in fids:
            flt = self._by_fid.pop(fid, None)
            if flt is None:
                continue
            n += 1
            if T.is_wildcard(flt):
                wild.append(fid)
                self._unseat(fid)
            else:
                self._drop_exact(flt, fid)
        if wild:
            deep = self._deep
            self._wild.delete_batch(wild)
            if len(deep):
                deep.delete_batch([f for f in wild if f in deep])
        return n

    def _unseat(self, fid: Hashable) -> None:
        """A deleted wildcard fid leaves the delta and the residual,
        and is tombstoned in both device tiers."""
        self._delta.pop(fid, None)
        seq = self._delta_seq.pop(fid, None)
        if seq is not None and seq > self._fold_watermark:
            self._residual_count -= 1
        # unconditional tombstones: membership checks against the
        # base/daut fid sets would race the builder threads'
        # in-place arena mutation; masking a fid no snapshot
        # carries is harmless (set subtraction of an absent
        # element), and both sets reset at the next build anyway
        self._deleted_base.add(fid)
        self._deleted_daut.add(fid)
        if self._folding:
            self._fold_deletes.add(fid)
        if self._building:
            self._pending_deletes.add(fid)

    def _drop_exact(self, flt: str, fid: Hashable) -> None:
        ids = self._exact.get(flt)
        if ids is not None:
            ids.discard(fid)
            if not ids:
                del self._exact[flt]

    def apply_routes(self, pairs: Sequence[Tuple[str, Hashable]],
                     fids: Sequence[Hashable]) -> None:
        """One batch of route changes: ``fids`` deleted, then ``pairs``
        inserted (a batch names each fid once, so the order is the
        caller's net one), under one ``_mlock`` acquisition
        (`delete_many`, `insert_many`).  Counted in ``stats()``:
        ``route_batches``, ``route_ops``, ``route_wait_us`` (waiting
        for the lock, which a window's overlay may hold) and
        ``route_apply_us`` (holding it)."""
        st = self._route_stats
        t0 = time.perf_counter()
        self._mlock.acquire()
        t1 = time.perf_counter()
        try:
            # the batch's native trie calls hold _mlock by the
            # `insert` design # brokerlint: ignore[LOCK402]
            if fids:
                self._delete_many_locked(fids)
            if pairs:
                self._insert_many_locked(pairs)
        finally:
            self._mlock.release()
        st["route_batches"] += 1
        st["route_ops"] += len(pairs) + len(fids)
        st["route_wait_s"] += t1 - t0
        st["route_apply_s"] += time.perf_counter() - t1

    def note_route_acks(self, seconds: float, filters: int) -> None:
        """The caller's clock of one acknowledgement that waited for
        its route batch: ``seconds`` from the SUBSCRIBE or UNSUBSCRIBE
        to its ack, for ``filters`` filters (``stats()``:
        ``route_ack_us``, ``route_ack_filters``)."""
        st = self._route_stats
        st["route_ack_s"] += seconds
        st["route_ack_filters"] += filters

    def __len__(self) -> int:
        return len(self._by_fid)

    # -------------------------------------------------------------- rebuild

    def _snapshot_filters(self) -> List[Tuple[Hashable, T.Words]]:
        return [
            (fid, ws)
            for fid, ws in self._wild.filters()
            if fid not in self._deep
        ]

    def _snapshot_inputs(self):
        """Cheap coherent capture of the build work-list; the O(delta)
        encode itself runs in `_build` (i.e. in the BUILDER thread for
        background rebuilds — encoding 65k filters on the insert thread
        at the threshold crossing was a ~150 ms publish-visible
        stall)."""
        if self._build_cache is None:
            return ("full", self._snapshot_filters())
        return (
            "delta",
            list(self._delta.items()),
            set(self._deleted_base),
        )

    def _build(
        self, inputs, hash_buckets: int = 0, device_put: bool = False
    ):
        from .ops.automaton import assemble_automaton

        with self._enc_lock:
            kind = inputs[0]
            if kind == "full":
                arena = _EncArena(self.max_levels)
                arena.apply(inputs[1], (), self._tdict)
            else:
                arena = self._build_cache
                arena.apply(inputs[1], inputs[2], self._tdict)
            mat, blen, ish, flist = arena.views()
            fid_arr = arena.fid_view()
            n_live = len(arena.rows)
        aut = assemble_automaton(
            mat,
            blen,
            ish,
            flist,
            max_levels=self.max_levels,
            hash_buckets=hash_buckets,
        )
        _pad_nodes_pow2(aut)  # stable kernel shapes across rebuilds
        dev = None
        if device_put:
            dev = self._device_put(aut)
        return aut, dev, fid_arr, n_live, arena

    def _device_put(self, aut, chunk_bytes: int = 1 << 17,
                    throttle: bool = True):
        """Upload the automaton tables, big ones in chunks concatenated
        ON DEVICE: one monolithic transfer of a large table holds the
        host->device link for its whole length, queueing the live
        match path's small batches behind it.  Chunking alone is not
        enough — dispatching all chunks back-to-back still fills the
        link FIFO ahead of any match — so a short SLEEP between chunks
        leaves a gap where a concurrently-submitted match's input
        lands between chunk i and i+1 and waits one chunk time instead
        of the whole upload (churn p99 stalls, VERDICT r4 #4).
        Uploads run on the background fold/build threads, so the
        sleeps cost nothing on the match or insert paths.  The chunk
        size and the sleep were tuned on a slower link than the one
        this installation has and are not re-measured."""
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        total_bytes = 0
        out = []
        for a in aut.device_arrays():
            if isinstance(a, np.ndarray):
                total_bytes += a.nbytes
            if (
                not isinstance(a, np.ndarray)
                or a.nbytes <= 2 * chunk_bytes
            ):
                out.append(jax.device_put(a))
                continue
            rows_per = max(chunk_bytes // max(a.strides[0], 1), 1)
            parts = []
            for i in range(0, len(a), rows_per):
                parts.append(jax.device_put(a[i:i + rows_per]))
                if throttle:
                    # throttled uploads only run on the background
                    # fold/build threads; the loop-reachable
                    # _device_tables path passes throttle=False, so
                    # this sleep never parks the event loop
                    # brokerlint: ignore[ASYNC101]
                    time.sleep(0.002)
            out.append(jnp.concatenate(parts, axis=0))
        prof = self.profiler
        if prof is not None:
            prof.event(
                "device_put", time.perf_counter() - t0,
                bytes=total_bytes, throttled=throttle,
            )
        return tuple(out)

    def _fold_delta_aut(self) -> None:
        """Fold the whole current delta into the second automaton
        (geometric cadence keeps this O(1) amortized per insert).  Node
        rows pad to a power-of-two capacity class (min 4096) and the
        hash table to a minimum bucket count, so successive folds reuse
        compiled kernel shapes; the scan length is pinned likewise.
        The class is the delta's own (`_fold_class_for`): only a fold
        that commits sets it, so a fold that a base swap discards (one
        of the base's whole table) leaves no base-sized class behind
        for every later fold to assemble and upload.

        Two-phase, called under ``_mlock``: only the O(residual)
        work-list capture runs inline; the encode, assemble, upload and
        shape warm all run in a daemon thread, and the result is
        adopted only if no base swap happened meanwhile (``_fold_gen``).
        Matching keeps using the old delta automaton + the live
        residual view (`match_since_words` past the old watermark)
        until the swap, so nothing stalls and nothing is missed; the
        swap itself is a watermark bump, not a residual rebuild.

        Each phase is timed (`_FOLD_PHASES`): a profiler histogram
        ``fold_<phase>`` and a ``stats()`` counter ``fold_<phase>_us``,
        beside ``folds``, ``fold_us`` and ``fold_upload_bytes``."""
        from .ops.automaton import assemble_automaton

        if self._folding:
            return
        t_cap = time.perf_counter()
        # under _mlock: capture the work-list only (no encoding here —
        # the O(residual) encode runs in the fold thread too, off the
        # insert path).  The log dedups in place: an entry is live iff
        # it still carries its fid's latest seq.
        live = [
            (fid, seq)
            for fid, seq in self._residual_log
            if self._delta_seq.get(fid) == seq
        ]
        self._residual_log = live
        new_items = [(fid, self._delta[fid]) for fid, _ in live]
        cache = self._fold_cache
        if cache is None:
            full_items = list(self._delta.items())
            if not full_items:
                return
        else:
            if not new_items and not self._deleted_daut:
                return
            full_items = None
        deleted_snap = set(self._deleted_daut)
        snap_seq = self._wild.last_seq()
        gen = self._fold_gen
        # fire BEFORE flipping _folding: a tp-harness exception here
        # (injection / ordering timeout) must not wedge folds off
        tp("fold_capture", gen=gen, snap_seq=snap_seq,
           n_new=len(new_items))
        self._folding = True
        self._fold_deletes = set()
        phases = {"capture": time.perf_counter() - t_cap}

        def timed(phase: str, t0: float) -> float:
            t1 = time.perf_counter()
            phases[phase] = t1 - t0
            return t1

        def work():
            aut = None
            t_fold = t = time.perf_counter()
            try:
                with self._enc_lock:
                    if cache is None:
                        arena = _EncArena(self.max_levels)
                        arena.apply(full_items, (), self._tdict)
                    else:
                        arena = cache
                        arena.apply(new_items, deleted_snap, self._tdict)
                    inputs = arena.views()
                    fid_view = arena.fid_view()
                    live_fids = set(arena.rows)
                t = timed("encode", t)
                if not live_fids:  # everything deleted since snapshot
                    with self._mlock:
                        self._folding = False
                    self._fold_done(phases, t_fold, False)
                    return
                cls = self._fold_class or (_FOLD_MIN_NODES,
                                           _FOLD_MIN_BUCKETS)
                aut = assemble_automaton(
                    *inputs, max_levels=self.max_levels,
                    hash_buckets=cls[1],
                )
                cls = self._fold_class_for(aut)
                if len(aut.fp_rows) < cls[1]:
                    # a hash table cannot be padded after the fact (and
                    # a bucket that overflows still doubles it)
                    aut = assemble_automaton(
                        *inputs, max_levels=self.max_levels,
                        hash_buckets=cls[1],
                    )
                    cls = (cls[0], len(aut.fp_rows))
                _pad_nodes_pow2(aut, minimum=cls[0])
                aut.kernel_levels = self.max_levels + 1
                t = timed("assemble", t)
                dev = None
                if self.use_device is not False:
                    try:
                        dev = self._device_put(aut)
                    except Exception:
                        dev = None
                    t = timed("upload", t)
                    if dev is not None:
                        self._fold_stats["fold_upload_bytes"] += sum(
                            a.nbytes for a in aut.device_arrays()
                            if isinstance(a, np.ndarray)
                        )
                        try:
                            # warm BEFORE the commit: a fold crossing
                            # a capacity class used to compile on the
                            # first post-commit match — a multi-second
                            # p99 stall ON the publish path.  A warm
                            # failure is non-fatal: the uploaded
                            # tables still serve (worst case the first
                            # match pays the compile).
                            self._warm_buckets(aut, dev)
                        except Exception:
                            import logging

                            logging.getLogger(
                                "emqx_tpu.engine"
                            ).debug("delta shape warm failed",
                                    exc_info=True)
                        t = timed("warm", t)
                tp("fold_assemble_done", gen=gen)  # fault-inject point
            except Exception:
                import logging

                logging.getLogger("emqx_tpu.engine").exception(
                    "delta fold failed (%d filters); matching continues "
                    "on the residual overlay", len(new_items)
                )
                with self._mlock:
                    self._folding = False
                self._fold_done(phases, t_fold, False)
                return
            # blocking tracepoint OUTSIDE the lock: force_ordering may
            # pin the adoption here while a match holds/needs _mlock.
            # A harness exception (ordering timeout) must release
            # _folding or no fold would ever run again.
            try:
                tp("fold_adopt", gen=gen)
            except BaseException:
                with self._mlock:
                    self._folding = False
                raise
            t = time.perf_counter()
            with self._mlock:
                self._folding = False
                if self._fold_gen != gen:
                    tp("fold_discard", gen=gen)
                    committed = False
                else:
                    self._fold_commit(aut, dev, fid_view, arena,
                                      live_fids, snap_seq, gen, cls)
                    committed = True
            timed("commit", t)
            self._fold_done(phases, t_fold, committed)
            prof = self.profiler
            if committed and prof is not None:
                prof.event(
                    "delta_fold", time.perf_counter() - t_fold,
                    n_new=len(new_items),
                )

        if self._fold_async:
            self._fold_thread = threading.Thread(
                target=work, name="matchengine-fold", daemon=True
            )
            self._fold_thread.start()
        else:
            work()  # _mlock is reentrant: safe from _insert_locked

    def _fold_class_for(self, aut) -> Tuple[int, int]:
        """The (node rows, buckets) class a fold's automaton takes: the
        committed class where it fits, else a step to powers of two
        `_FOLD_ROOM` times what it needs (the first fold is a step
        too).  A step compiles every batch bucket's kernels in the fold
        thread before the commit, seconds on a cold cache, so it buys
        room for the delta to grow: a churning delta holds its class
        (the fleet's live set and its churn need 1,632 and ~4,900 node
        rows: one class of 8,192), and the table stays the delta's
        size, not the base's."""
        cls = self._fold_class
        need = (aut.node_rows.shape[0], len(aut.fp_rows))
        if cls is not None and need[0] <= cls[0] and need[1] <= cls[1]:
            return cls
        floor = cls or (_FOLD_MIN_NODES, _FOLD_MIN_BUCKETS)
        return tuple(
            _pow2_at_least(_FOLD_ROOM * n, lo) for n, lo in zip(need, floor)
        )

    def _fold_commit(self, aut, dev, fid_view, arena, live_fids,
                     snap_seq: int, gen: int, cls: Tuple[int, int]) -> None:
        """Adopt a fold (under ``_mlock``, its generation current)."""
        tp("fold_commit", gen=gen, watermark=snap_seq)
        # the sticky class follows a committed fold, never a discarded
        # one (a boot's whole-table fold would leave it at the base's size)
        self._fold_class = cls
        self._fold_cache = arena
        self._dtier = (aut, dev, fid_view)
        self._warn_frontier(aut, "delta")
        self._daut_fids = live_fids
        # tombstones for fids deleted while the fold assembled
        # (fresh set: an in-flight match's captured snapshot
        # keeps the old set + old automaton pair); a fid
        # re-inserted during the fold stays tombstoned here but
        # its new seq is past the watermark, so the residual
        # view serves it — set union across tiers dedups
        self._deleted_daut = {
            f for f in self._fold_deletes if f in self._daut_fids
        }
        self._fold_deletes = set()
        # the fold swap IS the watermark bump: entries at or
        # below snap_seq are covered by the new automaton
        self._fold_watermark = snap_seq
        self._residual_log = [
            (fid, seq)
            for fid, seq in self._residual_log
            if seq > snap_seq
        ]
        self._residual_count = sum(
            1
            for fid, seq in self._residual_log
            if self._delta_seq.get(fid) == seq
        )

    def _fold_done(self, phases: Dict[str, float], t_fold: float,
                   committed: bool) -> None:
        """Count one fold's phases (`_FOLD_STATS`) and histogram them
        (``fold_<phase>``); the fold thread's own wall time is
        ``fold_s`` whether or not the fold committed."""
        st = self._fold_stats
        st["folds"] += committed
        st["fold_s"] += time.perf_counter() - t_fold
        prof = self.profiler
        for phase, dur in phases.items():
            st["fold_" + phase + "_s"] += dur
            if prof is not None:
                prof.stage("fold_" + phase, dur)

    def _warm_built(self, aut, dev, batch: int = 16) -> None:
        """Compile the kernel for a freshly built automaton's table
        shapes at one batch bucket (called off the hot path so the
        first real match never pays a shape-class compile in its own
        latency).  Sharded subclasses override — their tables feed a
        different kernel.

        Skips shape classes already warmed this process: the sticky
        fold capacity ladder means successive folds reuse one class,
        and each redundant warm queued two device round-trips that
        live matches had to wait behind (churn p99)."""
        from .ops.match_kernel import match_batch, match_batch_compact

        sig = (
            aut.node_rows.shape[0], len(aut.fp_rows), aut.kernel_levels,
            batch,
        )
        if sig in self._warmed_shapes:
            return
        self._warmed_shapes.add(sig)
        t0 = time.perf_counter()
        tokens = np.full((batch, aut.kernel_levels), -4, np.int32)
        lengths = np.zeros(batch, np.int32)
        dollar = np.zeros(batch, bool)
        out = match_batch_compact(
            *dev, tokens, lengths, dollar,
            f_width=self.f_width, m_cap=self.m_cap,
            c_cap=self._ccap_mult * batch,
        )
        out[0].block_until_ready()
        # the DENSE kernel is the compact-clip fallback: warm it too,
        # or the first over-fanin window would pay its compile inside
        # the live match path
        out = match_batch(
            *dev, tokens, lengths, dollar,
            f_width=self.f_width, m_cap=self.m_cap,
        )
        out[0].block_until_ready()
        prof = self.profiler
        if prof is not None:
            prof.event(
                "xla_compile", time.perf_counter() - t0,
                nodes=sig[0], buckets=sig[1], levels=sig[2],
                batch=batch,
            )

    def _warm_buckets(self, aut, dev) -> None:
        """`_warm_built` at every batch bucket a served window can
        take (16 up to the width `warmup()` was given), so a fold or
        rebuild that crosses a capacity class compiles in its own
        thread, not in the first wide window after the swap."""
        bp = 16
        while bp <= self._warm_batch:
            self._warm_built(aut, dev, bp)
            bp *= 2

    def _drop_delta_aut(self) -> None:
        self._dtier = _NO_DTIER
        self._daut_fids = set()
        self._fold_cache = None
        # discard any in-flight fold: its inputs predate this state
        self._fold_gen += 1
        self._fold_deletes = set()
        tp("daut_drop", gen=self._fold_gen)

    def rebuild(self, hash_buckets: int = 0) -> None:
        """Fold the delta into a fresh device automaton snapshot
        (synchronous; see ``background_rebuild`` for the no-stall path).

        If a background build is in flight, wait for it first: two
        concurrent builders would interleave TokenDict.add's
        check-then-act and could alias two words onto one token id."""
        t = self._build_thread
        if t is not None and t.is_alive():
            t.join()
        # under _mlock like every other writer of this state: a fold
        # thread's commit (gen check, then its stores) interleaved
        # with the drop below and adopted a stale or half-cleared
        # delta tier
        with self._mlock:
            self._poll_swap()
            inputs = self._snapshot_inputs()
            # the synchronous variant keeps _mlock across the native
            # sort on purpose: mutations must not interleave with the
            # table swap
            # brokerlint: ignore[LOCK402]
            built = self._build(inputs, hash_buckets=hash_buckets)
            (
                self._aut,
                self._dev,
                self._fid_arr,
                self._n_base,
                self._build_cache,
            ) = built
            self._warn_frontier(self._aut, "base")
            self._delta = {}
            self._delta_seq = {}
            self._residual_log = []
            self._residual_count = 0
            self._fold_watermark = self._wild.last_seq()
            self._drop_delta_aut()
            self._deleted_base = set()
            self._deleted_daut = set()

    def _warn_frontier(self, aut, tier: str) -> None:
        """One warning a build whose table can need a wider frontier
        than the kernel is run at: the answers stay exact, but the
        rows that pass the width are the host trie's."""
        if aut.frontier_need > self.f_width:
            import logging

            logging.getLogger("emqx_tpu.engine").warning(
                "%s automaton can need a frontier of %d, f_width is %d: "
                "rows whose frontier passes %d are matched on the host",
                tier, aut.frontier_need, self.f_width, self.f_width,
            )

    def kick_rebuild(self) -> bool:
        """Start a background rebuild NOW if the delta has outgrown
        the threshold — the olp ladder's recovery kick for rebuilds
        deferred during overload (a stable fleet may otherwise never
        mutate again, leaving the oversized delta tiers serving every
        window forever).  Returns True when one was started."""
        if (
            self.background_rebuild
            and len(self._delta) >= self.rebuild_threshold
            and not self._building
        ):
            self._start_background_rebuild()
            return True
        return False

    def _start_background_rebuild(self) -> None:
        with self._lock:
            if self._building:
                return
            self._building = True
            self._pending_inserts = []
            self._pending_deletes = set()
            self._rebuild_snap_seq = self._wild.last_seq()
            inputs = self._snapshot_inputs()
        # sharded engines snapshot a plain filter list, the base engine
        # encoded arrays — count accordingly (and BEFORE the try, so the
        # failure handler can never raise and wedge `_building`)
        n_filters = (
            len(inputs[1]) if isinstance(inputs, tuple) else len(inputs)
        )

        def work():
            try:
                t_build = time.perf_counter()
                built = self._build(inputs, device_put=True)
                # compile the kernel for the new table shapes HERE, in
                # the builder thread, so the first post-swap match never
                # pays a shape-class compile in its own latency
                try:
                    if built[1] is not None and built[0].n_nodes > 1:
                        self._warm_buckets(built[0], built[1])
                except Exception:
                    import logging

                    logging.getLogger("emqx_tpu.engine").debug(
                        "base shape warm failed", exc_info=True
                    )
                prof = self.profiler
                if prof is not None:
                    prof.event(
                        "rebuild", time.perf_counter() - t_build,
                        n_filters=n_filters,
                    )
            except Exception:  # build failure must not wedge the engine
                import logging

                logging.getLogger("emqx_tpu.engine").exception(
                    "background automaton rebuild failed "
                    "(%d filters); matching continues on the host overlay",
                    n_filters,
                )
                built = ()
            with self._lock:
                self._built = built

        self._build_thread = threading.Thread(
            target=work, name="matchengine-rebuild", daemon=True
        )
        self._build_thread.start()

    def _poll_swap(self) -> None:
        """Adopt a finished background build: O(pending) swap, no stall."""
        if self._built is None:
            return
        with self._lock:
            built = self._built
            self._built = None
            if not built:  # failed build: allow a retrigger
                self._building = False
                return
            (
                self._aut,
                self._dev,
                self._fid_arr,
                self._n_base,
                self._build_cache,
            ) = built
            self._warn_frontier(self._aut, "base")
            delta: Dict[Hashable, Tuple[str, ...]] = {}
            for flt, fid in self._pending_inserts:
                if self._by_fid.get(fid) == flt and fid not in self._deep:
                    delta[fid] = tuple(flt.split("/"))
            self._delta = delta
            # pending inserts become the fresh residual: the new base
            # covers everything up to the build snapshot, so the
            # watermark moves to the snapshot's sequence point and the
            # log keeps only what arrived after it
            self._delta_seq = {
                fid: s for fid, s in self._delta_seq.items() if fid in delta
            }
            self._fold_watermark = self._rebuild_snap_seq
            # rebuild the log from _delta_seq, NOT the old log: a fold
            # committing mid-build pruned the log past ITS watermark,
            # which is ahead of the rebuild snapshot — every pending
            # delta entry post-dates the snapshot, so all are residual
            self._residual_log = [
                (fid, s) for fid, s in self._delta_seq.items()
            ]
            self._residual_count = len(self._residual_log)
            self._drop_delta_aut()
            # unconditional: membership against the arena would race
            # its in-place mutation; masking absent fids is harmless
            self._deleted_base = set(self._pending_deletes)
            self._deleted_daut = set()
            self._pending_inserts = []
            self._pending_deletes = set()
            self._building = False
            tp("base_swap", pending=len(delta))
        # what came after the build's snapshot may fold now (callers
        # hold ``_mlock``, as `_fold_delta_aut` asks)
        self._maybe_fold()

    def warmup(self, max_batch: int = 4096) -> int:
        """Pre-compile the kernel for every power-of-two batch bucket up
        to ``max_batch`` (the `_pad_batch` shape set), and the rules
        kernel for the registered program over the same buckets, so a
        production publish flood never stalls on a first-use XLA
        compile.  A build or fold in flight is waited for and adopted
        first (it is what will serve), and every later fold or
        rebuild warms the same buckets in its own thread.  Returns
        the number of match buckets warmed (0 when the device path is
        off or no automaton is built yet)."""
        for t in (self._build_thread, self._fold_thread):
            if t is not None and t.is_alive():
                t.join()
        # raised only now: the sweep below covers whatever those
        # threads built, and a fold the swap discards would have
        # compiled every bucket for a table nothing serves
        self._warm_batch = max(self._warm_batch, max_batch)
        self._warm_rules(max_batch)
        self._dec_rows_warm = max(
            self._dec_rows_warm, _DEC_ROWS_PER_MSG * max_batch
        )
        t = self._dec_warm_thread
        if t is not None and t.is_alive():
            t.join()
        self._warm_decide()
        with self._mlock:
            self._poll_swap()
            device_on = (
                self.use_device is not False
                and self._aut is not None
                and self._aut.n_nodes > 1
            )
        if not device_on:
            return 0
        n = 0
        bp = 16
        # pin the device for the warmup sweep: in auto mode the policy
        # would route the small synthetic windows to the host, leaving
        # kernel buckets cold AND the device-cost EWMAs unseeded (the
        # first LIVE window would then pay the measurement probe as
        # head-of-line latency)
        self._warmup_force = True
        try:
            while bp <= max_batch:
                # DISTINCT topics: the window dedups before it pads,
                # so bp copies of one topic would compile the 16-row
                # bucket bp times over and leave the rest cold
                self.match_batch([f"\x00warmup/{i}" for i in range(bp)])
                # and the dense kernel, the compact-clip fallback: the
                # first over-fan-in window of this bucket would pay
                # its compile in the live match path
                with self._mlock:
                    snap = self._snapshot_refs()
                self._warm_built(snap[0], snap[1], bp)
                if snap[6][0] is not None and snap[6][1] is not None:
                    self._warm_built(snap[6][0], snap[6][1], bp)
                n += 1
                bp *= 2
            # the sweep's first-use compiles polluted the device-cost
            # EWMAs (a 2 s compile window is not a 100 ms steady-state
            # window): reseed from one more WARM window of DISTINCT
            # topics (a fully-deduped window hides the real per-topic
            # encode/expand cost) so the auto policy starts from
            # representative numbers
            self._dev_window_s = None
            self._dev_cpu_us = None
            self.match_batch(
                [f"\x00warmup/{i}" for i in range(min(1024, max_batch))]
            )
        finally:
            self._warmup_force = False
        return n

    def index_stats(self) -> Dict[str, object]:
        return {
            "base": self._n_base,
            "delta": len(self._delta),
            "folded": len(self._daut_fids),
            "residual": self._residual_count,
            "deep": len(self._deep),
            "exact": sum(len(v) for v in self._exact.values()),
            "deleted": len(self._deleted_base) + len(self._deleted_daut),
            "building": self._building,
            "folding": self._folding,
            "auto_host_windows": self._auto_stats["host_windows"],
            "auto_dev_windows": self._auto_stats["dev_windows"],
            "breaker_open": self._brk_open,
            "breaker_trips": self._brk_stats["trips"],
            "breaker_device_errors": self._brk_stats["device_errors"],
            "host_us_ewma": self._host_us,
            "dev_cpu_us_ewma": self._dev_cpu_us,
            "dev_window_ms_ewma": (
                self._dev_window_s * 1e3
                if self._dev_window_s is not None else None
            ),
        }

    def _device_tables(self):
        if self._dev is None:
            # LAZY path (upload-failed / toggled corners): runs under
            # _mlock on a match thread — no inter-chunk throttling
            # here, or the sleeps would hold the lock and stall every
            # SUBSCRIBE/match for seconds; the background fold/build
            # uploads keep the throttled default
            self._dev = self._device_put(self._aut, throttle=False)
        return self._dev

    # ---------------------------------------------------------- breaker

    def _device_failure(self, reason: str = "error") -> None:
        """Record one device-step failure; trips the breaker after
        `breaker_threshold` CONSECUTIVE ones.  Called from whatever
        thread ran the match — the trip callback must be thread-safe
        (the broker's is: it schedules onto the event loop)."""
        self._brk_stats["device_errors"] += 1
        self._brk_failures += 1
        if not self._brk_open and (
            self._brk_failures >= self.breaker_threshold
        ):
            self._trip_breaker(reason)

    def _device_ok(self, wall: float) -> None:
        """A device window completed.  A wall time past the watchdog
        deadline still counts as a failure: a wedged-but-eventually-
        returning device (link stall, compile storm) must degrade to
        the host path, not hold every window hostage."""
        if (
            self.breaker_deadline is not None
            and wall > self.breaker_deadline
        ):
            self._brk_stats["slow_windows"] += 1
            self._device_failure(reason="deadline")
            return
        self._brk_failures = 0

    def _trip_breaker(self, reason: str) -> None:
        self._brk_open = True
        self._brk_opened_at = time.monotonic()
        self._brk_probe_last = self._brk_opened_at
        self._brk_stats["trips"] += 1
        info = {"reason": reason, "failures": self._brk_failures,
                "trips": self._brk_stats["trips"]}
        import logging

        logging.getLogger("emqx_tpu.engine").warning(
            "device-path breaker OPEN (%s after %d consecutive "
            "failures): matching degrades to host-only; background "
            "probe every %.1fs", reason, self._brk_failures,
            self.breaker_probe_interval,
        )
        tp("breaker_trip", reason=reason)
        if self.on_breaker_trip is not None:
            try:
                self.on_breaker_trip(info)
            except Exception:
                logging.getLogger("emqx_tpu.engine").exception(
                    "breaker trip callback failed"
                )

    def _close_breaker(self) -> None:
        self._brk_open = False
        self._brk_failures = 0
        info = {"open_for": time.monotonic() - self._brk_opened_at,
                "trips": self._brk_stats["trips"]}
        import logging

        logging.getLogger("emqx_tpu.engine").warning(
            "device-path breaker CLOSED after %.1fs: device matching "
            "re-enabled", info["open_for"],
        )
        tp("breaker_clear")
        if self.on_breaker_clear is not None:
            try:
                self.on_breaker_clear(info)
            except Exception:
                logging.getLogger("emqx_tpu.engine").exception(
                    "breaker clear callback failed"
                )

    def _brk_maybe_probe(self) -> None:
        """While the breaker is open, re-try the device path out-of-
        band on a one-shot daemon thread (never as head-of-line latency
        in the live window stream); success re-closes the breaker."""
        now = time.monotonic()
        if (
            self._brk_probing
            or now - self._brk_probe_last < self.breaker_probe_interval
        ):
            return
        self._brk_probing = True
        self._brk_probe_last = now
        sample = list(self._probe_topics[:64]) or [
            f"\x00brkprobe/{i}" for i in range(64)
        ]

        def work() -> None:
            ok = False
            try:
                errs0 = self._brk_stats["device_errors"]
                pending = self.match_batch_submit(
                    sample, _force_device=True
                )
                self.match_batch_finish(pending)
                # success = the submit really chose the device ("host"
                # means it fell back internally) AND the finish side
                # recorded no new failure — finish catches its own
                # transfer faults and returns host results without
                # raising, which must NOT close the breaker
                ok = (
                    pending[0] == "dev"
                    and self._brk_stats["device_errors"] == errs0
                )
            except Exception:
                ok = False
            finally:
                self._brk_stats["probes"] += 1
                self._brk_probing = False
            if ok and self._brk_open:
                self._close_breaker()

        threading.Thread(
            target=work, name="engine-brk-probe", daemon=True
        ).start()

    @property
    def breaker_open(self) -> bool:
        return self._brk_open

    def breaker_info(self) -> Dict[str, object]:
        return {
            "open": self._brk_open,
            "consecutive_failures": self._brk_failures,
            "threshold": self.breaker_threshold,
            "probe_interval": self.breaker_probe_interval,
            "deadline": self.breaker_deadline,
            **self._brk_stats,
        }

    def stats(self) -> Dict[str, object]:
        """The engine's full gauge surface for exposition (Prometheus
        scrape, OTLP metrics, $SYS): index tier sizes, auto-policy
        window counts, the cost EWMAs and breaker state."""
        out = self.index_stats()
        out["auto_probes"] = self._auto_stats["probes"]
        out["breaker_slow_windows"] = self._brk_stats["slow_windows"]
        out["breaker_probes"] = self._brk_stats["probes"]
        out["decide_host_windows"] = self._dec_stats["host_windows"]
        out["decide_dev_windows"] = self._dec_stats["dev_windows"]
        out["decide_dev_errors"] = self._dec_stats["dev_errors"]
        out["rules_host_windows"] = self._rul_stats["host_windows"]
        out["rules_dev_windows"] = self._rul_stats["dev_windows"]
        out["rules_dev_errors"] = self._rul_stats["dev_errors"]
        out["rules_dev_refused"] = self._rul_stats["dev_refused"]
        out["rules_host_us_ewma"] = self._rul_host_us
        out["rules_dev_us_ewma"] = self._rul_dev_us
        out["host_rows"] = self._host_rows
        # the route batches' and the folds' counters, seconds as whole
        # microseconds (a reader takes their growth over a window)
        for st in (self._route_stats, self._fold_stats):
            for key, v in st.items():
                if key.endswith("_s"):
                    out[key[:-2] + "_us"] = int(v * 1e6)
                else:
                    out[key] = v
        # of the base and the delta automaton, the wider (0: neither)
        out["frontier_need"] = max(
            (aut.frontier_need for aut in (self._aut, self._dtier[0])
             if aut is not None),
            default=0,
        )
        return out

    # -------------------------------------------------------------- match

    def match(self, topic: str) -> Set[Hashable]:
        return self.match_batch([topic])[0]

    def match_host(self, topic_words: T.Words) -> Set[Hashable]:
        """Pure-host exact match (oracle path)."""
        out = set(self._exact.get(T.join(topic_words), ()))
        out |= self._wild.match_words(topic_words)
        return out

    def _snapshot_refs(self) -> Tuple:
        """Coherent (automaton, device tables, fid array, residual
        delta, deep, deleted, delta-automaton triple) snapshot; must be
        captured under ``_mlock`` so a concurrent rebuild swap cannot
        mix generations.  delta/deleted belong to the SAME generation as
        the automata: a swap landing mid-kernel replaces them with
        (empty) successors folded into the new base, and overlaying
        those against the old base would drop every delta-resident
        subscription for the window."""
        daut, ddev, dfids = self._dtier
        if daut is not None and ddev is None:
            import jax

            # lazy upload keeps device_put off the insert path (folds
            # usually stage device arrays themselves; this covers the
            # upload-failed / use_device-toggled corners)
            self._dtier = (
                daut,
                tuple(jax.device_put(a) for a in daut.device_arrays()),
                dfids,
            )
        return (
            self._aut,
            self._device_tables(),
            self._fid_arr,
            _ResidualView(self._wild, self._fold_watermark),
            self._deep,
            self._deleted_base,
            self._dtier,
            self._deleted_daut,
            # the residual's filters at the snapshot (a window's
            # ``info["residual"]``)
            self._residual_count,
        )

    def _auto_choose(self, n: int, congested: bool) -> bool:
        """Pick host (False) or device (True) for an auto-mode window
        of ``n`` topics from the measured cost EWMAs.  Device cost is
        HONEST HOST CPU (thread_time): where the transfer wait burns
        host cycles the device path shows its true cost and host wins;
        where it is a DMA wait with the GIL released the device cost
        collapses and the policy flips.
        While host is chosen, `_maybe_probe` keeps the device numbers
        fresh out-of-band."""
        self._auto_seq += 1
        host_us = self._host_us if self._host_us is not None else 5.0
        if self._dev_window_s is None:
            # unmeasured: serve on host; warmup() seeds the estimates
            # at boot, and the probe below fires if host degrades
            use_dev = False
        elif congested:
            # throughput mode: pipelining hides most of a device
            # window's wall, but the window still occupies an ordered-
            # dispatch slot for ~RTT/depth — a stall every HOST window
            # queued behind it pays too.  Effective per-topic device
            # cost = host-side CPU + that amortized slot: a long
            # round-trip keeps small windows on host (the slot term
            # dwarfs the trie), a short one lets the slot term vanish
            # and big windows offload.  The 1.2 margin resists
            # path flapping, whose head-of-line mixing cost neither
            # estimate sees.
            dev_cpu = (
                self._dev_cpu_us if self._dev_cpu_us is not None else 2.0
            )
            slot_us = (
                self._dev_window_s / 4.0 / max(n, 1) * 1e6
            )
            use_dev = host_us > (dev_cpu + slot_us) * 1.2
        else:
            # latency mode: the window resolves when the caller gets
            # the result back — compare wall times
            use_dev = n * host_us * 1e-6 > self._dev_window_s
        if not use_dev:
            # refresh the device numbers out-of-band: aggressively
            # (30 s) when there is a live case for switching
            # (congestion + an expensive host trie), lazily (120 s)
            # otherwise — without the lazy tick a transient device
            # slowdown would pin the policy to host FOREVER, because
            # host windows never re-measure the device
            self._maybe_probe(
                urgent=congested and host_us > 15.0
            )
        return use_dev

    def _maybe_probe(self, urgent: bool = False) -> None:
        """Refresh the device EWMAs off-band (30 s cadence when a
        switch is plausible, 120 s maintenance otherwise), on a
        one-shot daemon thread, over recent real topics."""
        now = time.monotonic()
        interval = 30.0 if urgent else 120.0
        if (
            self._probe_running
            or now - self._probe_last < interval
            or not self._probe_topics
        ):
            return
        self._probe_running = True
        self._probe_last = now
        sample = list(self._probe_topics)

        def work() -> None:
            try:
                self._warmup_probe(sample)
            except Exception:
                pass
            finally:
                self._probe_running = False

        threading.Thread(
            target=work, name="engine-dev-probe", daemon=True
        ).start()

    def _warmup_probe(self, topics: List[str]) -> None:
        """One measured device window (submit+finish) outside the live
        window stream; updates the device EWMAs.  Uses the explicit
        force flag, NOT _warmup_force — that one is instance-wide and
        would shunt concurrent live windows onto the device."""
        while 0 < len(topics) < 64:
            topics = topics + topics  # EWMA gate needs >=64 topics
        pending = self.match_batch_submit(topics, _force_device=True)
        self.match_batch_finish(pending)
        self._auto_stats["probes"] += 1

    # ------------------------------------------ window decide columns

    def decide_window(
        self,
        cols: Tuple,
        rev: int,
        opts_rows: np.ndarray,
        client_rows: np.ndarray,
        msg_idx: np.ndarray,
        m_qos: np.ndarray,
        m_retain: np.ndarray,
        m_from_row: np.ndarray,
        info: Optional[Dict] = None,
    ) -> Tuple[np.ndarray, str]:
        """Compute one window's packed per-delivery decision column
        (see ops.match_kernel's bit layout) on the host or the device,
        chosen per window by the measured per-delivery cost EWMAs.
        ``info`` (optional dict) receives, where the device served,
        ``upload`` and ``device_wait`` (the kernel call and the
        blocking copy back) as ``(start, dur)`` on the perf_counter
        clock, and ``rows``: ``(rows, the bucket they were padded to)``.

        ``cols`` are the router's SubOpts attribute columns and ``rev``
        their mutation counter (the device copies cache on it).  A
        device fault degrades THIS window to the bit-identical numpy
        twin and counts against the shared PR 1 circuit breaker, so a
        dead device path trips matching AND deciding to host-only
        together; the background breaker probe heals both."""
        n = len(opts_rows)
        if n and self._decide_choose(n):
            try:
                t0 = time.perf_counter()
                packed = self._decide_device(
                    cols, rev, opts_rows, client_rows, msg_idx,
                    m_qos, m_retain, m_from_row, info,
                )
                us = (time.perf_counter() - t0) * 1e6 / n
                if self._dec_dev_warm:
                    self._dec_dev_us = (
                        us if self._dec_dev_us is None
                        else 0.2 * us + 0.8 * self._dec_dev_us
                    )
                else:
                    # first device window: the JIT compile dominated
                    # the wall time — warm only, don't record
                    self._dec_dev_warm = True
                self._dec_stats["dev_windows"] += 1
                return packed, "dev"
            except Exception:
                self._dec_stats["dev_errors"] += 1
                self._device_failure("decide")
                import logging

                logging.getLogger("emqx_tpu.engine").exception(
                    "device decide step failed for window of %d; "
                    "host columns", n,
                )
        from .ops.match_kernel import decide_batch_host

        t0 = time.perf_counter()
        packed = decide_batch_host(
            *cols, opts_rows, client_rows, msg_idx,
            m_qos, m_retain, m_from_row,
        )
        if n:
            us = (time.perf_counter() - t0) * 1e6 / n
            self._dec_host_us = (
                us if self._dec_host_us is None
                else 0.2 * us + 0.8 * self._dec_host_us
            )
        self._dec_stats["host_windows"] += 1
        return packed, "host"

    def _decide_choose(self, n: int) -> bool:
        """Host (False) or device (True) for a decide window of ``n``
        deliveries.  ``decide_force`` pins the path (tests / property
        suites); the breaker overrides everything but a host pin."""
        force = self.decide_force
        if force is not None:
            return force == "dev" and not self._brk_open
        if self._brk_open or self.use_device is False:
            return False
        if self.use_device is True:
            return True
        # auto: the columns are one elementwise pass, so the host twin
        # wins until windows are large enough to amortize a dispatch —
        # measure rather than guess, seeding the device EWMA on the
        # first big window
        self._dec_seq += 1
        host = self._dec_host_us if self._dec_host_us is not None else 0.05
        dev = self._dec_dev_us
        if dev is None:
            use_dev = n >= 4096
        elif n >= 512 and host > dev * 1.2:
            use_dev = True
        else:
            # periodic in-band re-probe on a big window so a
            # transient device slowdown can't pin the policy to host
            # forever (host windows never re-measure the device)
            use_dev = (
                n >= 4096
                and self._dec_seq - self._dec_probe_seq >= 1024
            )
        if use_dev:
            self._dec_probe_seq = self._dec_seq
        return use_dev

    def _decide_device(
        self, cols, rev, opts_rows, client_rows, msg_idx,
        m_qos, m_retain, m_from_row, info=None,
    ) -> np.ndarray:
        """One device decide step: upload the attribute columns (cached
        by ``rev``), pad them and the delivery/message columns to the
        bounded shape classes `_warm_decide` compiles (the note at
        `_DEC_ROWS_MIN`), run the fused kernel, slice the padding off."""
        if failpoints.enabled:
            # chaos seam: an injected error degrades this window to the
            # host columns and feeds the shared device breaker
            failpoints.evaluate("dispatch.decide.device")
        cache = self._dec_cols_cache
        if cache is None or cache[0] != rev:
            import jax

            rung = _dec_cols_rung(len(cols[0]))
            cache = (rev, tuple(jax.device_put(
                _pad_to(c, rung, 0, c.dtype)) for c in cols), rung)
            self._dec_cols_cache = cache
            want = {rung}
            if len(cols[0]) >= rung:
                # the router has filled this rung: its successor
                # compiles before the next doubling needs it
                want.add(_dec_cols_rung(rung + 1))
            if not want <= self._dec_rungs:
                self._dec_rungs = self._dec_rungs | want
                self._kick_decide_warm()
        n = len(opts_rows)
        npad = _pow2_at_least(n, _DEC_ROWS_MIN)
        bpad = max(_pow2_at_least(len(m_qos), 16), self._warm_batch)
        packed = self._decide_run(cache[1], (
            _pad_to(opts_rows, npad, 0, np.int32),
            _pad_to(client_rows, npad, -1, np.int32),
            _pad_to(msg_idx, npad, 0, np.int32),
            _pad_to(m_qos, bpad, 0, np.int8),
            _pad_to(m_retain, bpad, False, bool),
            _pad_to(m_from_row, bpad, -1, np.int32),
        ), info)
        if info is not None:
            info["rows"] = (n, npad)
        if 2 * npad > self._dec_rows_warm:
            # the widest window so far: its successor bucket compiles
            # in the warm thread, ahead of the window that needs it
            self._dec_rows_warm = 2 * npad
            self._kick_decide_warm()
        return packed[:n]

    @staticmethod
    def _decide_run(cols_dev, window, info=None) -> np.ndarray:
        """The padded kernel call behind `_decide_device` and
        `_warm_decide`: upload the window's six columns, run, copy the
        packed column back.  ``info`` receives ``upload`` and
        ``device_wait`` (kernel and copy back) as ``(start, dur)``."""
        import jax

        from .ops.match_kernel import decide_batch

        t0 = time.perf_counter()
        window = jax.block_until_ready(jax.device_put(window))
        t1 = time.perf_counter()
        packed = np.asarray(decide_batch(*cols_dev, *window))
        if info is not None:
            info["upload"] = (t0, t1 - t0)
            info["device_wait"] = (t1, time.perf_counter() - t1)
        return packed

    def _decide_shapes(self) -> List[Tuple[int, int, int]]:
        """The ``(rung, rows, messages)`` triples a served window can
        take that this process has not compiled yet."""
        out = []
        for rung in sorted(self._dec_rungs):
            npad = _DEC_ROWS_MIN
            while npad <= self._dec_rows_warm:
                sig = (rung, npad, self._warm_batch)
                if sig not in _DEC_WARMED:
                    out.append(sig)
                npad *= 2
        return out

    def _warm_decide(self) -> bool:
        """Compile `decide_batch` for every shape triple a served
        window can take (`_decide_shapes`), so that no window pays a
        first-use compile on the loop thread.  It needs no automaton:
        a broker of exact subscriptions alone decides on the device
        too.  Best effort: False where it compiled nothing more (the
        device path is off or its breaker open, or a device fault,
        which is the served path's to count against the breaker)."""
        if (
            self.use_device is False or self._brk_open
            or self.decide_force == "host"
        ):
            return False
        import jax

        t0 = time.perf_counter()
        todo = self._decide_shapes()
        cols_dev: Dict[int, Tuple] = {}
        try:
            for rung, npad, bpad in todo:
                if rung not in cols_dev:
                    cols_dev[rung] = jax.device_put((
                        np.zeros(rung, np.int8), np.zeros(rung, bool),
                        np.zeros(rung, bool), np.zeros(rung, bool),
                    ))
                self._decide_run(cols_dev[rung], (
                    np.zeros(npad, np.int32), np.full(npad, -1, np.int32),
                    np.zeros(npad, np.int32),
                    np.zeros(bpad, np.int8), np.zeros(bpad, bool),
                    np.full(bpad, -1, np.int32),
                ))
                _DEC_WARMED.add((rung, npad, bpad))
        except Exception:
            import logging

            logging.getLogger("emqx_tpu.engine").debug(
                "decide shape warm failed", exc_info=True
            )
            return False
        self._dec_dev_warm = True
        prof = self.profiler
        if todo and prof is not None:
            prof.event(
                "xla_compile", time.perf_counter() - t0,
                decide_shapes=len(todo),
            )
        return True

    def _kick_decide_warm(self) -> None:
        """`_warm_decide` in a thread of its own, as folds warm their
        buckets: the loop thread never waits for it."""

        def work() -> None:
            while True:
                ok = self._warm_decide()
                with self._lock:
                    if not ok or not self._decide_shapes():
                        self._dec_warm_thread = None
                        return

        with self._lock:
            if self._dec_warm_thread is not None or not self._decide_shapes():
                return
            t = self._dec_warm_thread = threading.Thread(
                target=work, name="matchengine-decide-warm", daemon=True
            )
        t.start()

    # -------------------------------------- rules x window matrix

    def rules_eval_window(self, stack, rev: int, cols, rows=None,
                          info: Optional[Dict] = None):
        """Evaluate the rule registry's stacked WHERE program against
        one window's column planes: the ``[n_rules, n_msgs]`` boolean
        pass matrix, host numpy twin or the fused device kernel
        chosen per window by the measured per-cell cost EWMAs.

        ``stack`` is a `rules.predicate.StackedRules`, ``rev`` the
        rule engine's mutation counter (the device program-array
        cache keys on it), ``cols`` a `rules.columns.WindowColumns`.
        ``rows`` (sorted int array) names the matrix rows whose rules
        actually matched this window's topics: the host twin
        row-slices the program to just those and scatters back (a
        partitioned 10k-rule registry evaluates only the matched
        slice), while the device path keeps the full rev-cached
        program upload.  A device fault degrades THIS window to the
        bit-identical host twin and counts against the shared PR 1
        circuit breaker, so a dead device path trips matching,
        deciding and rule eval to host together; the background
        breaker probe heals all three.  ``info`` receives
        ``device_wait`` as in `decide_window`."""
        n_active = stack.n_rules if rows is None else len(rows)
        n = n_active * cols.n
        if n and self._rules_choose(stack, cols, n):
            try:
                t0 = time.perf_counter()
                mat = self._rules_device(stack, rev, cols, info)
                us = (time.perf_counter() - t0) * 1e6 / n
                if self._rul_dev_warm:
                    self._rul_dev_us = (
                        us if self._rul_dev_us is None
                        else 0.2 * us + 0.8 * self._rul_dev_us
                    )
                else:
                    # first device window: JIT compile dominated the
                    # wall time — warm only, don't record
                    self._rul_dev_warm = True
                self._rul_stats["dev_windows"] += 1
                return mat, "dev"
            except Exception:
                self._rul_stats["dev_errors"] += 1
                self._device_failure("rules")
                import logging

                logging.getLogger("emqx_tpu.engine").exception(
                    "device rules eval failed for %dx%d matrix; "
                    "host columns", stack.n_rules, cols.n,
                )
        from .ops.match_kernel import rules_eval_host

        t0 = time.perf_counter()
        if rows is not None and n_active < stack.n_rules:
            sub = rules_eval_host(
                stack.code[rows], stack.a0[rows], stack.a1[rows],
                stack.a2[rows], stack.a3[rows], stack.litn[rows],
                cols.lit_ranks, stack.last[rows],
                cols.num, cols.sid, cols.err, cols.prs,
            )
            mat = np.zeros((stack.n_rules, cols.n), bool)
            mat[rows] = sub
        else:
            mat = rules_eval_host(
                stack.code, stack.a0, stack.a1, stack.a2, stack.a3,
                stack.litn, cols.lit_ranks, stack.last,
                cols.num, cols.sid, cols.err, cols.prs,
            )
        if n:
            us = (time.perf_counter() - t0) * 1e6 / n
            self._rul_host_us = (
                us if self._rul_host_us is None
                else 0.2 * us + 0.8 * self._rul_host_us
            )
        self._rul_stats["host_windows"] += 1
        return mat, "host"

    def _rules_choose(self, stack, cols, n: int) -> bool:
        """Host (False) or device (True) for an ``n``-cell rules
        matrix.  `rules_force` pins the path (tests / benches); the
        breaker overrides everything but a host pin, and scheduling a
        heal probe here keeps a rules-heavy broker from staying
        host-pinned forever; the f32 gate (arith programs, f32-lossy
        literals or columns) protects the float64 oracle semantics."""
        force = self.rules_force
        if self._brk_open:
            self._brk_maybe_probe()
            return False
        if force == "host":
            return False
        if force is None and self.use_device is False:
            return False
        # resolve the COST decision before the f32 gate: the gate's
        # full-plane scan is O(P x W), and a window the policy would
        # serve on host anyway must not pay it
        if force == "dev" or self.use_device is True:
            use_dev = True
        else:
            self._rul_seq += 1
            host = (
                self._rul_host_us
                if self._rul_host_us is not None else 0.02
            )
            dev = self._rul_dev_us
            if dev is None:
                use_dev = n >= 16384
            elif n >= 2048 and host > dev * 1.2:
                use_dev = True
            else:
                # periodic in-band re-probe on a big matrix so a
                # transient device slowdown can't pin the policy to
                # host forever
                use_dev = (
                    n >= 16384
                    and self._rul_seq - self._rul_probe_seq >= 1024
                )
            if use_dev:
                self._rul_probe_seq = self._rul_seq
        if not use_dev:
            return False
        # the shape gate binds even under a dev pin: the kernel keeps
        # four [S, R, W] register planes resident, and a registry
        # whose padded planes pass the budget cannot fit the device —
        # refused here, by shape, as a counted policy decision served
        # by the host twin, not dispatched to fail and feed the breaker
        if _rules_cells(stack, cols.n) > RULES_DEV_MAX_CELLS:
            self._rul_stats["dev_refused"] += 1
            return False
        # the f32 gate binds even under a dev pin: the device kernel
        # cannot produce float64-correct results for these windows
        if stack.has_arith or not stack.f32_lits_safe:
            return False
        return cols.f32_safe()

    def _rules_device(self, stack, rev: int, cols, info=None) -> np.ndarray:
        """One device rules step: upload the stacked program (cached
        by the registry's ``rev``), pad rules/window/planes/literals
        to power-of-two buckets (bounded shape classes, as
        `_decide_device` does), run the fused kernel, slice the
        padding off."""
        if failpoints.enabled:
            # chaos seam: an injected error degrades this window to
            # the host twin and feeds the shared device breaker
            failpoints.evaluate("dispatch.rules.device")
        return self._rules_run(
            stack, rev, cols.n, cols.lit_ranks, cols.num, cols.sid,
            cols.err, cols.prs, info,
        )

    def _rules_run(
        self, stack, rev: int, w_n: int, lit_ranks, num, sid, err, prs,
        info=None,
    ) -> np.ndarray:
        """The padded kernel call behind `_rules_device` and
        `_warm_rules`: ``[P, w_n]`` planes in, ``[R, w_n]`` matrix
        out."""
        from .ops.match_kernel import rules_eval_batch

        r_n = stack.n_rules
        rpad = _pow2_at_least(r_n, 8)
        wpad = _pow2_at_least(w_n, 16)
        ppad = _pow2_at_least(num.shape[0], 1)

        def padr(a, fill, dtype):  # [R, S] -> [rpad, S]
            out = np.full((rpad,) + a.shape[1:], fill, dtype=dtype)
            out[: a.shape[0]] = a
            return out

        cache = self._rul_prog_cache
        if cache is None or cache[0] != (rev, rpad):
            import jax

            prog = (
                padr(stack.code, 0, np.int32),
                padr(stack.a0, -1, np.int32),
                padr(stack.a1, -1, np.int32),
                padr(stack.a2, -1, np.int32),
                padr(stack.a3, -1, np.int32),
                padr(stack.litn, 0.0, np.float32),
                padr(stack.last, 0, np.int32),
            )
            cache = (
                (rev, rpad),
                tuple(jax.device_put(a) for a in prog),
            )
            self._rul_prog_cache = cache
        code, a0, a1, a2, a3, litn, last = cache[1]

        def padw(a, fill, dtype):  # [P, W] -> [ppad, wpad]
            out = np.full((ppad, wpad), fill, dtype=dtype)
            out[: a.shape[0], :w_n] = a
            return out

        lits = np.zeros(_pow2_at_least(len(lit_ranks), 1), np.int32)
        lits[: len(lit_ranks)] = lit_ranks
        planes = (
            padw(num, np.nan, np.float32),
            padw(sid, -1, np.int32),
            padw(err, False, bool),
            padw(prs, False, bool),
        )
        t0 = time.perf_counter() if info is not None else 0.0
        mat = np.asarray(rules_eval_batch(
            code, a0, a1, a2, a3, litn, lits, last, *planes
        ))
        if info is not None:
            info["device_wait"] = (t0, time.perf_counter() - t0)
        return mat[:r_n, :w_n]

    def _warm_rules(self, max_batch: int) -> None:
        """Compile the rules kernel for the registered program at every
        window bucket up to ``max_batch``: its first-use compile takes
        tens of seconds, and paid in the dispatch window it stalls
        ordered dispatch while queued match windows run past the
        breaker deadline.  The planes are empty (every cell null), so
        the warm windows decide nothing."""
        src = self.rules_source() if self.rules_source else None
        if src is None or self.use_device is False or self._brk_open:
            return
        stack, rev = src
        if (
            not stack.n_rules or stack.has_arith
            or not stack.f32_lits_safe
            or self.rules_force == "host"
        ):
            return
        n_p = len(stack.paths)
        t0 = time.perf_counter()
        bp = 16
        while bp <= max_batch:
            if _rules_cells(stack, bp) > RULES_DEV_MAX_CELLS:
                break  # refused by shape in the served path too
            self._rules_run(
                stack, rev, bp, np.zeros(len(stack.lit_strings), np.int32),
                np.full((n_p, bp), np.nan), np.full((n_p, bp), -1, np.int32),
                np.zeros((n_p, bp), bool), np.zeros((n_p, bp), bool),
            )
            bp *= 2
        self._rul_dev_warm = True
        prof = self.profiler
        if prof is not None:
            prof.event(
                "xla_compile", time.perf_counter() - t0,
                rules=stack.n_rules, steps=stack.n_steps,
            )

    def match_batch(
        self, topics: Sequence[str], congested: bool = False
    ) -> List[Set[Hashable]]:
        """Staged so the device step runs lock-free on an immutable
        snapshot: encode/snapshot under the mutation lock, kernel
        outside it, overlay (exact/delta/deep/deleted — possibly newer
        than the snapshot, which only *adds* correctness) under it
        again.

        ``use_device=None`` (the broker default) resolves host-vs-
        device PER WINDOW via `_auto_choose`; True/False pin the path
        (benches and tests rely on the pinned behavior)."""
        return self.match_batch_finish(
            self.match_batch_submit(topics, congested)
        )

    def match_batch_submit(
        self, topics: Sequence[str], congested: bool = False,
        _force_device: bool = False,
    ):
        """Phase 1: decide the path, and for a device window ENCODE +
        DISPATCH the kernels without waiting (JAX async dispatch).
        The pending handle this returns pipelines: the broker submits
        windows N+1..N+k while window N's transfer streams back, so
        e2e throughput amortizes the host<->device round-trip from ONE
        thread — executor-thread concurrency does NOT overlap the
        transfer wait (the blocking conversion serializes), async
        dispatch does (the standalone bench's depth-8 scheme)."""
        tm = self._laps()
        words = [T.words(t) for t in topics]
        tm.lap("tokenize")
        with self._mlock:
            if self._built is not None:
                self._poll_swap()
            device_capable = (
                self.use_device is not False
                and self._aut is not None
                and self._aut.n_nodes > 1
            )
            if device_capable and self._brk_open and not _force_device:
                # breaker open: host-only until the background probe
                # re-closes it (failure-driven degradation)
                device_capable = False
                self._brk_maybe_probe()
            if _force_device and device_capable:
                device_on = True
            elif device_capable and self.use_device is None:
                device_on = (
                    True if self._warmup_force
                    else self._auto_choose(len(words), congested)
                )
            else:
                device_on = device_capable
            snap_failed = False
            if device_on:
                try:
                    snap = self._snapshot_refs()
                except Exception:
                    # lazy device upload failed: a device fault, so it
                    # feeds the breaker and the window serves on host
                    import logging

                    logging.getLogger("emqx_tpu.engine").exception(
                        "device snapshot failed; window falls back to "
                        "host matching"
                    )
                    device_on = False
                    snap_failed = True
                    self._device_failure()
                else:
                    tp("match_snapshot",
                       watermark=self._fold_watermark)
        if not device_on:
            # per-topic locking: holding _mlock across the whole batch
            # would stall a loop-thread SUBSCRIBE (and with it the
            # entire event loop) for the full window when this runs in
            # the batcher's executor
            c0 = time.thread_time()
            out: List[Set[Hashable]] = []
            for ws in words:
                with self._mlock:
                    out.append(self.match_host(ws))
            if device_capable and len(words) >= 64:
                us = (time.thread_time() - c0) / len(words) * 1e6
                self._host_us = (
                    us if self._host_us is None
                    else 0.8 * self._host_us + 0.2 * us
                )
                self._auto_stats["host_windows"] += 1
                # keep a fresh sample for the out-of-band device probe
                # (small: each probe's host-side cost is paid in GIL)
                self._probe_topics = list(topics[:256])
            return (
                "host-fallback" if snap_failed else "host", out,
                tm.timings(),
            )
        t0 = time.perf_counter()
        c0 = time.thread_time()
        try:
            # dispatch the delta kernel FIRST (async JAX dispatch) so
            # the small fixed-shape call overlaps the base kernel +
            # transfer
            daut, ddev, _ = snap[6]
            dpend = (
                self._flat_dispatch(daut, ddev, words, tm)
                if daut is not None
                else None
            )
            pend_base = self._flat_submit(snap, words, tm)
        except Exception:
            # a dispatch-side device fault (encode upload, compile,
            # injected engine.device_step error): count it toward the
            # breaker and serve THIS window on the host oracle —
            # per-topic locking, as in the host branch above.  The
            # distinct tag keeps the profiler's path attribution
            # honest: this window is a FALLBACK, not a policy choice
            import logging

            logging.getLogger("emqx_tpu.engine").exception(
                "device dispatch failed for window of %d; host "
                "fallback", len(words),
            )
            self._device_failure()
            out = []
            for ws in words:
                with self._mlock:
                    out.append(self.match_host(ws))
            return ("host-fallback", out, tm.timings())
        if len(words) >= 64:
            # keep a fresh sample for the breaker probe: after a trip
            # the device path stops running, and probing with recent
            # REAL topics measures what production windows would see
            self._probe_topics = list(topics[:256])
        cpu0 = time.thread_time() - c0  # encode + dispatch CPU
        return (
            "dev", snap, pend_base, dpend, topics, words, t0, cpu0,
            tm.timings(),
        )

    def _laps(self, seq: int = 0):
        """A lap clock for one submit or finish while the profiler is
        on, else the no-op: the engine times its own sections, wall
        and (they run on one executor thread) that thread's CPU, and
        hands them back in the pending handle and in ``info``; the
        caller lays them on its window's record."""
        prof = self.profiler
        return (
            CpuLaps(seq) if prof is not None and prof.enabled else NO_LAPS
        )

    @staticmethod
    def submit_timings(pending) -> Sequence[Tuple[str, float, float]]:
        """``(name, start, dur)`` of the sections `match_batch_submit`
        timed (``tokenize``, ``encode``, ``kernel_dispatch``): the last
        element of every pending handle, a subclass's too; ``start``
        is on the perf_counter clock, and None for a section's CPU
        seconds (``<name>_cpu``, behind the wall spans)."""
        return pending[-1]

    def _flat_submit(self, snap: Tuple, words: Sequence[T.Words],
                     tm=NO_LAPS):
        """Overridable async-dispatch hook for the base snapshot:
        subclasses whose flat path is synchronous (the sharded mesh
        engine's shard_map call) override this to compute eagerly."""
        return ("pend", self._flat_dispatch(snap[0], snap[1], words, tm))

    def _flat_result(self, token, tm=NO_LAPS):
        kind, v = token
        return self._flat_finish(v, tm) if kind == "pend" else v

    def match_batch_finish(self, pending, info=None) -> List[Set[Hashable]]:
        """Phase 2: wait for the device results (if any), overlay the
        host tiers, update the auto-policy cost EWMAs.  CPU is
        accounted with thread_time so a transfer wait that BURNS
        host cycles is charged to the device path honestly, while a
        true DMA wait (GIL released) is not.

        ``info`` (optional dict) receives ``path``: the path that
        ACTUALLY served the window — ``dev``, ``host``, or
        ``host-fallback`` when a device fault degraded it here — so
        the profiler's flight record never labels a fallback window
        as a device window.  While the profiler is on it also receives
        ``timings``: ``(name, start, dur)`` of the sections timed here
        (``device_wait``, ``expand_codes``, ``dense_rematch`` once a
        compact clip, ``overlay_lock_wait``, ``overlay``, and inside
        it ``overlay_host`` where a row went to the host trie; each
        working section's CPU seconds as ``<name>_cpu`` with no
        start); the caller's ``seq`` in it tags their trace
        annotations.  A ``dev``
        window also leaves ``host_rows`` there: the rows a kernel
        flagged, which `_overlay` matched on the host; and
        ``residual``: the filters the residual held at the window's
        snapshot, which every row of it matched on the host trie."""
        if pending[0] != "dev":
            if info is not None:
                info["path"] = pending[0]
            return pending[1]
        tm = NO_LAPS
        if info is not None:
            info["path"] = "dev"
            tm = self._laps(info.get("seq", 0))
        _, snap, pend_base, dpend, topics, words, t0, cpu0, _ = pending
        t1w = time.perf_counter()
        c1 = time.thread_time()
        try:
            rows, gpos, ovf = self._flat_result(pend_base, tm)
            dflat = (
                self._flat_finish(dpend, tm) if dpend is not None else None
            )
        except Exception:
            # the wait/transfer side of the device step failed: breaker
            # food, and the window re-matches on the host oracle
            import logging

            logging.getLogger("emqx_tpu.engine").exception(
                "device result failed for window of %d; host fallback",
                len(words),
            )
            self._device_failure()
            tm.unmark()
            if info is not None:
                info["path"] = "host-fallback"
                info["timings"] = tm.timings()
            return self.match_batch_host(list(topics))
        self._device_ok(time.perf_counter() - t0)
        tp("match_overlay")
        with self._mlock:
            tm.lap("overlay_lock_wait", then="overlay")
            tp("overlay_locked")
            out, n_host = self._overlay(
                topics, words, rows, gpos, ovf, snap, dflat, tm
            )
            tm.lap("overlay")
        if info is not None:
            info["timings"] = tm.timings()
            info["host_rows"] = n_host
            info["residual"] = snap[8]
        if self.use_device is None and len(words) >= 64:
            cpu_us = (
                (cpu0 + time.thread_time() - c1) / len(words) * 1e6
            )
            self._dev_cpu_us = (
                cpu_us if self._dev_cpu_us is None
                else 0.8 * self._dev_cpu_us + 0.2 * cpu_us
            )
            # the wall EWMA feeds LATENCY-mode decisions, so it must
            # estimate a SOLO window's round trip.  Only unqueued
            # windows (finish started right after submit) qualify:
            # a pipelined window's submit→finish wall includes time
            # queued behind predecessors (charging that to the device
            # disabled it with its own backlog — review r5), while its
            # finish-only wall UNDER-estimates (the transfer already
            # streamed during the queue wait) and flipped quiet
            # windows onto the device.
            if t1w - t0 < 0.005:
                wall = time.perf_counter() - t0
                self._dev_window_s = (
                    wall if self._dev_window_s is None
                    else 0.8 * self._dev_window_s + 0.2 * wall
                )
            self._auto_stats["dev_windows"] += 1
        return out

    def match_batch_host(self, topics: Sequence[str]) -> List[Set[Hashable]]:
        """Pure-host batch match (the device-failure fallback path)."""
        out: List[Set[Hashable]] = []
        for t in topics:
            with self._mlock:
                out.append(self.match_host(T.words(t)))
        return out

    def _overlay(
        self, topics, words, rows, gpos, ovf, snap, dflat=None, tm=NO_LAPS
    ) -> Tuple[List[Set[Hashable]], int]:
        """The window's answers, and how many of its rows the host
        trie gave: the rows a kernel flagged (``ovf``, base or delta),
        counted from the flag vector and matched in one pass that
        ``tm`` times as ``overlay_host``; a window without one pays
        neither."""
        fid_arr, delta, deep = snap[2], snap[3], snap[4]
        deleted_base, deleted_daut = snap[5], snap[7]
        fids_flat = fid_arr[gpos]
        per_row = np.bincount(rows, minlength=len(words))
        chunks = np.split(fids_flat, np.cumsum(per_row)[:-1])
        dchunks = None
        if dflat is not None:
            drows, dgpos, dovf = dflat
            dflat_fids = snap[6][2][dgpos]
            dper = np.bincount(drows, minlength=len(words))
            dchunks = np.split(dflat_fids, np.cumsum(dper)[:-1])
            ovf = ovf | dovf  # either kernel overflowing -> host row
        n_host = int(ovf.sum())
        hosted: Dict[int, Set[Hashable]] = {}
        if n_host:
            self._host_rows += n_host
            t_host = tm.now()
            for i in np.flatnonzero(ovf).tolist():
                hosted[i] = self.match_host(words[i])
            tm.nest("overlay_host", t_host)
        out: List[Set[Hashable]] = []
        for i, ws in enumerate(words):
            if i in hosted:
                out.append(hosted[i])
                continue
            # tombstones are per-generation: a fid deleted from the base
            # may live on (re-inserted) in the delta automaton, so each
            # kernel's chunk is masked by ITS OWN deleted set only
            fids: Set[Hashable] = set(chunks[i].tolist())
            if deleted_base:
                fids -= deleted_base
            if dchunks is not None:
                dfids = set(dchunks[i].tolist())
                if deleted_daut:
                    dfids -= deleted_daut
                fids |= dfids
            if self._exact:
                fids |= self._exact.get(topics[i], set())
            if len(delta):
                fids |= delta.match_words(ws)
            if len(deep):
                fids |= deep.match_words(ws)
            out.append(fids)
        return out, n_host

    def match_batch_flat(self, words: Sequence[T.Words]):
        """Device fast path: encoded topics -> flat row-sorted
        ``(topic_row, position)`` pairs into the base snapshot plus a
        per-row overflow flag.  The device ships only the compact code
        form; fan-out expansion happens host-side with vectorized CSR
        (`expand_codes_host`) — the SURVEY §7 amplification strategy.
        Rows flagged ``ovf`` must be re-matched on the host.  Callers
        must still overlay exact/delta/deep/deleted state."""
        with self._mlock:
            snap = self._snapshot_refs()
        return self._flat_from_snapshot(snap, words)

    def _flat_from_snapshot(self, snap: Tuple, words: Sequence[T.Words]):
        return self._flat_finish(self._flat_dispatch(snap[0], snap[1], words))

    def _encode_rows(self, words, levels: int):
        """Tokenize with a MATRIX row cache: live publish streams are
        Zipf-heavy, so the per-topic work collapses to one dict lookup
        yielding a row index, and the batch materializes as one numpy
        fancy-index gather instead of B per-row copies (the Python copy
        loop capped the full match path at ~⅓ of device throughput).
        Returns ``(idx, mat, lens, dol)`` — the row-index array doubles
        as the batch dedup key (`_flat_dispatch`).  The cache
        invalidates wholesale whenever the token dictionary grows (a
        previously-unknown word may now be a filter literal, making
        cached UNKNOWN rows stale)."""
        from .ops.dictionary import PAD_TOK

        with self._enc_mutex:
            gen = len(self._tdict)
            if gen != self._enc_gen:
                self._enc_cache.clear()
                self._enc_gen = gen
            def fresh_entry():
                cap = 4096
                return [
                    {},  # ws tuple -> row index
                    np.full((cap, levels), PAD_TOK, np.int32),
                    np.zeros(cap, np.int32),  # lengths
                    np.zeros(cap, bool),  # dollar
                    0,  # rows used
                ]

            entry = self._enc_cache.get(levels)
            if entry is None:
                entry = self._enc_cache[levels] = fresh_entry()
            # the hard-cap reset may only happen at a batch BOUNDARY,
            # and must allocate FRESH arrays: an in-flight batch on
            # another thread still gathers from the old ones after
            # releasing this mutex, so rows must never be overwritten
            # under it (growth and dict-clear paths already reallocate)
            elif entry[4] >= 262144:
                entry = self._enc_cache[levels] = fresh_entry()
            index, mat, lens, dol, used = entry
            b = len(words)
            # hit loop at C speed: one map() over the row cache (the
            # previous per-topic Python loop with numpy scalar stores
            # was ~1/3 of the full-path host cost)
            js = list(map(index.get, words))
            if None in js:
                miss_rows: Dict[Tuple[str, ...], int] = {}
                miss_ws: List[Tuple[str, ...]] = []
                for i, j in enumerate(js):
                    if j is None:
                        ws = words[i]
                        r = miss_rows.get(ws)
                        if r is None:
                            r = miss_rows[ws] = used + len(miss_ws)
                            miss_ws.append(ws)
                        js[i] = r
                need = used + len(miss_ws)
                while need > len(lens):  # grow by doubling
                    cap = len(lens) * 2
                    m2 = np.full((cap, levels), PAD_TOK, np.int32)
                    m2[: len(lens)] = mat
                    mat = m2
                    lens = np.resize(lens, cap)
                    dol = np.resize(dol, cap)
                    entry[1], entry[2], entry[3] = mat, lens, dol
                # _enc_mutex exists precisely to serialize the
                # native dictionary the first-use seeding touches
                # (see TokenDict.native's race note)
                # brokerlint: ignore[LOCK402]
                nat = self._tdict.native()
                if nat is not None and len(miss_ws) >= 16:
                    # batch the misses through the native tokenizer
                    # (GIL released, get-only lookups)
                    nat.encode_topics_into(
                        ["/".join(ws) for ws in miss_ws], levels,
                        mat[used:need], lens[used:need], dol[used:need],
                    )
                else:
                    get = self._tdict.get
                    for k, ws in enumerate(miss_ws):
                        n = min(len(ws), levels)
                        row = mat[used + k]
                        row[:] = PAD_TOK
                        for j2 in range(n):
                            row[j2] = get(ws[j2])
                        lens[used + k] = n
                        dol[used + k] = bool(ws) and ws[0].startswith("$")
                index.update(miss_rows)
                entry[4] = need
            idx = np.fromiter(js, np.int64, count=b)
            return idx, mat, lens, dol

    def _flat_dispatch(self, aut, tables, words: Sequence[T.Words],
                       tm=NO_LAPS):
        """Encode + launch the kernel; returns a pending handle without
        blocking (JAX async dispatch), so several automata (base +
        segments) overlap on the device and the host<->device link.
        ``tm`` takes the ``encode`` lap (everything since the previous
        one: path choice and snapshot too) and ``kernel_dispatch``.

        The batch is DEDUPLICATED first: publish windows are Zipf-heavy
        (hot topics repeat ~2x at bench scale), and matching each
        distinct topic once halves both the device step and the
        device->host code transfer.  The kernel returns the COMPACT
        layout (flat codes + int16 counts): the dense [B, m_cap] code
        matrix at a few-percent fill is mostly ``-1`` padding on the
        device->host link."""
        from .ops.match_kernel import match_batch_compact

        if failpoints.enabled:
            # chaos seam: error raises (breaker food), delay stalls the
            # step (watchdog food); evaluated per kernel dispatch
            failpoints.evaluate("engine.device_step")
        idx, mat, lens, dol = self._encode_rows(words, aut.kernel_levels)
        uniq, inv = np.unique(idx, return_inverse=True)
        tokens, lengths, dollar = _pad_batch(
            mat[uniq], lens[uniq], dol[uniq]
        )
        # compact-buffer capacity follows the observed fan-out: a live
        # broker window dedups to FEW unique topics each matching many
        # filters (100 uniques x fanout 9 overflows a 2x buffer), and
        # every clip costs a dense-kernel re-match — a second full
        # round-trip (+ possible compile) per window.  The multiplier
        # is sticky power-of-two (bounded shape-class ladder).
        c_cap = self._ccap_mult * tokens.shape[0]
        tm.lap("encode")
        flat, counts, total = match_batch_compact(
            *tables,
            tokens,
            lengths,
            dollar,
            f_width=self.f_width,
            m_cap=self.m_cap,
            c_cap=c_cap,
        )
        # start device->host copies immediately: results stream back
        # while later dispatches (delta automaton, next windows) compute,
        # instead of serializing on the round-trip at finish time
        flat.copy_to_host_async()
        counts.copy_to_host_async()
        total.copy_to_host_async()
        tm.lap("kernel_dispatch")
        return (
            aut, tables, flat, counts, total, (tokens, lengths, dollar),
            len(uniq), inv,
        )

    def _flat_finish(self, pending, tm=NO_LAPS):
        from .ops.automaton import expand_codes_dedup, expand_codes_flat

        (aut, tables, flat, counts, total, enc, n_uniq, inv) = pending
        tm.mark("device_wait")
        if int(np.asarray(total)[0]) > len(flat):
            # the compact buffer clipped: re-match this window on the
            # dense kernel — correct for any fill, just more bytes on
            # the wire — and DOUBLE the sticky capacity multiplier so
            # subsequent windows of this fan-out shape never clip
            # again.  The first clip at a given batch shape may pay
            # the dense kernel's compile; enable_compile_cache()
            # bounds that to once per shape EVER
            self._ccap_mult = min(self._ccap_mult * 2, 64)
            from .ops.match_kernel import match_batch

            tm.lap("device_wait", then="dense_rematch")
            codes, _, ovf = match_batch(
                *tables, *enc, f_width=self.f_width, m_cap=self.m_cap
            )
            rows, pos = expand_codes_dedup(
                aut.code_off, aut.code_idx,
                np.asarray(codes)[:n_uniq], inv,
            )
            ovf = np.asarray(ovf)[:n_uniq][inv]
            tm.lap("dense_rematch")
            return rows, pos, ovf
        # block on both results before the host work on either, so the
        # wait and the work are two spans
        counts = np.asarray(counts).astype(np.int64)
        flat = np.asarray(flat)
        tm.lap("device_wait", then="expand_codes")
        ovf_u = counts < 0
        counts_pos = np.where(ovf_u, -counts - 1, counts)
        rows, pos = expand_codes_flat(
            aut.code_off, aut.code_idx, flat, counts_pos, inv,
        )
        ovf = ovf_u[:n_uniq][inv]
        tm.lap("expand_codes")
        return rows, pos, ovf
