"""The rows a `dev` window hands back to the host trie, counted and
timed, and the frontier a built table can need (PR 33).

A `MatchEngine` over `benchmark/generators/plus_tree.py`'s seven-level
tree of `+` filters, BASELINE.json configs[1] in small: at `f_width` 32
the device matches every row, at the shipped 16 the kernel flags the
rows whose frontier passes it and `_overlay` matches them on the host.
Both give the answers of a plain dictionary trie written here, which
also measures each topic's frontier.  All on the CPU at 5,000
subscriptions; none of these numbers is a device number."""

import itertools
import logging
import os
import sys
from math import prod

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, "benchmark"))

import traffic  # noqa: E402

from emqx_tpu.broker.broker import Broker  # noqa: E402
from emqx_tpu.config import BrokerConfig  # noqa: E402
from emqx_tpu.engine import MatchEngine  # noqa: E402
from emqx_tpu.message import Message  # noqa: E402
from emqx_tpu.observability import NO_LAPS, Laps, Profiler, WindowRecord  # noqa: E402
from emqx_tpu.ops.automaton import build_automaton  # noqa: E402
from emqx_tpu.ops.dictionary import TokenDict  # noqa: E402

LEVELS = [4, 4, 4, 8, 8, 16, 64]
MASKS = ["+LLLLLL", "L+LLLLL", "LL+LLLL", "LLL+LLL", "LLLL+LL", "LLLLL+L",
         "LLLLLL+", "++LLLLL", "L++LLLL", "LL++LLL", "LLL++LL", "LLLL++L",
         "+L+LLLL", "L+L+LLL", "+++LLLL", "L+++LLL", "+L+L+LL", "LLLLL++",
         "LL+L+L+", "L+LL+L+"]


def room(levels, mask):
    return prod(n for n, m in zip(levels, mask) if m == "L")


class PlainTrie:
    """A dictionary a level, walked one topic at a time: MQTT's rule for
    `+` and a trailing `#`, and the widest frontier the walk saw."""

    def __init__(self, pairs):
        self.root = {}
        for flt, fid in pairs:
            node = self.root
            for w in flt.split("/"):
                node = node.setdefault(w, {})
            node.setdefault(None, set()).add(fid)

    def walk(self, topic):
        frontier, out, widest = [self.root], set(), 1
        for w in topic.split("/"):
            for node in frontier:
                out |= node.get("#", {}).get(None, set())
            frontier = [node[k] for node in frontier for k in (w, "+")
                        if k in node]
            widest = max(widest, len(frontier))
        for node in frontier:
            out |= node.get(None, set()) | node.get("#", {}).get(None, set())
        return out, widest


@pytest.fixture(scope="module")
def tree():
    """5,000 subscriptions of the twenty masks, 1,500 seeded topics (a
    tenth of them outside the tree), and what the plain trie says."""
    pairs, pops = traffic.generate("table", {
        "generator": "plus_tree", "subscriptions": 5000, "levels": LEVELS,
        "masks": [[m, min(room(LEVELS, m), 8000)] for m in MASKS],
    })
    topics = traffic.generate(
        "pool", {"generator": "plus_tree", "pool": 1500, "nomatch": 0.1},
        np.random.default_rng(33), pops=pops,
    )
    assert len(set(topics)) == len(topics)
    plain = PlainTrie(pairs)
    walked = [plain.walk(t) for t in topics]
    return pairs, topics, [w[0] for w in walked], [w[1] for w in walked]


def engine(pairs, f_width, profiled=False):
    eng = MatchEngine(f_width=f_width, use_device=True)
    if profiled:
        eng.profiler = Profiler()
    eng.insert_many(pairs)
    eng.rebuild()
    return eng


def test_at_width_32_the_device_matches_every_row(tree):
    pairs, topics, want, widest = tree
    eng = engine(pairs, 32)
    assert eng.stats()["frontier_need"] == 20 >= max(widest)
    info = {}
    got = eng.match_batch_finish(eng.match_batch_submit(topics), info=info)
    assert got == want and sum(map(len, want)) > 0
    assert info["path"] == "dev" and info["host_rows"] == 0
    assert eng.stats()["host_rows"] == 0


def test_at_width_16_the_flagged_rows_are_the_hosts_and_are_counted(tree):
    pairs, topics, want, widest = tree
    wide = sum(w > 16 for w in widest)
    assert 0 < wide < len(topics)
    eng = engine(pairs, 16)
    info = {}
    got = eng.match_batch_finish(eng.match_batch_submit(topics), info=info)
    assert got == want
    # the topics are distinct and no row passes `m_cap`: the rows the
    # kernel flags are the ones whose frontier passes the width
    assert info["path"] == "dev" and info["host_rows"] == wide
    assert eng.stats()["host_rows"] == wide
    # a second window adds its own; a whole-window host path adds none
    eng.match_batch(topics[:300])
    assert eng.stats()["host_rows"] == wide + sum(
        w > 16 for w in widest[:300]
    )
    before = eng.stats()["host_rows"]
    eng.use_device = False
    assert eng.match_batch(topics[:300]) == want[:300]
    assert eng.stats()["host_rows"] == before


@pytest.mark.parametrize("f_width,spans", [(32, 0), (16, 1)])
def test_overlay_host_is_a_span_only_where_a_row_was_flagged(
        tree, f_width, spans):
    pairs, topics, want, _ = tree
    eng = engine(pairs, f_width, profiled=True)
    info = {"seq": 7}
    assert eng.match_batch_finish(
        eng.match_batch_submit(topics), info=info
    ) == want
    timed = {}
    for name, start, dur in info["timings"]:
        if start is not None:  # (a section's CPU seconds have none)
            timed.setdefault(name, []).append((start, start + dur))
    assert len(timed.get("overlay_host", [])) == spans
    assert len(timed["overlay"]) == 1
    if spans:
        (h0, h1), (o0, o1) = timed["overlay_host"][0], timed["overlay"][0]
        assert o0 <= h0 < h1 <= o1  # nested, one clock pair a window
    # the profiler off: no clock is read, the count is still kept
    eng.profiler = None
    info = {}
    eng.match_batch_finish(eng.match_batch_submit(topics), info=info)
    assert info["timings"] == () and (info["host_rows"] > 0) == bool(spans)


@pytest.mark.parametrize("f_width", [32, 16])
def test_window_records_sum_to_the_growth_of_host_rows(tree, f_width):
    pairs, topics, _, widest = tree
    cfg = BrokerConfig()
    cfg.engine.use_device = True
    cfg.engine.f_width = f_width
    broker = Broker(config=cfg)
    eng = broker.router.engine
    eng.insert_many(pairs)
    eng.rebuild()
    before = eng.stats()["host_rows"]
    for at in range(0, 900, 300):
        broker.publish_many([Message(topic=t, payload=b"x")
                             for t in topics[at:at + 300]])
    wins = broker.profiler.windows(10)
    assert len(wins) == 3 and all(w["path"] == "dev" for w in wins)
    grown = eng.stats()["host_rows"] - before
    assert sum(w["n_host_rows"] for w in wins) == grown
    assert grown == sum(w > f_width for w in widest[:900])
    for w in wins:
        st = w["stages_us"]
        assert ("overlay_host" in st) == (w["n_host_rows"] > 0)
        assert st.get("overlay_host", 0.0) <= st["overlay"]
    assert (grown > 0) == (f_width == 16)


def test_a_record_carries_the_field_before_any_window_sets_it():
    rec = WindowRecord(1, 8, "publish")
    assert rec.to_dict()["n_host_rows"] == 0
    start = rec.now()
    rec.lap("prepare")
    rec.nest("inner", start)
    # a record's laps stay a contiguous chain: what nests is a sub-span
    assert [s[0] for s in rec.spans] == ["prepare"]
    assert [s[0] for s in rec.subs] == ["inner"]


def test_laps_nest_a_span_and_the_no_op_reads_no_clock():
    tm = Laps(3)
    tm.lap("a")
    start = tm.now()
    tm.nest("inside_b", start)
    tm.lap("b")
    names = [n for n, _, _ in tm.timings()]
    assert names == ["a", "inside_b", "b"]
    (_, s_in, d_in), (_, s_b, d_b) = tm.timings()[1:]
    assert s_b <= s_in and s_in + d_in <= s_b + d_b
    assert NO_LAPS.now() == 0.0
    NO_LAPS.nest("x", 0.0)
    assert NO_LAPS.timings() == ()


# ------------------------------------------------------ frontier_need

def brute_widest(filters, pops, roots=("tele",)):
    """The widest frontier over EVERY topic of the tree (and one level
    deeper), by the plain trie."""
    plain = PlainTrie([(f, i) for i, f in enumerate(filters)])
    ids = [[f"{chr(97 + at)}{i}" for i in range(n)]
           for at, n in enumerate(pops)]
    return max(
        plain.walk("/".join((root,) + words + ("deeper",)))[1]
        for root in roots for words in itertools.product(*ids)
    )


def need_of(filters):
    aut = build_automaton(
        [(i, tuple(f.split("/"))) for i, f in enumerate(filters)],
        TokenDict(),
    )
    return aut.frontier_need


def test_frontier_need_is_the_widest_frontier_of_a_full_tree():
    levels = [2, 3, 2, 2]
    masks = ["".join(m) for m in itertools.product("L+", repeat=4)
             if "+" in m]
    pairs, pops = traffic.generate("table", {
        "generator": "plus_tree", "levels": levels,
        "subscriptions": sum(room(levels, m) for m in masks),
        "masks": [[m, room(levels, m)] for m in masks],
    })
    filters = [f for f, _ in pairs]
    assert len(set(filters)) == len(filters)
    # every shape at every depth is there: 8 of them at the last
    # level but one, 15 at the last
    assert need_of(filters) == brute_widest(filters, pops) == 15


@pytest.mark.parametrize("filters,pops,roots", [
    # a sparse tree of `+` alone: a few filters of six shapes
    ([f for f, _ in traffic.generate("table", {
        "generator": "plus_tree", "subscriptions": 40, "levels": [3, 3, 3, 3],
        "masks": [["+LLL", 1], ["L+LL", 1], ["LL+L", 1], ["++LL", 1],
                  ["L++L", 1], ["+L+L", 1]]})[0]], [3, 3, 3, 3], ("tele",)),
    # a `#` family among them: the body stops short, the flag sits on
    # a node and makes no node of its own
    (["tele/a0/#", "tele/+/b1/#", "tele/+/+/c0", "tele/a1/+/#",
      "tele/+/b0/+", "tele/#", "+/a0/b0/c0", "#"], [2, 2, 2], ("tele", "x")),
    # one shape, many literals: the need is 1 + the `+` path
    (["tele/a0/+"] + [f"tele/a{i}/b{j}" for i in range(3) for j in range(3)],
     [3, 3], ("tele",)),
], ids=["sparse-plus", "hash-family", "one-shape"])
def test_frontier_need_is_never_under_the_widest_frontier(filters, pops,
                                                          roots):
    need = need_of(filters)
    assert brute_widest(filters, pops, roots) <= need
    assert need <= len(filters)


def test_no_filter_needs_a_frontier_of_one_and_stats_says_zero_before_a_build():
    assert need_of([]) == 1
    assert need_of(["a/b/c", "a/b/d"]) == 1
    eng = MatchEngine(use_device=True)
    assert eng.stats()["frontier_need"] == 0
    assert eng.stats()["host_rows"] == 0


def test_a_build_that_needs_more_than_the_width_says_so(tree, caplog):
    pairs = tree[0]
    with caplog.at_level(logging.WARNING, logger="emqx_tpu.engine"):
        eng = MatchEngine(f_width=32, use_device=True)
        eng.insert_many(pairs)
        eng.rebuild()
    assert not [r for r in caplog.records if "frontier" in r.getMessage()]
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="emqx_tpu.engine"):
        eng = MatchEngine(f_width=16, use_device=True,
                          rebuild_threshold=1 << 20)
        eng.insert_many(pairs)
        eng.rebuild()
    said = [r.getMessage() for r in caplog.records
            if "frontier" in r.getMessage()]
    assert len(said) == 1  # one build, one warning
    assert "base automaton can need a frontier of 20" in said[0]
    assert "rows whose frontier passes 16 are matched on the host" in said[0]


def test_the_delta_automaton_counts_and_reports_as_the_base_does(caplog):
    """A base of one shape and a folded delta of nine at `f_width` 8:
    `stats()` reports the wider of the two, the fold warns, and the
    rows the DELTA kernel flags are counted like the base's."""
    base = [(f"tele/a{i}/+/c{i % 4}", f"b{i}") for i in range(64)]
    masks = ["".join(m) for m in itertools.product("L+", repeat=4)
             if m.count("+") >= 2][:9]
    live = [f for m in masks for f, _ in traffic.generate("table", {
        "generator": "plus_tree", "subscriptions": room([4] * 4, m),
        "levels": [4] * 4, "masks": [[m, 1]]})[0]]
    eng = MatchEngine(f_width=8, use_device=True, delta_aut_threshold=32)
    eng._fold_async = False  # the fold inline, at a point the test knows
    eng.insert_many(base)
    eng.rebuild()
    assert eng.stats()["frontier_need"] == 1  # one shape, `L+L`
    np.random.default_rng(9).shuffle(live)  # every shape in every fold
    with caplog.at_level(logging.WARNING, logger="emqx_tpu.engine"):
        for i, f in enumerate(live):
            eng.insert(f, f"l{i}")
    assert eng.index_stats()["folded"] > 0
    assert eng.stats()["frontier_need"] == 9
    assert any("delta automaton can need a frontier of 9"
               in r.getMessage() for r in caplog.records)
    plain = PlainTrie(base + [(f, f"l{i}") for i, f in enumerate(live)])
    topics = ["/".join(("tele",) + w) for w in itertools.product(
        *[[f"{chr(97 + at)}{i}" for i in range(4)] for at in range(4)])]
    info = {}
    got = eng.match_batch_finish(eng.match_batch_submit(topics), info=info)
    assert got == [plain.walk(t)[0] for t in topics]
    assert info["host_rows"] == eng.stats()["host_rows"] > 0


def test_a_sharded_index_needs_what_its_widest_shard_needs():
    from emqx_tpu.parallel.sharded import build_sharded_index

    filters = [(i, tuple(f.split("/"))) for i, f in enumerate(
        ["t/+/b", "t/a/+", "t/+/+", "t/a/b", "u/+", "u/x"])]
    index = build_sharded_index(filters, TokenDict(), 2)
    assert index.frontier_need == max(
        a.frontier_need for a in index.shards
    ) >= 1
