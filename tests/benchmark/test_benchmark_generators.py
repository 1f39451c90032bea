"""A deployment's generators come by name (`traffic.generator`): the six
built-ins first, byte for byte what they were, then a file under
`benchmark/generators/`.  And the first such file, `plus_tree.py`, keeps
the contract of README.md, "A generator"."""

import hashlib
import json
import os
import sys
from collections import Counter

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import referee as R  # noqa: E402
import traffic as TR  # noqa: E402

# ------------------------------------------------------------ resolution


def test_a_built_in_wins_and_a_file_is_found():
    assert TR.generator("table", "fleet_families") is TR.table_fleet_families
    assert TR.generator("live", "exact_fanout") is TR.live_exact_fanout
    assert TR.generator("pool", "fleet_zipf") is TR.pool_fleet_zipf
    for kind in ("table", "live", "pool"):
        fn = TR.generator(kind, "plus_tree")
        assert fn.__name__ == kind
        assert fn.__code__.co_filename == os.path.join(
            TR.GENERATORS, "plus_tree.py"
        )


@pytest.mark.parametrize("kind,name", [
    ("table", "nobody"), ("pool", "fleet_live"), ("live", "../traffic"),
])
def test_an_unknown_name_is_refused_with_both_places_named(kind, name):
    with pytest.raises(TR.BadGenerator) as e:
        TR.generator(kind, name)
    assert "traffic.py" in str(e.value)
    assert os.path.join(TR.GENERATORS, name + ".py") in str(e.value)


def test_a_file_that_shadows_a_built_in_is_refused(tmp_path, monkeypatch):
    monkeypatch.setattr(TR, "GENERATORS", str(tmp_path))
    (tmp_path / "fleet_zipf.py").write_text("def pool(rng, pops):\n return []\n")
    (tmp_path / "only_table.py").write_text("def table():\n return [], ()\n")
    # whichever kind asks for the name, the file is what is refused
    for kind in ("pool", "table"):
        with pytest.raises(TR.BadGenerator) as e:
            TR.generator(kind, "fleet_zipf")
        assert "traffic.py" in str(e.value) and "fleet_zipf.py" in str(e.value)
    assert TR.generator("pool", "exact_topics") is TR.pool_exact
    assert TR.generator("table", "only_table")() == ([], ())
    with pytest.raises(TR.BadGenerator, match="no function 'live'"):
        TR.generator("live", "only_table")


@pytest.mark.parametrize("kind,group", [
    ("live", {"generator": "exact_fanout", "subscribers": 4, "topics": 2,
              "qoss": 1}),
    ("table", {"generator": "fleet_families", "subscriptions": 40}),
    ("table", {"generator": "plus_tree", "subscriptions": 40,
               "levels": [4, 4], "masks": [["L+", 1]], "fanuot": 2}),
    ("table", {"generator": "plus_tree", "subscriptions": 40,
               "levels": [4, 4], "masks": [["L#", 1]]}),
    ("table", {"generator": "plus_tree", "subscriptions": 40,
               "levels": [4, 4], "masks": [["LL", 1]]}),
    ("table", {"generator": "plus_tree", "subscriptions": 40,
               "levels": [4, 4], "masks": [["L+", 1]]}),
    ("live", {"generator": "plus_tree", "subscribers": 4, "filters_each": 5,
              "levels": [4, 4], "masks": ["L+"]}),
])
def test_an_argument_a_generator_does_not_take_is_refused(kind, group):
    """A mistyped or missing key, a mask with no `+` or with a `#`, more
    distinct filters than the tree has: refused, not run as a default."""
    with pytest.raises(TR.BadGenerator, match=group["generator"]):
        TR.generate(kind, group)


def test_overrides_replace_a_group_whole_only_where_asked():
    import run as harness

    cell = "fleet-1m-rules.flood-qos1"
    swap = {"generator": "plus_tree", "subscriptions": 40,
            "levels": [4, 4], "masks": [["L+", 1]]}
    _c, work, conf, _m = harness.load_cell(cell, {
        "config": {"table": swap, "live": {"subscribers": 7}},
        "workload": {"topics": {"pool": 64}}, "replace": ["table"],
    })
    assert conf["table"] == swap
    assert conf["live"] == {"generator": "fleet_live", "subscribers": 7,
                            "filters_each": 4}
    assert work["topics"]["generator"] == "fleet_zipf"
    assert work["topics"]["pool"] == 64 and "zipf" in work["topics"]
    # merged key by key the swap inherits `fanout`, which is refused
    _c, _w, conf, _m = harness.load_cell(cell, {"config": {"table": swap}})
    assert conf["table"] == {**swap, "fanout": 8}


# ----------------------------------------- the built-ins, byte for byte

def digest(made) -> str:
    return hashlib.sha256(
        json.dumps(made, sort_keys=True).encode()
    ).hexdigest()[:16]


FLEET_POPS = (312, 125, 125, 62)    # table_fleet_families(5000, 8)[1]
# computed on the parent's traffic.py (commit e49a661), before the
# look-ups went through `generator`
PINNED = [
    ("table", {"generator": "fleet_families", "subscriptions": 5000,
               "fanout": 8}, "583aa22d8bf76430"),
    ("table", {"generator": "none"}, "72cd4c13a096b612"),
    ("live", {"generator": "fleet_live", "subscribers": 300,
              "filters_each": 4}, "2c9b843867332427"),
    ("live", {"generator": "exact_fanout", "subscribers": 40, "topics": 4},
     "358ad257009ffe1d"),
    ("live", {"generator": "exact_fanout", "subscribers": 40, "topics": 40,
              "qos": 1}, "f63fa03a1d59004a"),
    ("pool", ({"generator": "fleet_zipf", "pool": 2048, "pool_seed": 1,
               "zipf": 1.3}, FLEET_POPS, 3000000019, 512),
     "55412f9eb7cfad58"),
    ("pool", ({"generator": "exact_topics", "pool": 4}, (1, 1, 1, 1), 7, 8),
     "eb36a4a862716d15"),
    ("pool", ({"generator": "exact_topics", "pool": 40}, (1, 1, 1, 1), 7, 8),
     "6b2b57dc12868ba3"),
]


@pytest.mark.parametrize("kind,args,want", PINNED, ids=[
    f"{k}-{(a if k != 'pool' else a[0])['generator']}-{w[:4]}"
    for k, a, w in PINNED
])
def test_the_six_built_ins_make_what_they_made_on_the_parent(kind, args, want):
    if kind == "pool":
        made = TR.topic_pool(*args)
    else:
        made = TR.generate(kind, args)
    assert digest(made) == want
    if args == PINNED[0][1]:
        assert made[1] == FLEET_POPS


# -------------------------------------------------- plus_tree's contract

M7 = ["+LLLLLL", "L+LLLLL", "LL+LLLL", "LLL+LLL", "LLLL+LL", "LLLLL+L",
      "LLLLLL+", "++LLLLL", "L++LLLL", "LL++LLL", "LLL++LL", "LLLL++L",
      "LLLLL++", "+L+LLLL", "L+L+LLL", "+++LLLL", "L+++LLL", "+L+L+LL",
      "LL+L+L+", "L+LL+L+"]
TREES = {
    # five levels and six masks: the shape that keeps every frontier narrow
    "five-six": {
        "levels": [12, 10, 6, 4, 3], "subscriptions": 3000, "fanout": 1,
        "masks": [["L+LLL", 20], ["LL+LL", 25], ["LLL+L", 25],
                  ["LLLL+", 25], ["L++LL", 1], ["LL++L", 4]],
        "live_masks": ["L+++L", "L++L+", "L+L++", "LL+++", "+L+L+", "L++LL"],
        "subscribers": 60, "filters_each": 4,
    },
    # seven levels and twenty masks: the shape that does not
    "seven-twenty": {
        "levels": [4, 3, 3, 4, 3, 4, 6], "subscriptions": 2000, "fanout": 2,
        "masks": [[m, 1 + i % 3] for i, m in enumerate(M7)],
        "live_masks": M7[7:], "subscribers": 39, "filters_each": 3,
    },
    "one-level": {
        "levels": [50], "subscriptions": 3, "fanout": 3, "masks": [["+", 1]],
        "live_masks": ["+"], "subscribers": 2, "filters_each": 1,
    },
}


@pytest.fixture(params=sorted(TREES))
def tree(request):
    t = dict(TREES[request.param])
    t["pairs"], t["pops"] = TR.generate("table", {
        "generator": "plus_tree", "subscriptions": t["subscriptions"],
        "levels": t["levels"], "masks": t["masks"], "fanout": t["fanout"],
    })
    t["live"] = {"generator": "plus_tree", "subscribers": t["subscribers"],
                 "filters_each": t["filters_each"], "levels": t["levels"],
                 "masks": t["live_masks"]}
    t["topics"] = {"generator": "plus_tree", "pool": 2000, "nomatch": 0.1}
    return t


def mask_of(flt: str) -> str:
    return "".join("+" if w == "+" else "L" for w in flt.split("/")[1:])


def in_tree(topic: str, levels) -> bool:
    words = topic.split("/")
    return words[0] == "tele" and len(words) == 1 + len(levels) and all(
        w[0] == chr(97 + at) and 0 <= int(w[1:]) < n
        for at, (w, n) in enumerate(zip(words[1:], levels))
    )


def test_table_filters_are_plus_only_distinct_and_matched(tree):
    pairs, levels = tree["pairs"], tree["levels"]
    assert len(pairs) == tree["subscriptions"]
    assert tree["pops"] == tuple(levels)
    fids = [fid for _f, fid in pairs]
    assert len(set(fids)) == len(fids)
    by_filter = Counter(f for f, _fid in pairs)
    assert len(by_filter) == -(-tree["subscriptions"] // tree["fanout"])
    assert max(by_filter.values()) == tree["fanout"]
    masks = {m for m, _w in tree["masks"]}
    for f in by_filter:
        words = f.split("/")
        assert "#" not in f and "+" in words and words[0] == "tele"
        assert mask_of(f) in masks
        # some topic of the tree matches it: the filter with its
        # wildcards filled in is one
        topic = "/".join(
            f"{chr(96 + at)}0" if w == "+" else w
            for at, w in enumerate(words)
        )
        assert in_tree(topic, levels) and R.matches(topic, f)
    # every mask got its share, to within the rounding
    total = sum(w for _m, w in tree["masks"])
    for mask, w in tree["masks"]:
        n = sum(mask_of(f) == mask for f in by_filter)
        assert abs(n - len(by_filter) * w / total) <= len(tree["masks"])


def test_pool_is_wildcard_free_inside_the_tree_and_seed_permutes(tree):
    a = TR.topic_pool(tree["topics"], tree["pops"], 1, 16)
    assert len(a) == 2000
    assert not any("+" in t or "#" in t for t in a)
    outside = [t for t in a if not in_tree(t, tree["levels"])]
    assert len(outside) == 200
    assert all(t.startswith("nomatch/") for t in outside)
    # each id level is drawn over its whole population
    for at, n in enumerate(tree["levels"]):
        seen = {t.split("/")[1 + at] for t in a if in_tree(t, tree["levels"])}
        assert len(seen) == n
    # the same call gives the same pool; another seed the same multiset
    assert a == TR.topic_pool(tree["topics"], tree["pops"], 1, 16)
    b = TR.topic_pool(tree["topics"], tree["pops"], 2 ** 31 + 7, 16)
    assert a != b and sorted(a) == sorted(b)
    # another pool_seed draws anew
    c = TR.topic_pool({**tree["topics"], "pool_seed": 2}, tree["pops"], 1, 16)
    assert sorted(c) != sorted(a)


def test_live_filters_of_one_subscriber_are_disjoint_on_the_pool(tree):
    subs = TR.generate("live", tree["live"])
    assert subs == TR.generate("live", tree["live"])
    assert len(subs) == tree["subscribers"]
    assert [q for _c, _f, q in subs] == [j % 2 for j in range(len(subs))]
    assert all(q == 1 for _c, _f, q in
               TR.generate("live", {**tree["live"], "qos": 1}))
    for _cid, flts, _q in subs:
        assert len(set(flts)) == len(flts) == tree["filters_each"]
        assert all("#" not in f and "+" in f.split("/") for f in flts)
    pool = TR.topic_pool(tree["topics"], tree["pops"], 5, 16)
    owed = 0
    for topic in pool:
        for _cid, flts, _q in subs:
            n = sum(R.matches(topic, f) for f in flts)
            assert n <= 1, (topic, flts)
            owed += n
    assert owed > 0
    # and the referee, which checks the same on every run, takes them
    exp = R.Expected(pool, subs, 0, np.arange(4000))
    assert exp.n_deliveries == 2 * owed


def test_live_filters_pass_the_fold_threshold_at_a_cells_size():
    """At the sizes a cell would use the live set has at least 1,024
    distinct filters (`engine.delta_aut_threshold`), so that it is folded
    onto the device and not left to the host's residual."""
    subs = TR.generate("live", {
        "generator": "plus_tree", "subscribers": 300, "filters_each": 4,
        "levels": [64, 64, 16, 8, 4],
        "masks": TREES["five-six"]["live_masks"],
    })
    assert len({f for _c, flts, _q in subs for f in flts}) == 1200


def test_filter_tree_equals_the_programs_matching_on_a_plus_tree(tree):
    """`referee.FilterTree` against `emqx_tpu.topic`, filter by filter,
    on this tree's table and live filters."""
    from emqx_tpu import topic as T

    subs = TR.generate("live", tree["live"])
    flts = sorted({f for f, _fid in tree["pairs"]}
                  | {f for _c, fl, _q in subs for f in fl})[:700]
    ft = R.FilterTree()
    for f in flts:
        ft.add(f, f)
    pool = TR.topic_pool(tree["topics"], tree["pops"], 9, 16)[:150]
    n = 0
    for t in pool:
        want = sorted(f for f in flts
                      if T.match_words(T.words(t), T.words(f)))
        assert sorted(ft.match(t)) == want
        assert want == sorted(f for f in flts if R.matches(t, f))
        n += len(want)
    assert n > 0


# ------------------------------------------------------ churned filters

FLEET_CHURN = {"generator": "fleet_churn", "filters": 6000, "first_id": 244,
               "clients": 1000, "rate": 200, "dwell_s": 5.0, "qos": [0, 1],
               "churn_children": 1}


def test_fleet_churn_is_new_distinct_and_disjoint_on_the_fleets_pool():
    """At the fleet's own size: no churned filter is one the live set
    holds (each is a route the residual carries), no two are equal, and
    no topic of the pool matches two (so a connection may hold any of
    them at once); the pool publishes to some of them."""
    conf = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "fleet-1m-rules.json")))
    _pairs, pops = TR.generate("table", conf["table"])
    live = {f for _c, flts, _q in TR.generate("live", conf["live"])
            for f in flts}
    flts = TR.churn_filters(FLEET_CHURN, pops, 3004100001)
    assert len(flts) == len(set(flts)) == 6000 and not set(flts) & live
    pool = TR.topic_pool({"generator": "fleet_zipf", "pool": 65536},
                         pops, 1, 512)
    ch = R.Churned(pool, flts, {k: np.zeros(0) for k in (
        "conn", "filter", "qos", "sub", "suback", "unsub", "unsuback")},
        np.zeros(0, np.int64), np.zeros(0), np.zeros(0))
    assert (ch.filter_of >= 0).mean() > 0.04


def test_churn_filters_are_the_same_set_for_every_seed_in_another_order():
    pops = (62500, 25000, 25000, 12500)
    a = TR.churn_filters(FLEET_CHURN, pops, 1)
    b = TR.churn_filters(FLEET_CHURN, pops, 2 ** 31 + 7)
    assert a != b and sorted(a) == sorted(b)
    assert a == TR.churn_filters(FLEET_CHURN, pops, 1)
    assert Counter(f.split("/")[0] for f in a) == {
        "vehicles": 2000, "dev": 2000, "site": 2000}


@pytest.mark.parametrize("group", [
    {"generator": "fleet_churn", "filters": 10},
    {"generator": "fleet_churn", "filters": 10, "first_id": 5, "fanout": 2},
    {"generator": "fleet_churn", "filters": 0, "first_id": 5},
    {"generator": "nobody", "filters": 10, "first_id": 5},
])
def test_a_churn_group_the_generator_cannot_take_is_refused(group):
    with pytest.raises(TR.BadGenerator):
        TR.churn_filters(group, (10, 10, 10, 10), 1)


def test_a_churn_generator_comes_as_a_file(tmp_path, monkeypatch):
    monkeypatch.setattr(TR, "GENERATORS", str(tmp_path))
    (tmp_path / "pairs.py").write_text(
        "def churn(rng, pops, n):\n"
        "    return [f'p/{k}/#' for k in range(n)]\n"
    )
    (tmp_path / "twice.py").write_text(
        "def churn(rng, pops):\n    return ['a/#', 'a/#']\n"
    )
    flts = TR.churn_filters({"generator": "pairs", "n": 5, **{
        k: FLEET_CHURN[k] for k in TR.CHURN_KEYS}}, (), 9)
    assert sorted(flts) == [f"p/{k}/#" for k in range(5)]
    with pytest.raises(TR.BadGenerator, match="not distinct"):
        TR.churn_filters({"generator": "twice"}, (), 9)
