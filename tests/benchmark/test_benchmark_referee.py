"""The benchmark's plain reference (`benchmark/referee.py`) agrees with
the MQTT semantics the program implements, and its comparison fails
where an answer is missing, extra, doubled, out of order or at the
wrong QoS."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import referee as R  # noqa: E402
import traffic as TR  # noqa: E402

CASES = [
    ("a/b/c", "a/b/c", True), ("a/b/c", "a/+/c", True),
    ("a/b/c", "a/#", True), ("a", "a/#", True), ("a/b", "a", False),
    ("a", "a/b", False), ("a/b/c", "+/+", False), ("a/b", "#", True),
    ("$SYS/x", "#", False), ("$SYS/x", "+/x", False),
    ("$SYS/x", "$SYS/#", True), ("a//b", "a/+/b", True),
    ("a/b", "a/b/+", False), ("a/b/", "a/b/+", True),
]


@pytest.mark.parametrize("topic,flt,want", CASES)
def test_matches_against_the_spec_and_the_program(topic, flt, want):
    from emqx_tpu import topic as T

    assert R.matches(topic, flt) is want
    assert T.match_words(T.words(topic), T.words(flt)) is want
    tree = R.FilterTree()
    tree.add(flt, 1)
    assert (tree.match(topic) == [1]) is want


def test_tree_equals_pairwise_on_the_fleet_traffic():
    subs = TR.live_fleet(300, 4)
    _pairs, pops = TR.table_fleet_families(5000, 8)
    pool = TR.topic_pool(
        {"generator": "fleet_zipf", "pool": 2048}, pops, 5, 512
    )
    tree = R.FilterTree()
    for j, (_c, flts, _q) in enumerate(subs):
        for f in flts:
            tree.add(f, j)
    n = 0
    for t in pool[:400]:
        want = sorted(j for j, (_c, flts, _q) in enumerate(subs)
                      for f in flts if R.matches(t, f))
        assert sorted(tree.match(t)) == want
        n += len(want)
    assert n > 0


@pytest.mark.parametrize("i", range(10))
def test_rule_predicates_equal_the_programs_interpreter(i):
    from emqx_tpu.message import Message
    from emqx_tpu.rules.runtime import build_env, eval_where
    from emqx_tpu.rules.sql import parse_sql as parse

    rule = parse(TR.rule_sql(i))
    assert list(rule.froms) == [R.RULE_FROM[i % 5]]
    seqs = np.arange(0, 700, dtype=np.int64)
    got = R.rule_where(i, seqs)
    for s in seqs.tolist():
        env = build_env(Message(topic="t", payload=TR.payload_of(s), qos=1))
        assert bool(eval_where(rule.where, env)) == bool(got[s]), (i, s)


def _exact_run(n=400, k=4):
    subs = TR.live_exact_fanout(8, 4)
    pool = TR.topic_pool({"generator": "exact_topics", "pool": 4},
                         (1, 1, 1, 1), 1, k)
    sent = np.arange(n, dtype=np.int64)
    exp = R.Expected(pool, subs, 0, sent)
    received = [s.copy() for s in exp.sub_seqs]
    qos = [1 << q for _c, _f, q in subs]
    return exp, k, sent, received, qos


def _numbers(exp, k, acked, received, qos, device=None):
    nums, failed = R.judge(
        exp, k, acked, received, qos, np.zeros(0, np.int32),
        np.zeros(0, np.int64), device or {},
    )
    return {n: v for n, v, _lim in nums}, failed


def test_a_sound_run_compares_clean():
    exp, k, sent, received, qos = _exact_run()
    nums, failed = _numbers(exp, k, sent, received, qos)
    assert exp.n_deliveries == 2 * 400 and not any(nums.values())
    assert len(failed) == 0


@pytest.mark.parametrize("fault,number", [
    ("drop", "deliveries_missing"), ("extra", "deliveries_unexpected"),
    ("double", "deliveries_duplicated"), ("swap", "deliveries_out_of_order"),
    ("qos", "subscribers_wrong_qos"), ("unacked", "pubacks_missing"),
])
def test_each_fault_fails_its_number(fault, number):
    exp, k, sent, received, qos = _exact_run()
    acked = sent
    if fault == "drop":
        received[3] = np.delete(received[3], 10)
    elif fault == "extra":
        received[3] = np.append(received[3], received[2][0])
    elif fault == "double":
        received[3] = np.append(received[3], received[3][-1])
    elif fault == "swap":
        received[3][[4, 5]] = received[3][[5, 4]]
    elif fault == "qos":
        qos[1] = 1
    else:
        acked = sent[:-1]
    nums, failed = _numbers(exp, k, acked, received, qos)
    assert nums.pop(number) > 0
    if fault != "double":
        assert not any(nums.values()), nums
    assert (len(failed) > 0) == (fault in ("drop", "unacked"))


def test_rule_firings_are_held_to_the_predicates():
    subs = TR.live_fleet(10, 4)
    _pairs, pops = TR.table_fleet_families(5000, 8)
    pool = TR.topic_pool({"generator": "fleet_zipf", "pool": 512},
                         pops, 3, 16)
    sent = np.arange(2000, dtype=np.int64)
    exp = R.Expected(pool, subs, 10, sent)
    assert exp.n_firings > 0
    fr = np.concatenate([np.full(len(s), i, np.int32)
                         for i, s in enumerate(exp.rule_seqs)])
    fs = np.concatenate(exp.rule_seqs)
    qos = [1 << q for _c, _f, q in subs]
    nums, _ = R.judge(exp, 16, sent, exp.sub_seqs, qos, fr, fs, {})
    assert not any(v for _n, v, _l in nums)
    nums, failed = R.judge(exp, 16, sent, exp.sub_seqs, qos, fr[1:], fs[1:],
                           {"windows_not_dev": 2})
    bad = {n: v for n, v, _l in nums if v}
    assert bad == {"firings_missing": 1, "windows_not_dev": 2}
    assert len(failed) == 1


def test_every_seed_gets_the_same_work_in_another_order():
    _pairs, pops = TR.table_fleet_families(5000, 8)
    spec = {"generator": "fleet_zipf", "pool": 1024}
    a = TR.topic_pool(spec, pops, 1, 512)
    b = TR.topic_pool(spec, pops, 2 ** 31 + 7, 512)
    assert a != b and sorted(a) == sorted(b)
    assert a == TR.topic_pool(spec, pops, 1, 512)
    x = TR.poisson_schedule(100.0, 5.0, 1)
    y = TR.poisson_schedule(100.0, 5.0, 2 ** 31 + 7)
    assert len(x) == len(y) == 500 and x[-1] < 5.0 and y[-1] < 5.0
    assert not np.allclose(x, y)
    assert np.allclose(np.sort(np.diff(x, prepend=0))[5:-5],
                       np.sort(np.diff(y, prepend=0))[5:-5], atol=0.05)


def test_a_topic_on_two_filters_of_one_subscriber_is_refused_by_name():
    """The broker owes a delivery a subscription, `Expected` counts one
    a subscriber: a live set that overlaps on the pool is the data
    file's fault and must not read as `deliveries_duplicated`."""
    subs = [("sub0", ["tele/a1/+"], 1),
            ("sub1", ["tele/+/b2", "tele/a1/+", "tele/a2/b2"], 0)]
    pool = ["tele/a0/b0", "tele/a1/b2", "tele/a1/b1"]
    with pytest.raises(R.Overlap) as e:
        R.Expected(pool, subs, 0, np.arange(1))   # the topic was not even sent
    said = str(e.value)
    assert "'sub1'" in said and "'tele/a1/b2'" in said
    assert "tele/+/b2" in said and "tele/a1/+" in said
    assert "tele/a2/b2" not in said and "sub0" not in said
    # two subscribers on one topic are fan-out, not overlap
    exp = R.Expected(pool[::2], subs, 0, np.arange(4))
    assert exp.n_deliveries == 4


@pytest.mark.parametrize("cell", [
    "fleet-1m-rules.flood-qos1", "fleet-1m-rules.paced-qos1",
    "exact-1k-fanout.flood-qos1", "p2p-1k.flood-qos1",
])
def test_every_cells_live_set_is_disjoint_on_its_whole_pool(cell):
    """At the cells' own sizes (the table's populations apart from its
    pairs: only `pops` reaches the pool)."""
    import json

    bench = os.path.join(REPO, "benchmark")
    work = json.load(open(os.path.join(bench, "workloads", cell + ".json")))
    conf = json.load(open(os.path.join(
        bench, "configs", cell.rsplit(".", 1)[0] + ".json"
    )))
    _pairs, pops = TR.generate("table", conf["table"])
    subs = TR.generate("live", conf["live"])
    pool = TR.topic_pool(work["topics"], pops, 3000000032,
                         work["publishers"])
    exp = R.Expected(pool, subs, 0, np.arange(len(pool)))
    assert exp.n_deliveries > 0


# ------------------------------------- shared subscriptions (MQTT 5 4.8.2)

# topics by seq % 4: two a group is owed, one plain, one nobody's; two
# groups on one filter, and `m1` holds a plain filter beside its share
SHARED_POOL = ["s/a", "s/b", "t/x", "u/1"]
SHARED_SUBS = [
    ("m0", ["$share/g/s/+"], 1), ("m1", ["$share/g/s/+", "t/x"], 0),
    ("m2", ["$share/g/s/+"], 1), ("h0", ["$share/h/s/+"], 0),
    ("h1", ["$share/h/s/+"], 1), ("p0", ["t/x"], 1),
]
M0, M1, M2, H0, H1, P0 = range(6)


def _shared_run():
    """40 publishes from 2 publishers, each group's share dealt round
    the members in publish order, the plain parts as owed."""
    exp = R.Expected(SHARED_POOL, SHARED_SUBS, 0, np.arange(40))
    got = [list(s) for s in exp.sub_seqs]
    for g, seqs in enumerate(exp.group_seqs):
        members = [j for j, gs in enumerate(exp.groups_of) if g in gs]
        for i, seq in enumerate(seqs):
            got[members[i % len(members)]].append(seq)
    got = [np.sort(np.asarray(r, dtype=np.int64)) for r in got]
    return exp, got, [1 << min(q, 1) for _c, _f, q in SHARED_SUBS]


def _give(got, j, seq):
    got[j] = np.sort(np.append(got[j], seq))


def _take(got, j, seq):
    got[j] = got[j][got[j] != seq]


def _swap_same_topic(got, j):
    have = got[j]
    a, b = [i for i in range(len(have)) if have[i] % 4 == have[0] % 4][:2]
    have[[a, b]] = have[[b, a]]


SHARED_CASES = {
    # (what happens to the sound run, the numbers it reads, the seqs failed)
    "served-once-by-different-members": (lambda got, q: None, {}, []),
    "one-publish-to-two-members": (
        lambda got, q: _give(got, M2, got[M0][0]),
        {"deliveries_duplicated": 1}, []),
    "one-publish-to-no-member": (
        lambda got, q: _take(got, M1, got[M1][got[M1] % 4 < 2][0]),
        {"deliveries_missing": 1}, [1]),
    "a-topic-none-of-its-filters-match": (
        lambda got, q: _give(got, P0, 4),
        {"deliveries_unexpected": 1}, []),
    "two-groups-on-one-filter-each-owed": (
        lambda got, q: [_take(got, j, s) for j in (H0, H1)
                        for s in list(got[j])],
        {"deliveries_missing": 20}, list(range(0, 40, 4))
        + list(range(1, 40, 4))),
    "plain-beside-shared-is-the-members-own": (
        lambda got, q: (_take(got, M1, 2), _give(got, M0, 2)),
        {"deliveries_missing": 1, "deliveries_unexpected": 1}, [2]),
    "out-of-order-at-one-member": (
        lambda got, q: _swap_same_topic(got, M2),
        {"deliveries_out_of_order": 1}, []),
    "a-member-at-the-wrong-qos": (
        lambda got, q: q.__setitem__(M2, 1),
        {"subscribers_wrong_qos": 1}, []),
}


@pytest.mark.parametrize("case", [*SHARED_CASES, "overlap-plain-and-shared",
                                  "malformed-wildcard-name", "bare-share"])
def test_shared_subscriptions_are_judged_by_the_group_rule(case):
    """A group (`<name>`, `<rest>`) is owed each publish `<rest>` matches
    once, by any one member; the five delivery numbers keep their names."""
    if case not in SHARED_CASES:
        subs, says = {
            "overlap-plain-and-shared": (
                [("c", ["s/a", "$share/g/s/+"], 1)],
                "matches more than one filter of subscriber 'c': "
                "['s/a', '$share/g/s/+']"),
            "malformed-wildcard-name": ([("c", ["$share/+/x"], 1)],
                                        "'$share/+/x'"),
            "bare-share": ([("c", ["$share/g"], 1)], "'$share/g'"),
        }[case]
        with pytest.raises(R.Overlap) as e:
            R.Expected(SHARED_POOL, subs, 0, np.arange(4))
        assert says in str(e.value)
        malformed = case != "overlap-plain-and-shared"
        assert ("$share/<name>/<filter>" in str(e.value)) is malformed
        return
    exp, got, qos = _shared_run()
    assert exp.groups == [("g", "s/+"), ("h", "s/+")]
    assert exp.groups_of == [[0], [0], [0], [1], [1], []]
    # 20 publishes on s/+ to each group, 10 on t/x to each plain holder
    assert exp.n_deliveries == 20 + 20 + 10 + 10
    assert all(len(got[j]) for j in range(6))
    break_it, want, failed = SHARED_CASES[case]
    break_it(got, qos)
    nums, bad = _numbers(exp, 2, np.arange(40), got, qos)
    assert list(nums) == [
        "pubacks_missing", "deliveries_missing", "deliveries_unexpected",
        "deliveries_duplicated", "deliveries_out_of_order",
        "subscribers_wrong_qos",
    ]
    assert {n: v for n, v in nums.items() if v} == want
    assert sorted(bad.tolist()) == sorted(failed)


# ------------------------------ a live set without `$share`, as it was

def _digest(arrays) -> str:
    import hashlib

    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a, dtype=np.int64)
        h.update(len(a).to_bytes(8, "little"))
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# computed on the parent's referee.py (commit 5db1955), before the group
# rule: each configuration's live set and rules over its cell's pool
# (seed 3000000032) for the publishes 0, 3, 6, ... 39999
PINNED_EXPECTED = [
    ("fleet-1m-rules", "flood-qos1", "eaddcfee95b688c8", "aeb57d3b893e738e",
     331116),
    ("exact-1k-fanout", "flood-qos1", "b7dfba40e647ddd1", "e3b0c44298fc1c14",
     3333500),
    ("p2p-1k", "flood-qos1", "9b93ff1c27b75ddf", "e3b0c44298fc1c14", 13334),
    ("plus-100k", "flood-qos1", "84d46d551898a253", "e3b0c44298fc1c14",
     23724),
]


@pytest.mark.parametrize("config,traffic,subs_pin,rules_pin,n",
                         PINNED_EXPECTED, ids=[p[0] for p in PINNED_EXPECTED])
def test_expected_without_share_is_what_it_was(config, traffic, subs_pin,
                                               rules_pin, n):
    import json

    bench = os.path.join(REPO, "benchmark")
    work = json.load(open(os.path.join(bench, "workloads",
                                       f"{config}.{traffic}.json")))
    conf = json.load(open(os.path.join(bench, "configs", config + ".json")))
    _pairs, pops = TR.generate("table", conf["table"])
    pool = TR.topic_pool(work["topics"], pops, 3000000032, work["publishers"])
    exp = R.Expected(pool, TR.generate("live", conf["live"]),
                     conf["rules"]["count"], np.arange(0, 40000, 3))
    assert exp.groups == [] and exp.group_seqs == []
    assert _digest(exp.sub_seqs) == subs_pin
    assert _digest(exp.rule_seqs) == rules_pin
    assert exp.n_deliveries == n


# -------------------- churned subscriptions (MQTT 5.0 3.8.4 and 3.10.4)

# topics by seq % 4: two the churned `c/+` matches, one `d/#` does, one
# no churned filter; two publishers
CHURN_POOL = ["c/a", "c/b", "d/x", "e/1"]
CHURN_FILTERS = ["c/+", "d/#"]
# a life on connection 0 holds `c/+` at QoS 1: SUBSCRIBE sent at 10,
# SUBACK in at 11, UNSUBSCRIBE sent at 20, UNSUBACK in at 21
LIFE = {"conn": 0, "filter": 0, "qos": 1, "sub": 10.0, "suback": 11.0,
        "unsub": 20.0, "unsuback": 21.0}


def _lives(*lives):
    return {k: np.asarray([life[k] for life in lives]) for k in LIFE}


def _churned(pubs, lives=(LIFE,)):
    """``pubs``: seq -> (sent, acked); the churned subscriptions'
    reference over them."""
    seqs = np.asarray(sorted(pubs), np.int64)
    sends = np.asarray([pubs[s][0] for s in seqs.tolist()])
    acks = np.asarray([pubs[s][1] for s in seqs.tolist()])
    return R.Churned(CHURN_POOL, CHURN_FILTERS, _lives(*lives), seqs,
                     sends, acks)


def _got(*receipts):
    """``(conn, seq, instant, qos)`` receipts as the harness hands them."""
    cols = list(zip(*receipts)) or [(), (), (), ()]
    return (np.asarray(cols[0], np.int64), np.asarray(cols[1], np.int64),
            np.asarray(cols[2], float), np.asarray(cols[3], np.int64))


NAMES = ("deliveries_missing", "deliveries_unexpected",
         "deliveries_duplicated", "deliveries_out_of_order",
         "subscribers_wrong_qos")


def _churn_numbers(ch, *receipts, publishers=2):
    *nums, lost = ch.judge(publishers, *_got(*receipts))
    return {n: v for n, v in zip(NAMES, nums) if v}, sorted(lost.tolist())


# (publish 4, on c/a: sent, acknowledged; received at 22 or never) ->
# the numbers it reads
EDGES = {
    "owed-and-received": (12.0, 13.0, True, {}),
    "owed-and-missing": (12.0, 13.0, False, {"deliveries_missing": 1}),
    "sent-as-the-suback-came": (11.0, 13.0, False, {}),
    "sent-just-after-the-suback": (11.001, 13.0, False,
                                   {"deliveries_missing": 1}),
    "acked-as-the-unsubscribe-left": (12.0, 20.0, False, {}),
    "acked-just-before-the-unsubscribe": (12.0, 19.999, False,
                                          {"deliveries_missing": 1}),
    "permitted-before-the-suback": (10.5, 10.6, True, {}),
    "permitted-after-the-unsubscribe": (19.5, 20.5, True, {}),
    "acked-as-the-subscribe-left": (9.0, 10.0, True,
                                    {"deliveries_unexpected": 1}),
    "acked-just-after-the-subscribe-left": (9.0, 10.001, True, {}),
    "sent-as-the-unsuback-came": (21.0, 21.5, True,
                                  {"deliveries_unexpected": 1}),
    "sent-just-before-the-unsuback": (20.999, 21.5, True, {}),
    "never-acknowledged-yet-in-the-band": (12.0, 0.0, True, {}),
    "never-acknowledged-and-sent-after": (21.5, 0.0, True,
                                          {"deliveries_unexpected": 1}),
}


@pytest.mark.parametrize("case", sorted(EDGES))
def test_a_churned_life_is_owed_its_band_and_no_more(case):
    """Owed: sent after the SUBACK came and acknowledged before the
    UNSUBSCRIBE left; permitted: acknowledged after the SUBSCRIBE left
    and sent before the UNSUBACK came; the edges are strict."""
    sent, acked, received, want = EDGES[case]
    ch = _churned({4: (sent, acked)})
    owed = "deliveries_missing" in want or (case == "owed-and-received")
    assert ch.n_owed == int(owed)
    nums, lost = _churn_numbers(ch, *([(0, 4, 22.0, 1)] if received else []))
    assert nums == want
    assert lost == ([4] if "deliveries_missing" in want else [])


SECOND = {**LIFE, "sub": 30.0, "suback": 31.0, "unsub": 40.0,
          "unsuback": 41.0, "qos": 0}


@pytest.mark.parametrize("case,receipts,want,lost", [
    ("each-life-its-own", [(0, 4, 14.0, 1), (0, 8, 35.0, 0)], {}, []),
    # a receipt belongs to the life that subscribed last before it came:
    # publish 4, acked at 13, cannot be the second life's
    ("late-into-the-next-life", [(0, 4, 31.0, 0), (0, 8, 35.0, 0)],
     {"deliveries_missing": 1, "deliveries_unexpected": 1}, [4]),
    ("the-second-life-at-the-first-ones-qos", [(0, 4, 14.0, 1),
                                              (0, 8, 35.0, 1)],
     {"subscribers_wrong_qos": 1}, []),
    ("one-owed-publish-twice", [(0, 4, 14.0, 1), (0, 4, 15.0, 1),
                                (0, 8, 35.0, 0)],
     {"deliveries_duplicated": 1}, []),
    ("in-the-other-order", [(0, 12, 14.0, 1), (0, 4, 15.0, 1),
                            (0, 8, 35.0, 0)],
     {"deliveries_out_of_order": 1}, []),
    # publish 2 is on d/x: the connection never held d/#
    ("a-topic-the-filter-does-not-match", [(0, 4, 14.0, 1),
                                           (0, 2, 15.0, 1),
                                           (0, 8, 35.0, 0)],
     {"deliveries_unexpected": 1}, []),
    # publish 3 is on e/1: no churned filter matches it
    ("a-topic-no-churned-filter-matches", [(0, 4, 14.0, 1),
                                           (0, 3, 15.0, 1),
                                           (0, 8, 35.0, 0)],
     {"deliveries_unexpected": 1}, []),
    ("on-a-connection-that-held-nothing", [(0, 4, 14.0, 1),
                                           (1, 4, 14.0, 1),
                                           (0, 8, 35.0, 0)],
     {"deliveries_unexpected": 1}, []),
])
def test_two_lives_on_one_connection_are_judged_apart(case, receipts, want,
                                                      lost):
    # 4 and 12 on c/a from publisher 0, both owed to the first life, and
    # 12 only where the case receives it; 8 on c/a owed to the second;
    # 2 and 3 on topics the first may not have
    pubs = {2: (12.0, 13.0), 3: (12.0, 13.0), 4: (12.0, 13.0),
            8: (32.0, 33.0)}
    if any(seq == 12 for _c, seq, _t, _q in receipts):
        pubs[12] = (12.5, 13.5)
    ch = _churned(pubs, (LIFE, SECOND))
    assert ch.n_owed == 2 + (12 in pubs)
    assert _churn_numbers(ch, *receipts) == (want, lost)


def test_churned_filters_that_overlap_on_the_pool_are_refused():
    with pytest.raises(R.Overlap) as e:
        R.Churned(CHURN_POOL, ["c/+", "c/a"], _lives(LIFE),
                  np.arange(4), np.zeros(4), np.zeros(4))
    assert "'c/a'" in str(e.value) and "churned" in str(e.value)


def test_the_judge_adds_the_churn_under_the_same_five_names():
    exp, k, sent, received, qos = _exact_run(n=40)
    plain, _ = _numbers(exp, k, sent, received, qos)
    ch = _churned({4: (12.0, 13.0), 5: (12.0, 13.0)})
    pool = TR.topic_pool({"generator": "exact_topics", "pool": 4},
                         (1, 1, 1, 1), 1, k)
    with_churn = R.Expected(pool, exp.subs, 0, sent, churn=ch)
    assert with_churn.n_deliveries == exp.n_deliveries + 2
    nums, failed = R.judge(with_churn, k, sent, received, qos,
                           np.zeros(0, np.int32), np.zeros(0, np.int64),
                           {}, _got((0, 4, 14.0, 1)))
    nums = {n: v for n, v, _l in nums}
    assert list(nums) == list(plain)
    assert {n: v for n, v in nums.items() if v} == {"deliveries_missing": 1}
    assert failed.tolist() == [5]


@pytest.mark.parametrize("case,life,unanswered", [
    ("both-answered", LIFE, 0),
    ("no-suback", {**LIFE, "suback": 0.0}, 1),
    ("no-unsuback", {**LIFE, "unsuback": 0.0}, 1),
    ("neither", {**LIFE, "suback": 0.0, "unsuback": 0.0}, 1),
])
def test_a_life_an_ack_never_answered_is_a_client_error(case, life,
                                                        unanswered):
    """Without its UNSUBACK a life's band never closes, so a receipt long
    after the UNSUBSCRIBE reads permitted: the missing ack itself is what
    fails the run, under ``client_errors``."""
    exp, k, sent, received, qos = _exact_run(n=40)
    ch = _churned({4: (12.0, 13.0), 5: (50.0, 51.0)}, (life,))
    assert ch.unanswered == unanswered
    pool = TR.topic_pool({"generator": "exact_topics", "pool": 4},
                         (1, 1, 1, 1), 1, k)
    with_churn = R.Expected(pool, exp.subs, 0, sent, churn=ch)
    receipts = [(0, 4, 14.0, 1)] if life["suback"] else []
    if not life["unsuback"]:
        receipts.append((0, 5, 52.0, 1))
    nums, _failed = R.judge(with_churn, k, sent, received, qos,
                            np.zeros(0, np.int32), np.zeros(0, np.int64),
                            {"client_errors": 2}, _got(*receipts))
    nums = {n: v for n, v, _l in nums if v}
    assert nums == {"client_errors": 2 + unanswered}
