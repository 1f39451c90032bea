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
