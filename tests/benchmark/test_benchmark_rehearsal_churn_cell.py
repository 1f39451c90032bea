"""`fleet-1m-consoles.churn-paced` rehearsed on the CPU: the cell's own
churn group (`generators/fleet_follow.py`) on a table of 6,000 and at
700 cycles a second held 2 s each, sound and traced, and with each churn
guarantee broken underneath; and the generator at the cell's own size.
The platform override lives in `test_benchmark_rehearsal`; none of these
numbers is a device number."""

import json
import os

import numpy as np
import pytest

from test_benchmark_rehearsal import (  # noqa: F401  (on_cpu: a fixture)
    REPO, last_line, on_cpu,
)

import referee
import traffic

CELL = "fleet-1m-consoles.churn-paced"
PACED = "fleet-1m-rules.paced-qos1"
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
WORK = json.load(open(os.path.join(
    REPO, "benchmark", "workloads", CELL + ".json")))
CONF = json.load(open(os.path.join(
    REPO, "benchmark", "configs", "fleet-1m-consoles.json")))
FLEET = json.load(open(os.path.join(
    REPO, "benchmark", "configs", "fleet-1m-rules.json")))
NEW = {"route_apply_us_per_op.churn", "route_wait_pct.churn",
       "subscribe_us_per_op.churn", "fold_ms_per_s.churn",
       "fold_upload_mb_per_s.churn", "residual_filters_mean.churn"}
# a table of fan-out 1 (3,000 vehicles, as many as the churn follows),
# one child a side, and churn enough that the residual passes the
# engine's fold trigger inside the 3 s window
SMALL = {
    "config": {"table": {"subscriptions": 6000, "fanout": 1},
               "rules": {"count": 6}},
    "workload": {"warmup_publishes": 300, "rate": 600,
                 "topics": {"pool": 8192}, "publisher_children": 1,
                 "subscriber_children": 1,
                 "churn": {"rate": 700, "dwell_s": 2.0,
                           "churn_children": 1, "clients": 200}},
}


def run_cell(harness, trace="0", fault=None, seed="3004200011"):
    return harness.main(
        ["--workload", CELL, "--seed", seed, "--seconds", "3",
         "--trace", trace], fault=fault, overrides=SMALL,
    )


def test_the_cell_is_the_paced_cell_and_a_churn_group():
    paced = json.load(open(os.path.join(
        REPO, "benchmark", "workloads", PACED + ".json")))
    cell, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["config"] == "fleet-1m-consoles" and cell["chips"] == 1
    # the fleet's deployment whole; the consoles add assumptions and a
    # guarantee, and the source that batches their route changes
    mine = ("name", "source", "assumed", "guarantees")
    assert {k: v for k, v in CONF.items() if k not in mine} \
        == {k: v for k, v in FLEET.items() if k not in mine}
    for group in ("assumed", "guarantees"):
        assert CONF[group].items() >= FLEET[group].items()
    assert "subscription_lifetime" in CONF["guarantees"]
    assert CONF["source"] != FLEET["source"]
    assert {k: v for k, v in WORK.items() if k not in ("why", "churn")} \
        == {k: v for k, v in paced.items() if k != "why"}
    assert WORK["churn"] == {
        "generator": "fleet_follow", "filters": 3000, "clients": 1000,
        "rate": 200, "dwell_s": 6.5, "qos": [0, 1], "churn_children": 2,
    }
    churn = WORK["churn"]
    # a filter comes round only after its child's others
    assert churn["filters"] > churn["rate"] * 2 * churn["dwell_s"]
    # what is live at once passes the fold trigger (the live set's 1,142
    # folded at boot, half the delta)
    live = churn["rate"] * churn["dwell_s"]
    assert live > max(1024, (1142 + live) / 2)
    per = {m["name"]: m for m in BENCH["per_layer"]}
    assert all(per[n]["workloads"] == [CELL] for n in NEW)
    assert all(per[n]["moves"] == "deliver_p50_ms" for n in NEW)
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"deliver_p50_ms", "setup_s"}


def test_fleet_follow_owes_each_life_twenty_times_what_fleet_churn_does():
    """At the cell's own size: the pool of 65,536 topics the fleet's
    table draws for, the churn's 3,000 filters disjoint on it, and the
    publishes a life is owed at 1,400 a second for 6.5 s, reckoned alike
    for both (its whole life, no acknowledgement's delay taken off: chip
    runs of `fleet_churn` read 0.038-0.044 where this reads ~0.074)."""
    _pairs, pops = traffic.table_fleet_families(
        CONF["table"]["subscriptions"], CONF["table"]["fanout"])
    pool = traffic.topic_pool(WORK["topics"], pops, 3004200012,
                              WORK["publishers"])
    per_life = {}
    # this cell's churn, and `fleet_churn` as it was first run on this
    # pool (6,000 filters from id 244, each held 5 s)
    for name, args, dwell in (
            ("fleet_follow", {"filters": 3000}, WORK["churn"]["dwell_s"]),
            ("fleet_churn", {"filters": 6000, "first_id": 244}, 5.0)):
        flts = traffic.churn_filters({"generator": name, **args}, pops,
                                     3004200012)
        none = {k: np.zeros(0) for k in ("conn", "filter", "qos", "sub",
                                         "suback", "unsub", "unsuback")}
        ch = referee.Churned(pool, flts, none, np.zeros(0, np.int64),
                             np.zeros(0), np.zeros(0))
        share = float((ch.filter_of >= 0).mean())
        # a life holds one filter of the list, any alike
        per_life[name] = WORK["rate"] * dwell * share / len(flts)
        if name == "fleet_follow":
            assert 0.5 < share < 0.6  # the pool's hottest vehicles
    print("owed publishes a life:", per_life)
    assert 1.5 < per_life["fleet_follow"] < 2.0
    assert per_life["fleet_follow"] > 20 * per_life["fleet_churn"]
    with pytest.raises(traffic.BadGenerator, match="vehicles"):
        traffic.churn_filters({"generator": "fleet_follow",
                               "filters": pops[0] + 1}, pops, 1)


def test_churn_cell_runs_to_a_correct_traced_line(on_cpu, capsys):
    assert run_cell(on_cpu, trace="1") == 0
    res, _err, window = last_line(capsys, also_window=True)
    assert res["correct"] is True and res["failed"] == 0
    assert all(v == 0 for v, _lim in res["compared"].values())
    churn = res["churn"]
    print("churn:", churn)
    print("owed a life:", churn["owed"] / churn["lives"])
    assert churn["subscribed"] > 2000 and churn["owed"] > 100
    assert churn["folds"] >= 1 and churn["clashes"] == 0
    got = res["metrics"]
    for name in sorted(NEW):
        print(name, got.get(name))
    assert NEW <= set(got)
    assert got["route_apply_us_per_op.churn"]["value"] > 0
    assert 0 <= got["route_wait_pct.churn"]["value"] < 100
    assert got["subscribe_us_per_op.churn"]["value"] > 0
    assert got["fold_ms_per_s.churn"]["value"] > 0
    assert got["fold_upload_mb_per_s.churn"]["value"] > 0
    assert got["residual_filters_mean.churn"]["value"] > 0
    eng = window["engine"]  # (its counters grow to the drain's end)
    assert eng["folds"] >= churn["folds"]
    assert eng["route_ack_filters"] >= churn["subscribed"]


@pytest.mark.parametrize("fault,fails", [
    ("churn_sub_lost", "deliveries_missing"),
    ("churn_unsub_kept", "deliveries_unexpected"),
    ("churn_unsub_ignored", "client_errors"),
])
def test_a_broken_churn_guarantee_reads_not_correct(fault, fails, on_cpu,
                                                    capsys, monkeypatch):
    import control

    monkeypatch.setattr(on_cpu, "DRAIN_S", 5.0)
    assert run_cell(on_cpu, fault=control.FAULTS[fault]) == 0
    res, _ = last_line(capsys)
    assert res["correct"] is False
    over = [n for n, (v, lim) in res["compared"].items() if v > lim]
    assert over == [fails]
