"""`fanin-share-1k.flood-qos1` rehearsed on the CPU: the cell's own
live set (73 consumers in four `$share` pools and one plain subscriber)
against 40 publishers on a pool of 4,096: sound, traced, and with a
group's publish lost underneath.  The platform override lives in
`test_benchmark_rehearsal`; none of these numbers is a device number."""

import json
import os

from test_benchmark_rehearsal import (  # noqa: F401  (on_cpu: a fixture)
    REPO, last_line, on_cpu,
)

CELL = "fanin-share-1k.flood-qos1"
CONF = json.load(open(os.path.join(
    REPO, "benchmark", "configs", "fanin-share-1k.json")))
WORK = json.load(open(os.path.join(
    REPO, "benchmark", "workloads", CELL + ".json")))
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
PER = {m["name"]: m for m in BENCH["per_layer"] if CELL in m["workloads"]}
NEW = {"shared_pick_us_per_msg", "shared_vector_pct.fanin"}
SMALL = {"workload": {"publishers": 40, "topics": {"pool": 4096},
                      "warmup_publishes": 300, "publisher_children": 1,
                      "subscriber_children": 1}}
COMPARED = [
    "pubacks_missing", "deliveries_missing", "deliveries_unexpected",
    "deliveries_duplicated", "deliveries_out_of_order",
    "subscribers_wrong_qos", "device_errors", "client_errors",
    "decide_host_windows", "no_decide_dev_window",
]


def run_cell(harness, trace="0", fault=None, seed="3000003901"):
    return harness.main(
        ["--workload", CELL, "--seed", seed, "--seconds", "2",
         "--trace", trace], fault=fault, overrides=SMALL,
    )


def test_fanin_cell_is_declared_at_its_size():
    entry, = [c for c in BENCH["configs"] if c["name"] == "fanin-share-1k"]
    cell, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and entry["reduced"] == ["live_connections"]
    live = CONF["live"]
    assert live["generator"] == "share_groups"
    assert live["groups"] == [
        ["ingest", "fanin/+/+", 32], ["archive", "fanin/+/+", 16],
        ["analytics", "fanin/#", 16], ["alerts", "fanin/s0/+", 8],
    ]
    assert live["plain"] == [["fanin/s1/+", ["dash-0"]]]
    # EMQX 5's shipped strategy and the shipped session limits
    assert CONF["mqtt"] == {"shared_subscription_strategy": "round_robin"}
    assert CONF["table"] == {"generator": "none"}
    assert CONF["rules"] == {"count": 0}
    assert CONF["guarantees"]["device_steps"] == ["decide"]
    assert WORK["publishers"] == 1000 and WORK["inflight"] == 4
    assert WORK["qos"] == 1 and WORK["loop"] == "flood"
    assert WORK["topics"] == {"generator": "share_groups", "pool": 65536,
                              "streams": 16, "devices": 4096}
    assert WORK["warmup_publishes"] == 70000
    # fourteen the cell's ring feeds, and the two it came with
    assert len(PER) == 16 and NEW <= set(PER)
    assert all(PER[n]["workloads"] == [CELL] for n in NEW)
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"deliver_rate", "setup_s"}


def test_fanin_cell_runs_to_a_correct_line(on_cpu, capsys):
    assert run_cell(on_cpu) == 0
    out = capsys.readouterr().out
    lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    res = lines[-1]
    window, = [ln for ln in lines if ln.get("phase") == "window"]
    subscribed, = [ln for ln in lines if ln.get("phase") == "subscribed"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"deliver_rate", "setup_s"}
    assert list(res["compared"]) == COMPARED
    assert all(v == 0 for v, _lim in res["compared"].values())
    # 73 consumers, five filter strings, four routes: ingest and
    # archive share fanin/+/+
    assert subscribed["live_subscribers"] == 73
    assert subscribed["live_filters"] == 4
    # no table and four live filters: the host trie matches by design,
    # and every window that delivers is decided on the device
    assert set(window["paths"]) == {"host"}
    assert window["engine"]["decide_host_windows"] == 0
    assert window["engine"]["decide_dev_windows"] > 0
    assert window["compiles_in_window"]["requests"] == 0
    # ~3.125 deliveries a publish, nothing dropped at the shipped limits
    assert 2.9 < window["expected_deliveries"] / res["attempted"] < 3.4
    assert not [k for k in window["broker_drops"] if "queue" in k
                or "inflight" in k], window["broker_drops"]


def test_fanin_traced_run_reports_the_pick(on_cpu, capsys):
    assert run_cell(on_cpu, trace="1") == 0
    res, _ = last_line(capsys)
    assert res["correct"] is True
    got = res["metrics"]
    assert set(got) <= set(PER) and NEW <= set(got)
    assert got["shared_pick_us_per_msg"]["value"] > 0
    assert got["shared_vector_pct.fanin"]["value"] == 100.0


def test_fanin_lost_share_reads_not_correct(on_cpu, capsys, monkeypatch):
    import control

    monkeypatch.setattr(on_cpu, "DRAIN_S", 5.0)
    assert run_cell(on_cpu, fault=control.FAULTS["share_lost"]) == 0
    res, _ = last_line(capsys)
    assert res["correct"] is False and res["failed"] > 0
    over = {n: v for n, (v, lim) in res["compared"].items() if v > lim}
    assert set(over) == {"deliveries_missing"}, over
