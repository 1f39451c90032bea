"""`plus-100k.flood-qos1` rehearsed on the CPU at a small size: 5,000
subscriptions of the cell's twenty masks on a seven-level tree small
enough that they fill its upper levels (so nine topics in ten have a
frontier of 17-20, as at full size), 264 x 4 live filters (1,056, past
the fold threshold), a pool of 4,096.  Sound, traced, with
`engine.f_width` 16 by `overrides` (the rows go back to the host trie
and the run stays `correct`: what `match_host_rows_pct.flood` is there
to show), and with an answer lost underneath (`host_match` is the
fleet rehearsal's to show: the path is the same).  The platform
override lives in `test_benchmark_rehearsal`; none of these numbers is
a device number."""

import json
import os
from math import prod

import pytest

from test_benchmark_rehearsal import (  # noqa: F401  (on_cpu: a fixture)
    BENCH as BENCH_DIR, REPO, last_line, on_cpu,
)

CELL = "plus-100k.flood-qos1"
NAME = "match_host_rows_pct.flood"
CONF = json.load(open(os.path.join(
    REPO, "benchmark", "configs", "plus-100k.json")))
WORK = json.load(open(os.path.join(
    REPO, "benchmark", "workloads", CELL + ".json")))
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
PER = {m["name"]: m for m in BENCH["per_layer"] if CELL in m["workloads"]}
MASKS = [m for m, _w in CONF["table"]["masks"]]
LEVELS = [2, 2, 2, 4, 4, 8, 8]


def room(mask):
    return prod(n for n, m in zip(LEVELS, mask) if m == "L")


SMALL = {
    "config": {
        "table": {"generator": "plus_tree", "subscriptions": 5000,
                  "fanout": 1, "levels": LEVELS,
                  "masks": [[m, room(m)] for m in MASKS]},
        # masks with room for 176 distinct filters each in this tree
        "live": {"generator": "plus_tree", "subscribers": 264,
                 "filters_each": 4, "levels": LEVELS,
                 "masks": ["++LLLLL", "+L+LLLL", "L++LLLL", "+LL+LLL",
                           "L+L+LLL", "LL++LLL"]},
    },
    "workload": {"topics": {"pool": 4096}, "warmup_publishes": 300,
                 "publisher_children": 1, "subscriber_children": 1},
    "replace": ["table", "live"],
}
WIDTH_16 = {**SMALL, "config": {**SMALL["config"],
                                "engine": {"f_width": 16}}}


def run_cell(harness, seconds="2", trace="0", fault=None, overrides=SMALL,
             seed="3000000033"):
    return harness.main(
        ["--workload", CELL, "--seed", seed, "--seconds", seconds,
         "--trace", trace], fault=fault, overrides=overrides,
    )


def test_plus_cell_is_declared_at_its_size():
    entry, = [c for c in BENCH["configs"] if c["name"] == "plus-100k"]
    cell, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and entry["reduced"] == ["live_connections"]
    assert list(CONF["reduced"]) == ["live_connections"]
    # the source's size and kind: 100,000 distinct filters of `+` alone
    # in twenty shapes over seven levels, no rule, the shipped limits
    table = CONF["table"]
    assert table["generator"] == "plus_tree" and table["fanout"] == 1
    assert table["subscriptions"] == 100000
    assert table["levels"] == [4, 4, 4, 8, 8, 16, 64]
    assert len(MASKS) == len(set(MASKS)) == 20
    assert all(len(m) == 7 and set(m) <= {"L", "+"} and "+" in m
               for m in MASKS)
    assert CONF["live"]["subscribers"] * CONF["live"]["filters_each"] == 1200
    assert CONF["live"]["levels"] == table["levels"]
    assert CONF["rules"] == {"count": 0} and CONF["mqtt"] == {}
    assert CONF["engine"]["f_width"] == 32
    assert CONF["guarantees"]["device_steps"] == ["match", "decide"]
    assert {"tree", "masks", "f_width", "live_filters", "matches_per_row",
            "payload", "mqtt"} <= set(CONF["assumed"])
    # the fleet flood's loop over a uniform pool
    assert WORK["publishers"] * WORK["inflight"] == (
        CONF["engine"]["batch_max"]
    )
    assert WORK["topics"] == {"generator": "plus_tree", "pool": 65536,
                              "pool_seed": 1, "nomatch": 0.1}
    fleet = json.load(open(os.path.join(
        BENCH_DIR, "workloads", "fleet-1m-rules.flood-qos1.json")))
    assert WORK["warmup_bursts"] == fleet["warmup_bursts"]
    # the twenty metrics of every flood, the fleet flood's six of the
    # match path, and the one this cell came with
    assert len(PER) == 27 and NAME in PER
    assert PER[NAME]["workloads"] == [CELL, "fleet-1m-rules.flood-qos1"]
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"deliver_rate", "setup_s"}


def test_plus_cell_runs_to_a_correct_line(on_cpu, capsys):
    seen = {}

    def watch(server):  # the harness's way in, breaking nothing
        seen["engine"] = server.broker.router.engine
        return lambda: None

    assert run_cell(on_cpu, fault=watch) == 0
    out = capsys.readouterr().out
    lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    res = lines[-1]
    window, = [ln for ln in lines if ln.get("phase") == "window"]
    subscribed, = [ln for ln in lines if ln.get("phase") == "subscribed"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"deliver_rate", "setup_s"}
    assert all(v <= lim for v, lim in res["compared"].values())
    assert {"windows_not_dev", "decide_host_windows"} <= set(res["compared"])
    assert "rules_host_windows" not in res["compared"]
    # the table in the base, the live filters folded, every window
    # matched and decided on the device and no row handed back
    idx = subscribed["index"]
    assert idx["base"] == 5000 and subscribed["live_filters"] == 1056
    assert idx["folded"] >= 1024 and not idx["folding"]
    assert set(window["paths"]) == {"dev"}
    assert window["engine"]["host_rows"] == 0
    assert window["engine"]["decide_host_windows"] == 0
    assert window["compiles_in_window"]["requests"] == 0
    assert window["expected_deliveries"] > 0
    assert window["expected_firings"] == 0
    # nothing dropped at the shipped session limits
    assert not [k for k in window["broker_drops"] if "queue" in k
                or "inflight" in k], window["broker_drops"]
    # the width the configuration states holds what the table can need
    stats = seen["engine"].stats()
    assert 16 < stats["frontier_need"] == 20 <= CONF["engine"]["f_width"]


def test_plus_traced_run_reports_the_share_as_a_number(on_cpu, capsys):
    assert run_cell(on_cpu, seconds="3", trace="1") == 0
    res, _ = last_line(capsys)
    assert res["correct"] is True
    # everything but the device trace's metrics is a number here; the
    # trace metrics stay silent on a CPU, they do not read 0
    want = {n for n, m in PER.items() if m["source"] != "device_trace"}
    assert set(res["metrics"]) == want and NAME in want
    m = {n: v["value"] for n, v in res["metrics"].items()}
    # no row flagged: the share reads 0.0, present, not absent
    assert res["metrics"][NAME]["unit"] == "%" and 0 <= m[NAME] < 1
    assert m["match_host_us_per_msg"] > 0 and m["match_us_per_msg"] > 0
    assert m["inline_compiles.flood"] == 0
    assert m["deliver_plain_run_pct.flood"] == 100.0


def test_at_width_16_the_rows_go_to_the_host_and_the_run_stays_correct(
        on_cpu, capsys):
    """What no comparison of the harness can see yet: every window reads
    `dev`, `correct` holds, and the host trie matched most rows."""
    assert run_cell(on_cpu, seconds="3", trace="1", overrides=WIDTH_16) == 0
    res, _err, window = last_line(capsys, also_window=True)
    assert res["correct"] is True and res["failed"] == 0
    assert set(window["paths"]) == {"dev"}
    assert res["compared"]["windows_not_dev"] == [0, 0]
    assert 50 < res["metrics"][NAME]["value"] <= 100
    assert window["engine"]["host_rows"] > 0.5 * window["publishes"]


def test_plus_lost_match_reads_not_correct(on_cpu, capsys, monkeypatch):
    import control

    monkeypatch.setattr(on_cpu, "DRAIN_S", 5.0)
    assert run_cell(on_cpu, fault=control.FAULTS["lost_match"]) == 0
    res, _ = last_line(capsys)
    assert res["correct"] is False
    assert res["compared"]["deliveries_missing"][0] > 0


@pytest.mark.parametrize("ring,reads", [
    # a program from before the counter (the parent): nothing to read
    ([{"n_msgs": 500}, {"n_msgs": 300}], None),
    ([{"n_msgs": 500, "n_host_rows": 0}], 0.0),
    ([{"n_msgs": 500, "n_host_rows": 450},
      {"n_msgs": 500, "n_host_rows": 450}], 90.0),
], ids=["field-absent", "no-row-flagged", "nine-in-ten"])
def test_the_share_is_read_by_the_metrics_own_file(ring, reads, on_cpu):
    how = json.load(open(os.path.join(
        BENCH_DIR, "metrics", NAME + ".json")))
    got = on_cpu.reader(how["reader"])({"ring": ring, "window_s": 20},
                                       **how["args"])
    assert got == reads
