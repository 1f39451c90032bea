"""A fan-in deployment through MQTT 5 shared subscriptions
(`generators/share_groups.py`: live set and pool) rehearsed on the CPU
through `run.main`, by `overrides` on `exact-1k-fanout.flood-qos1`: the
referee's group rule against today's program, sound and with a group's
publish lost underneath.  The platform override lives in
`test_benchmark_rehearsal`; none of these numbers is a device number."""

import json

import pytest

from test_benchmark_rehearsal import (  # noqa: F401  (on_cpu: a fixture)
    last_line, on_cpu,
)

CELL = "exact-1k-fanout.flood-qos1"
# two groups on one wildcard filter (each owed the whole stream), one on
# a multi-level wildcard, one on an exact topic; `ingest-0` holds a
# plain filter beside its shared one and `dash-0` that filter alone;
# members at QoS 0 and 1 in every group; stream s4 has no subscriber
GROUPS = [["ingest", "fanin/s0/+", 4], ["archive", "fanin/s0/+", 3],
          ["alerts", "fanin/s1/#", 3], ["audit", "fanin/s2/d0", 3]]
SHARE = {
    "config": {"live": {"generator": "share_groups", "groups": GROUPS,
                        "plain": [["fanin/s3/+", ["ingest-0", "dash-0"]]]}},
    "workload": {"topics": {"generator": "share_groups", "pool": 64,
                            "streams": 5, "devices": 8},
                 "warmup_publishes": 200, "publishers": 8, "inflight": 8,
                 "publisher_children": 1, "subscriber_children": 1},
    "replace": ["live", "topics"],
}


def run_cell(harness, fault=None, overrides=SHARE, seed="3000003801"):
    return harness.main(
        ["--workload", CELL, "--seed", seed, "--seconds", "2",
         "--trace", "0"], fault=fault, overrides=overrides,
    )


def test_the_live_set_holds_what_the_rehearsal_needs():
    import traffic

    subs = traffic.generate("live", SHARE["config"]["live"])
    held = {cid: (flts, q) for cid, flts, q in subs}
    assert held["ingest-0"] == (["$share/ingest/fanin/s0/+", "fanin/s3/+"], 0)
    assert held["dash-0"] == (["fanin/s3/+"], 1)
    for name, flt, n in GROUPS:
        qos = {held[f"{name}-{k}"][1] for k in range(n)}
        assert qos == {0, 1} and all(
            held[f"{name}-{k}"][0][0] == f"$share/{name}/{flt}"
            for k in range(n)
        )


def test_shared_groups_run_to_a_correct_line(on_cpu, capsys):
    """Every group got each of its publishes once, by one member."""
    assert run_cell(on_cpu) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    res = lines[-1]
    subscribed, = [ln for ln in lines if ln.get("phase") == "subscribed"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert all(v <= lim for v, lim in res["compared"].values())
    # the names and order the cell prints on its own live set
    assert list(res["compared"]) == [
        "pubacks_missing", "deliveries_missing", "deliveries_unexpected",
        "deliveries_duplicated", "deliveries_out_of_order",
        "subscribers_wrong_qos", "device_errors", "client_errors",
        "decide_host_windows", "no_decide_dev_window",
    ]
    # five filter strings, four filters routed: the two groups on
    # fanin/s0/+ share one route
    assert subscribed["live_subscribers"] == 14
    assert subscribed["live_filters"] == 4


def test_a_lost_share_reads_not_correct(on_cpu, capsys, monkeypatch):
    import control

    monkeypatch.setattr(on_cpu, "DRAIN_S", 5.0)
    assert run_cell(on_cpu, fault=control.FAULTS["share_lost"]) == 0
    res, _ = last_line(capsys)
    assert res["correct"] is False and res["failed"] > 0
    over = {n: v for n, (v, lim) in res["compared"].items() if v > lim}
    assert set(over) == {"deliveries_missing"}, over


@pytest.mark.parametrize("group,says", [
    (["+", "fanin/s0/+", 3], "'$share/+/fanin/s0/+'"),
    (["g", "", 3], "'$share/g/'"),
])
def test_a_malformed_share_is_refused_before_the_run(group, says, on_cpu,
                                                     capsys):
    bad = {**SHARE, "config": {"live": {
        "generator": "share_groups", "groups": [group]}}}
    assert run_cell(on_cpu, overrides=bad) == 1
    out = capsys.readouterr()
    assert '"correct"' not in out.out
    assert not any('"phase"' in ln for ln in out.out.splitlines())
    refusal, = [ln for ln in out.err.splitlines()
                if ln.startswith("refused: ")]
    assert says in refusal and "$share/<name>/<filter>" in refusal
