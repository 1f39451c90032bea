"""Subscription churn rehearsed on the CPU: the fleet's paced cell with a
``churn`` group (`churn_rehearsal.json`, beside this file, not a cell of
the benchmark), sound and with each churn guarantee broken underneath.
The platform override lives in `test_benchmark_rehearsal`; none of these
numbers is a device number."""

import json
import os

import pytest

from test_benchmark_rehearsal import (  # noqa: F401  (on_cpu: a fixture)
    REPO, last_line, on_cpu,
)

CELL = "fleet-1m-rules.paced-qos1"
HERE = os.path.dirname(os.path.abspath(__file__))
CHURN = json.load(open(os.path.join(HERE, "churn_rehearsal.json")))
COMPARED = [
    "pubacks_missing", "deliveries_missing", "deliveries_unexpected",
    "deliveries_duplicated", "deliveries_out_of_order",
    "subscribers_wrong_qos", "firings_missing", "firings_unexpected",
    "firings_duplicated", "device_errors", "client_errors",
    "windows_not_dev", "decide_host_windows", "no_decide_dev_window",
    "rules_host_windows", "no_rules_dev_window", "rules_not_lowered",
]


def run_cell(harness, fault=None, seed="3004100011"):
    return harness.main(
        ["--workload", CELL, "--seed", seed, "--seconds", "3",
         "--trace", "0"], fault=fault, overrides=CHURN,
    )


def test_the_rehearsal_churns_across_the_fold_threshold():
    """Live at once, rate x dwell_s churned filters hold the residual
    past `delta_aut_threshold` (1,024) and half the delta, the engine's
    fold trigger (the live set's 1,142 filters are folded at boot)."""
    churn = CHURN["workload"]["churn"]
    live = churn["rate"] * churn["dwell_s"]
    assert live > max(1024, (1142 + live) / 2)


def test_churn_runs_to_a_correct_line_with_a_fold_in_the_window(on_cpu,
                                                                capsys):
    assert run_cell(on_cpu) == 0
    res, err, window = last_line(capsys, also_window=True)
    assert res["correct"] is True and res["failed"] == 0
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "churn", "compared"]
    assert list(res["compared"]) == COMPARED
    assert all(v == 0 for v, _lim in res["compared"].values())
    assert set(res["metrics"]) == {"deliver_p50_ms", "setup_s"}
    churn = res["churn"]
    assert window["churn"] == churn
    # 700 cycles a second for 3 s, each held 2 s: subscriptions made and
    # ended inside the window, and owed publishes among them.  Of the
    # 2,100 cycles due in the window, one due at either edge may leave
    # on the other side of it when the rehearsal's CPU is loaded
    assert churn["subscribed"] > 2000 and churn["unsubscribed"] > 500
    assert churn["owed"] > 20 and churn["clashes"] == 0
    assert churn["suback_ms_p50"] > 0 and churn["unsuback_ms_p99"] > 0
    assert churn["folds"] >= 1 and churn["fold_ms"] > 0
    assert window["expected_deliveries"] > churn["owed"]


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


def test_a_schedule_that_brings_a_filter_round_too_soon_is_refused(on_cpu):
    group = dict(CHURN["workload"]["churn"], filters=1000)
    with pytest.raises(on_cpu.Refused, match="under 2 x dwell_s"):
        on_cpu.churn_plans(group, (2500, 1000, 1000, 500), 1, 3.0, 1883)
    _flts, plans = on_cpu.churn_plans(
        dict(group, filters=3500, churn_children=2),
        (2500, 1000, 1000, 500), 1, 3.0, 1883,
    )
    assert [len(p["conns"]) for p in plans] == [100, 100]
    assert sum(len(p["dues"]) for p in plans) == 2100
    assert {g for p in plans for g, _f in p["filters"]} == set(range(3500))
