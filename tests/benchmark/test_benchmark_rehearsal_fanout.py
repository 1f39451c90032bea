"""`exact-1k-fanout.flood-qos1` rehearsed on the CPU at a small size
(40 subscribers on the 4 topics): sound, traced, and with each
guarantee the cell can lose broken underneath.  The platform override
lives in `test_benchmark_rehearsal`; none of these numbers is a device
number."""

import json
import os

import pytest

from test_benchmark_rehearsal import (  # noqa: F401  (on_cpu: a fixture)
    REPO, last_line, on_cpu,
)

CELL = "exact-1k-fanout.flood-qos1"
SMALL = {"config": {"live": {"subscribers": 40}},
         "workload": {"warmup_publishes": 200, "publishers": 8,
                      "inflight": 8, "publisher_children": 1,
                      "subscriber_children": 1}}
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
PER = {m["name"]: m for m in BENCH["per_layer"] if CELL in m["workloads"]}


def run_cell(harness, seconds="2", trace="0", fault=None,
             seed="3000000026"):
    return harness.main(
        ["--workload", CELL, "--seed", seed, "--seconds", seconds,
         "--trace", trace], fault=fault, overrides=SMALL,
    )


def test_fanout_cell_runs_to_a_correct_line(on_cpu, capsys):
    assert run_cell(on_cpu) == 0
    res, err, window = last_line(capsys, also_window=True)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"deliver_rate", "setup_s"}
    # ten subscribers a topic: ten deliveries a publish, every window
    # decided on the device, none matched there (the exact index is
    # the host's by design)
    assert window["expected_deliveries"] == 10 * (
        window["warm_publishes"] + res["attempted"]
    )
    assert window["paths"].get("dev", 0) == 0
    assert window["engine"]["decide_dev_windows"] > 0
    assert window["engine"]["decide_host_windows"] == 0
    assert window["compiles_in_window"]["requests"] == 0
    assert {"decide_host_windows", "no_decide_dev_window"} <= set(
        res["compared"]
    )
    assert not {"windows_not_dev", "rules_host_windows"} & set(
        res["compared"]
    )


def test_fanout_traced_run_reports_its_per_layer_metrics(on_cpu, capsys):
    assert run_cell(on_cpu, seconds="3", trace="1") == 0
    res, _ = last_line(capsys)
    assert res["correct"] is True
    assert set(res["metrics"]) <= set(PER)
    # everything but the device trace's metrics is a number here; the
    # trace metrics stay silent on a CPU, they do not read 0
    want = {n for n, m in PER.items() if m["source"] != "device_trace"}
    assert set(res["metrics"]) == want
    m = {n: v["value"] for n, v in res["metrics"].items()}
    assert m["window_deliveries_mean"] == pytest.approx(
        10 * m["window_msgs_mean"]
    )
    assert m["flush_writes_per_window"] <= 40
    assert m["decide_pad_pct.fanout"] >= 100.0
    assert m["ack_run_pct.flood"] == 100.0
    assert m["inline_compiles.flood"] == 0
    # the two parts are parts of the decide lap
    assert 0 < m["decide_upload_us_per_msg"] + \
        m["decide_device_wait_us_per_msg"] <= m["decide_us_per_msg"]
    assert m["loop_device_wait_us_per_msg"] == pytest.approx(
        m["decide_device_wait_us_per_msg"]
    )


@pytest.mark.parametrize("fault,fails", [
    ("weak_ack", "missing"),
    ("lost_match", "missing"),
    ("host_decide", "decide_host_windows"),
])
def test_fanout_broken_guarantee_reads_not_correct(fault, fails, on_cpu,
                                                   capsys, monkeypatch):
    import control

    monkeypatch.setattr(on_cpu, "DRAIN_S", 5.0)
    assert run_cell(on_cpu, fault=control.FAULTS[fault]) == 0
    res, _ = last_line(capsys)
    assert res["correct"] is False
    over = [n for n, (v, lim) in res["compared"].items() if v > lim]
    assert any(fails in n for n in over), over
