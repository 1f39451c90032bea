"""`p2p-1k.flood-qos1` rehearsed on the CPU at a small size (40 pairs,
4 in flight a publisher, the shipped session settings): sound, traced,
and with each guarantee the cell can lose broken underneath.  The
platform override lives in `test_benchmark_rehearsal`; none of these
numbers is a device number."""

import asyncio
import json
import os

import pytest

from test_benchmark_rehearsal import (  # noqa: F401  (on_cpu: a fixture)
    REPO, last_line, on_cpu,
)

CELL = "p2p-1k.flood-qos1"
SMALL = {"config": {"live": {"subscribers": 40, "topics": 40}},
         "workload": {"warmup_publishes": 200, "publishers": 40,
                      "topics": {"pool": 40}, "publisher_children": 1,
                      "subscriber_children": 1}}
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
PER = {m["name"]: m for m in BENCH["per_layer"] if CELL in m["workloads"]}
NEW = {"ingress_publish_us_per_publish", "ingress_ack_us_per_ack",
       "publishes_per_read.flood", "packets_per_write.flood",
       "sender_busy_pct.flood", "deliver_us_per_client_run"}


def run_cell(harness, seconds="2", trace="0", fault=None,
             seed="3000000030"):
    return harness.main(
        ["--workload", CELL, "--seed", seed, "--seconds", seconds,
         "--trace", trace], fault=fault, overrides=SMALL,
    )


def test_p2p_cell_is_declared_at_its_size():
    conf = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "p2p-1k.json")))
    work = json.load(open(os.path.join(
        REPO, "benchmark", "workloads", CELL + ".json")))
    # one topic a pair, QoS1 on both legs, the shipped session settings
    assert conf["live"] == {"generator": "exact_fanout",
                            "subscribers": 1000, "topics": 1000, "qos": 1}
    assert conf["mqtt"] == {} and conf["reduced"] == {}
    assert work["publishers"] == work["topics"]["pool"] == 1000
    assert work["qos"] == 1 and work["inflight"] in (2, 4, 8)
    # the fan-out cell's twenty and the six this cell came with
    assert len(PER) >= 26 and NEW <= set(PER)


def test_p2p_cell_runs_to_a_correct_line(on_cpu, capsys):
    assert run_cell(on_cpu) == 0
    res, err, window = last_line(capsys, also_window=True)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"deliver_rate", "setup_s"}
    # one subscriber a topic: one delivery a publish, every window
    # decided on the device, none matched there (the exact index is
    # the host's by design), nothing dropped at the shipped limits
    assert window["expected_deliveries"] == (
        window["warm_publishes"] + res["attempted"]
    )
    assert window["paths"].get("dev", 0) == 0
    assert window["engine"]["decide_dev_windows"] > 0
    assert window["engine"]["decide_host_windows"] == 0
    assert window["compiles_in_window"]["requests"] == 0
    assert not [k for k in window["broker_drops"] if "queue" in k
                or "inflight" in k], window["broker_drops"]
    assert {"decide_host_windows", "no_decide_dev_window"} <= set(
        res["compared"]
    )
    assert not {"windows_not_dev", "rules_host_windows"} & set(
        res["compared"]
    )


def test_p2p_traced_run_reports_its_per_layer_metrics(on_cpu, capsys,
                                                      monkeypatch):
    rings = []
    real = on_cpu.reader

    def reader(name):
        read = real(name)

        def spy(run, **args):
            rings.append(run["ring"])
            return read(run, **args)
        return spy

    monkeypatch.setattr(on_cpu, "reader", reader)
    # the broker's first housekeeping tick, a second after start(),
    # samples the host and raises its alarms as publishes of its own,
    # which no one receives; the window opened ~1 s after start() and
    # took one in now and then.  It opens after that tick, and the tick
    # always raises `high_cpu`, so every run shows the window clear of it
    from emqx_tpu.broker.listener import BrokerServer

    real_start = BrokerServer.start

    async def start(server):
        await real_start(server)
        while not server.sysmon._last:
            await asyncio.sleep(0.02)

    monkeypatch.setattr(BrokerServer, "start", start)
    monkeypatch.setattr(os, "getloadavg", lambda: (1e3, 1e3, 1e3))
    assert run_cell(on_cpu, seconds="3", trace="1") == 0
    res, _, window = last_line(capsys, also_window=True)
    assert window["broker_drops"]["messages.dropped.no_subscribers"] >= 1
    assert res["correct"] is True
    # everything but the device trace's metrics is a number here; the
    # trace metrics stay silent on a CPU, they do not read 0
    want = {n for n, m in PER.items() if m["source"] != "device_trace"}
    assert set(res["metrics"]) == want and NEW <= want
    m = {n: v["value"] for n, v in res["metrics"].items()}
    assert m["window_deliveries_mean"] == pytest.approx(
        m["window_msgs_mean"]
    )
    assert m["publishes_per_read.flood"] >= 1
    assert m["packets_per_write.flood"] >= 1
    assert m["ack_run_pct.flood"] == 100.0
    assert m["inline_compiles.flood"] == 0
    assert 0 < m["sender_busy_pct.flood"] < 100
    # a run a delivery: a client run costs what a message costs, or
    # more where one run carried two of a publisher's messages
    assert m["flush_writes_per_window"] <= m["window_msgs_mean"]
    assert m["deliver_us_per_client_run"] >= m["deliver_us_per_msg"] > 0
    # the split's parts are parts of the ingress clock, over the ring
    ring = rings[0]
    total = sum(r["loop_ingress_us"] for r in ring)
    parts = sum(r["loop_ingress_publish_us"] + r["loop_ingress_ack_us"]
                for r in ring)
    assert 0 < parts <= total + 0.1 * len(ring)
    assert sum(r["loop_ingress_publish_reads"] + r["loop_ingress_ack_reads"]
               for r in ring) <= sum(r["loop_ingress_reads"] for r in ring)
    assert m["ingress_publish_us_per_publish"] > 0
    assert m["ingress_ack_us_per_ack"] > 0


@pytest.mark.parametrize("fault,fails", [
    ("weak_ack", "missing"),
    ("lost_match", "missing"),
    ("host_decide", "decide_host_windows"),
])
def test_p2p_broken_guarantee_reads_not_correct(fault, fails, on_cpu,
                                                capsys, monkeypatch):
    import control

    monkeypatch.setattr(on_cpu, "DRAIN_S", 5.0)
    assert run_cell(on_cpu, fault=control.FAULTS[fault]) == 0
    res, _ = last_line(capsys)
    assert res["correct"] is False
    over = [n for n, (v, lim) in res["compared"].items() if v > lim]
    assert any(fails in n for n in over), over
