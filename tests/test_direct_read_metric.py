"""`ingress_direct_read_pct.flood` and `loop_cpu_us_per_msg`, from the
counter to the result line: a served window's ring record holds
`loop_ingress_reads_direct` (every read of a plain-TCP client, none of
a WebSocket one), the two metric files read it through the reader the
benchmark has, and a traced CPU rehearsal of each flood cell reports
both as numbers (the harness, its platform override and the small
sizes are `tests/benchmark/`'s; the `+` tree's cell lists neither yet:
its rehearsal pins the cell's count of metrics).  None of these
numbers is a device number."""

import asyncio
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))

import test_benchmark_rehearsal as fleet  # noqa: E402
import test_benchmark_rehearsal_fanout as fanout  # noqa: E402
import test_benchmark_rehearsal_p2p as p2p  # noqa: E402
from test_benchmark_rehearsal import last_line, on_cpu  # noqa: E402,F401

from emqx_tpu.broker.listener import BrokerServer  # noqa: E402
from emqx_tpu.config import BrokerConfig, ListenerConfig  # noqa: E402
from mqtt_client import TestClient  # noqa: E402
from test_listeners import WsTestClient  # noqa: E402

DIRECT = "ingress_direct_read_pct.flood"
CPU = "loop_cpu_us_per_msg"
FLEET = "fleet-1m-rules.flood-qos1"


def how(name):
    with open(os.path.join(fleet.BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("kind,share", [("tcp", 100.0), ("ws", 0.0)])
def test_a_served_windows_record_counts_the_direct_reads(kind, share,
                                                         on_cpu):
    async def main():
        cfg = BrokerConfig()
        cfg.engine.use_device = False
        cfg.listeners = [
            ListenerConfig(name=kind, type=kind, bind="127.0.0.1", port=0)
        ]
        srv = BrokerServer(cfg)
        await srv.start()
        try:
            client = WsTestClient if kind == "ws" else TestClient
            port = srv.listeners[0].port
            sub, pub = client(port, "sub"), client(port, "pub")
            for c in (sub, pub):
                await c.connect()
            await sub.subscribe("m/#", qos=1)
            for i in range(20):
                await pub.publish("m/%d" % i, b"x", qos=1)
            for c in (sub, pub):
                await c.disconnect()
            return srv.broker.profiler.windows(limit=256)
        finally:
            await srv.stop()

    ring = asyncio.run(main())
    reads = sum(r["loop_ingress_reads"] for r in ring)
    direct = sum(r["loop_ingress_reads_direct"] for r in ring)
    assert reads >= 20 and direct == (reads if kind == "tcp" else 0)
    got = on_cpu.reader(how(DIRECT)["reader"])(
        {"ring": ring, "window_s": 1.0}, **how(DIRECT)["args"]
    )
    assert got == share


@pytest.mark.parametrize("name,ring,reads", [
    # a program from before the counter (the parent): nothing to read
    (DIRECT, [{"loop_ingress_reads": 40}], None),
    (DIRECT, [{"loop_ingress_reads": 40, "loop_ingress_reads_direct": 40},
              {"loop_ingress_reads": 10, "loop_ingress_reads_direct": 0}],
     80.0),
    (DIRECT, [{"loop_ingress_reads": 0, "loop_ingress_reads_direct": 0}],
     None),
    # the loop thread's CPU a publish: both sides have the field
    (CPU, [{"n_msgs": 500, "loop_cpu_us": 90000.0},
           {"n_msgs": 500, "loop_cpu_us": 110000.0}], 200.0),
    (CPU, [{"n_msgs": 500}], None),
], ids=["field-absent", "four-in-five", "no-read", "cpu-a-publish",
        "cpu-absent"])
def test_the_two_metrics_are_read_by_their_own_files(name, ring, reads,
                                                     on_cpu):
    assert how(name)["reader"] == "profiler_ratio"
    got = on_cpu.reader("profiler_ratio")(
        {"ring": ring, "window_s": 20}, **how(name)["args"]
    )
    assert got == reads


@pytest.mark.parametrize("cell", [FLEET, fanout.CELL, p2p.CELL])
def test_flood_rehearsal_reports_the_direct_share_and_the_cpu_a_publish(
        cell, on_cpu, capsys):
    if cell == FLEET:
        rc = fleet.run_cell(on_cpu, cell, seconds="3", trace="1")
    else:
        mod = {fanout.CELL: fanout, p2p.CELL: p2p}[cell]
        rc = mod.run_cell(on_cpu, seconds="3", trace="1")
    assert rc == 0
    res, _ = last_line(capsys)
    assert res["correct"] is True
    direct, cpu = res["metrics"][DIRECT], res["metrics"][CPU]
    # every connection of every cell is plain TCP with no limiter
    assert direct["unit"] == "%" and direct["value"] == 100.0
    assert cpu["unit"] == "us/msg" and cpu["value"] > 0
