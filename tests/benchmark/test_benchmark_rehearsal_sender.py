"""Both floods rehearsed on the CPU at ``--trace 1``: the two metrics
of the native sender thread come out as numbers (what share of the
loop's writes the thread took; what a ``send`` costs on it).  Neither
is a device number, and the microseconds here are this box's; the
override of the platform check lives in `test_benchmark_rehearsal`."""

import json
import os

import pytest

import test_benchmark_rehearsal_fanout as fanout
from test_benchmark_rehearsal import (  # noqa: F401  (on_cpu: a fixture)
    CELLS, REPO, last_line, on_cpu, run_cell,
)

SENDER = {"sender_write_pct.flood", "sender_us_per_write"}
FLOODS = ["fleet-1m-rules.flood-qos1", "exact-1k-fanout.flood-qos1"]


@pytest.mark.parametrize("cell", FLOODS)
def test_traced_flood_reads_the_senders_metrics(cell, on_cpu, capsys):
    from emqx_tpu.ops import sockwriter

    if sockwriter.load() is None:
        pytest.skip("native sockwriter not built")
    if cell in CELLS:
        assert run_cell(on_cpu, cell, seconds="3", trace="1") == 0
    else:
        assert fanout.run_cell(on_cpu, seconds="3", trace="1") == 0
    res, _ = last_line(capsys)
    assert res["correct"] is True
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    declared = {m["name"]: m for m in bench["per_layer"]
                if cell in m["workloads"]}
    assert SENDER <= set(declared)
    for name in SENDER:
        assert declared[name]["layer"] == "socket, codec, channel"
        assert declared[name]["moves"] == "deliver_rate"
    m = {n: v["value"] for n, v in res["metrics"].items()}
    # a window's flush and the publishers' acks are the thread's; the
    # handful of lone writes (CONNACK, SUBACK) are the transport's
    assert 50.0 < m["sender_write_pct.flood"] <= 100.0
    assert m["sender_us_per_write"] > 0
    # and the loop's own clock no longer holds a send for each write
    assert m["egress_us_per_msg"] > 0
