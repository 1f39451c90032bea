"""Both cells rehearsed on the CPU at ``--trace 1``: every per-layer
metric that reads the window's sub-spans and the event loop's clock
comes out as a number.  None of them is a device number here; the
override of the platform check lives in `test_benchmark_rehearsal`."""

import json
import os

import pytest

from test_benchmark_rehearsal import (  # noqa: F401  (on_cpu: a fixture)
    CELLS, REPO, last_line, on_cpu, run_cell,
)

NEW = {
    "fleet-1m-rules.flood-qos1": {
        "match_queue_us_per_msg", "match_device_wait_us_per_msg",
        "match_host_us_per_msg", "loop_device_wait_us_per_msg",
        "ingress_us_per_msg", "egress_us_per_msg", "loop_cpu_pct.flood",
    },
    "fleet-1m-rules.paced-qos1": {
        "loop_cpu_pct.paced", "match_queue_ms_p99.paced",
        "match_device_wait_ms_p99.paced", "match_host_ms_p99.paced",
    },
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_rehearsal_reads_the_new_metrics(cell, on_cpu, capsys):
    assert run_cell(on_cpu, cell, seconds="3", trace="1") == 0
    res, _ = last_line(capsys)
    assert res["correct"] is True
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    declared = {m["name"] for m in bench["per_layer"]
                if cell in m["workloads"]}
    assert NEW[cell] <= declared
    for name in sorted(NEW[cell]):
        assert name in res["metrics"], name
        assert res["metrics"][name]["value"] > 0, name
    # the parts are parts: none above the lap it lies in
    m = res["metrics"]
    if "match_us_per_msg" in m:
        parts = (m["match_queue_us_per_msg"]["value"]
                 + m["match_device_wait_us_per_msg"]["value"]
                 + m["match_host_us_per_msg"]["value"])
        assert 0.9 * m["match_us_per_msg"]["value"] <= parts
        assert parts <= m["match_us_per_msg"]["value"]
    for name in ("loop_cpu_pct.flood", "loop_cpu_pct.paced"):
        # one thread's CPU from the window before the first to the
        # last one's start: not more than the window and its lead-in
        if name in m:
            assert m[name]["value"] <= 100.0 * (3.0 + 0.3) / 3.0
