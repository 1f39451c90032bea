"""The turn clock's metrics, the executors' CPU beside their wall time
and the collections' clock, read end to end through `benchmark/run.py`
on the CPU at a small size: a traced run of the point-to-point cell and
of the fleet flood prints every metric its cell lists for them, none
missing (a `null` in the result line).  The platform override lives in
`test_benchmark_rehearsal`; none of these numbers is a device number."""

import json
import os

import pytest

import test_benchmark_rehearsal as fleet
import test_benchmark_rehearsal_p2p as p2p
from test_benchmark_rehearsal import (  # noqa: F401  (on_cpu: a fixture)
    REPO, last_line, on_cpu,
)

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
TURN = {"loop_poll_pct.flood", "loop_recv_us_per_msg", "loop_acks_us_per_msg",
        "collect_us_per_msg", "loop_unclocked_us_per_msg",
        "reads_per_turn.flood", "loop_gc_us_per_msg"}
MATCH = {"match_host_cpu_us_per_msg", "overlay_us_per_msg",
         "overlay_cpu_us_per_msg"}
NEW = {
    "p2p-1k.flood-qos1": TURN,
    "fleet-1m-rules.flood-qos1": TURN | MATCH,
    "exact-1k-fanout.flood-qos1": TURN,
    "fleet-1m-rules.paced-qos1": {"loop_poll_pct.paced"},
}
PHASES = ("loop_poll_us", "loop_recv_us", "loop_reads_us", "loop_acks_us",
          "loop_tail_us")
LAPS = ("batch_wait", "prepare", "match_submit", "match_wait", "dispatch_wait",
        "expand", "decide", "deliver", "flush", "rules")
RUN = {
    "p2p-1k.flood-qos1": lambda harness: p2p.run_cell(
        harness, seconds="3", trace="1", seed="3000000035"),
    "fleet-1m-rules.flood-qos1": lambda harness: fleet.run_cell(
        harness, "fleet-1m-rules.flood-qos1", seconds="3", trace="1",
        seed="3000000035"),
}


def test_the_new_metrics_are_declared_where_the_issue_lists_them():
    listed = {}
    for m in BENCH["per_layer"]:
        if m["name"] in TURN | MATCH | {"loop_poll_pct.paced"}:
            for cell in m["workloads"]:
                listed.setdefault(cell, set()).add(m["name"])
    assert listed == NEW  # and `plus-100k.flood-qos1` lists none


@pytest.mark.parametrize("cell", sorted(RUN))
def test_traced_run_prints_every_new_metric_of_its_cell(cell, on_cpu, capsys,
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
    assert RUN[cell](on_cpu) == 0
    res, _ = last_line(capsys)
    assert res["correct"] is True
    m = res["metrics"]
    for name in sorted(NEW[cell]):
        assert name in m, name  # a reader's None leaves the name out
        assert m[name]["value"] >= 0.0, name
    assert 0.0 <= m["loop_poll_pct.flood"]["value"] <= 100.0
    assert m["reads_per_turn.flood"]["value"] > 0
    if cell.startswith("fleet"):
        # a section's CPU is at most its wall time, give or take the
        # clocks' grain over the run's windows
        assert 0 < m["overlay_cpu_us_per_msg"]["value"] <= (
            1.05 * m["overlay_us_per_msg"]["value"] + 1.0)
        assert m["match_host_cpu_us_per_msg"]["value"] <= (
            1.05 * m["match_host_us_per_msg"]["value"] + 1.0)
    # the phases of the measured windows' records add up to the wall
    # time those records span: each record holds the growth since the
    # commit before it, and a record commits as its last lap ends
    ring = sorted(rings[0], key=lambda r: r["seq"])
    assert len(ring) > 2 and all(p in r for r in ring for p in PHASES)

    def committed(r):
        return r["at"] + sum(r["stages_us"].get(k, 0.0) for k in LAPS) / 1e6

    phases = sum(r[p] for r in ring[1:] for p in PHASES) / 1e6
    span = committed(ring[-1]) - committed(ring[0])
    assert phases == pytest.approx(span, rel=0.02, abs=5e-3)
    for r in ring:
        assert r["gc_collections"] >= 0 and r["gc_us"] >= 0.0
        assert r["loop_turns"] >= r["loop_recv_turns"] >= 0
