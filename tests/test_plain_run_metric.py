"""`deliver_plain_run_pct.flood` in the three flood cells' CPU
rehearsals (the harness, its platform override and the small sizes
are `tests/benchmark/`'s): a traced run reports it as a number, and in
the two exact deployments, where every run is what the columnar pass
of `Broker._dispatch_columns` serves, as 100.  None of these numbers
is a device number."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))

import test_benchmark_rehearsal as fleet  # noqa: E402
import test_benchmark_rehearsal_fanout as fanout  # noqa: E402
import test_benchmark_rehearsal_p2p as p2p  # noqa: E402
from test_benchmark_rehearsal import last_line, on_cpu  # noqa: E402,F401

NAME = "deliver_plain_run_pct.flood"


@pytest.mark.parametrize("cell", [
    "fleet-1m-rules.flood-qos1", fanout.CELL, p2p.CELL,
])
def test_flood_rehearsal_reports_the_plain_run_share(cell, on_cpu, capsys):
    if cell == p2p.CELL:
        rc = p2p.run_cell(on_cpu, seconds="3", trace="1")
    elif cell == fanout.CELL:
        rc = fanout.run_cell(on_cpu, seconds="3", trace="1")
    else:
        rc = fleet.run_cell(on_cpu, cell, seconds="3", trace="1")
    assert rc == 0
    res, _ = last_line(capsys)
    assert res["correct"] is True
    metric = res["metrics"][NAME]
    assert metric["unit"] == "%" and 0 < metric["value"] <= 100
    if cell != "fleet-1m-rules.flood-qos1":
        assert metric["value"] == 100.0
