"""A deployment made wholly of a generator file (`generators/
plus_tree.py`: table, live subscribers and pool) rehearsed on the CPU
through `run.main`, by `overrides` on a cell that is there: no file of
the harness knows the generator's name.  Sound, with an answer lost
underneath, and with a generator nobody has.  The platform override
lives in `test_benchmark_rehearsal`; none of these numbers is a device
number."""

import json
import os

import pytest

from test_benchmark_rehearsal import (  # noqa: F401  (on_cpu: a fixture)
    last_line, on_cpu,
)

CELL = "fleet-1m-rules.flood-qos1"
LEVELS = [64, 64, 16, 8, 4]
# BASELINE.json configs[1] in small (5,000 subscriptions: past the
# engine's `rebuild_threshold` 4,096, under which a table stays in the
# delta): `+` filters alone, no rules, the
# match and decide steps held to the device; 264 x 4 = 1,056 distinct
# live filters, past the engine's fold threshold of 1,024
PLUS = {
    "config": {
        "table": {"generator": "plus_tree", "subscriptions": 5000,
                  "levels": LEVELS,
                  "masks": [["L+LLL", 20], ["LL+LL", 25], ["LLL+L", 25],
                            ["LLLL+", 25], ["L++LL", 1], ["LL++L", 4]]},
        "live": {"generator": "plus_tree", "subscribers": 264,
                 "filters_each": 4, "levels": LEVELS,
                 "masks": ["L+++L", "L++L+", "L+L++", "LL+++", "+L+L+",
                           "L++LL"]},
        "rules": {"count": 0},
        "guarantees": {"device_steps": ["match", "decide"]},
    },
    "workload": {"topics": {"generator": "plus_tree", "pool": 4096,
                            "nomatch": 0.1},
                 "warmup_publishes": 300, "publisher_children": 1,
                 "subscriber_children": 1},
    "replace": ["table", "live", "topics"],
}


def run_cell(harness, overrides=PLUS, fault=None):
    return harness.main(
        ["--workload", CELL, "--seed", "3000000032", "--seconds", "2",
         "--trace", "0"], fault=fault, overrides=overrides,
    )


def test_a_deployment_of_a_generator_file_runs_to_a_correct_line(on_cpu,
                                                                 capsys):
    assert run_cell(on_cpu) == 0
    out = capsys.readouterr().out
    lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    res = lines[-1]
    window, = [ln for ln in lines if ln.get("phase") == "window"]
    subscribed, = [ln for ln in lines if ln.get("phase") == "subscribed"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert all(v <= lim for v, lim in res["compared"].values())
    # the table went into the base, the live filters were folded onto
    # the device, and every window was matched and decided there
    assert subscribed["live_filters"] == 1056
    assert subscribed["index"]["base"] == 5000
    # (the fold takes what the delta held as it crossed the threshold;
    # subscriptions that came after stay in the host-matched residual)
    idx = subscribed["index"]
    assert idx["folded"] >= 1024 and not idx["folding"]
    assert idx["folded"] + idx["residual"] == 1056
    assert set(window["paths"]) == {"dev"}
    assert window["engine"]["decide_host_windows"] == 0
    assert window["compiles_in_window"]["requests"] == 0
    assert window["expected_deliveries"] > 0 and window["expected_firings"] == 0
    assert {"windows_not_dev", "decide_host_windows"} <= set(res["compared"])
    assert "rules_host_windows" not in res["compared"]


def test_the_same_with_an_answer_lost_reads_not_correct(on_cpu, capsys,
                                                        monkeypatch):
    import control

    monkeypatch.setattr(on_cpu, "DRAIN_S", 5.0)
    assert run_cell(on_cpu, fault=control.FAULTS["lost_match"]) == 0
    res, _ = last_line(capsys)
    assert res["correct"] is False
    assert res["compared"]["deliveries_missing"][0] > 0


OVERLAPPING = """
def live(subscribers):
    return [(f"sub{j}", ["tele/a0/+/+/+/+", "tele/+/b0/+/+/+"], 1)
            for j in range(subscribers)]
"""


@pytest.mark.parametrize("what,files,overrides,says", [
    ("a generator nobody has", {},
     {**PLUS, "workload": {**PLUS["workload"], "topics": {
         "generator": "nobody", "pool": 64}}},
     "no pool generator 'nobody'"),
    ("an argument the generator does not take", {},
     {**PLUS, "config": {**PLUS["config"], "table": {
         **PLUS["config"]["table"], "fan_out": 2}}},
     "unexpected keyword argument 'fan_out'"),
    ("a file that bears a built-in's name", {"fleet_zipf.py": "pool = list"},
     None, "bears the name of a built-in"),
    # found only once the window has closed, by the referee, and laid at
    # the data's door: not a `deliveries_duplicated` of the program's
    ("a live set that overlaps on the pool", {"overlapping.py": OVERLAPPING},
     {**PLUS, "config": {**PLUS["config"], "live": {
         "generator": "overlapping", "subscribers": 3}}},
     "matches more than one filter of subscriber 'sub0'"),
], ids=["unknown-name", "unknown-argument", "shadowing-file", "overlap"])
def test_a_bad_generator_group_is_refused_with_no_result_line(
        what, files, overrides, says, on_cpu, capsys, monkeypatch, tmp_path):
    import shutil

    import traffic

    if files:
        shutil.copy(os.path.join(traffic.GENERATORS, "plus_tree.py"), tmp_path)
        for name, text in files.items():
            (tmp_path / name).write_text(text)
        monkeypatch.setattr(traffic, "GENERATORS", str(tmp_path))
    assert run_cell(on_cpu, overrides=overrides) == 1
    out = capsys.readouterr()
    assert '"correct"' not in out.out
    refusal, = [ln for ln in out.err.splitlines()
                if ln.startswith("refused: ")]
    assert says in refusal
