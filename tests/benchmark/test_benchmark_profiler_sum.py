"""`benchmark/readers/profiler_sum.py` on a ring made by hand: ring
fields and stage laps added, others taken off, per message and per
second, and the ways it has nothing to read."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import run as harness  # noqa: E402

RING = [
    {"n_msgs": 100, "loop_tail_us": 9000.0, "loop_acks_us": 1000.0,
     "stages_us": {"deliver": 3000.0, "flush": 500.0, "collect": 100.0}},
    {"n_msgs": 300, "loop_tail_us": 21000.0, "loop_acks_us": 3000.0,
     "stages_us": {"deliver": 8000.0, "rules": 2400.0}},  # no flush lap
]
TAIL = {"fields": ["loop_tail_us"]}
LAPS = {"stages": ["deliver", "flush", "rules", "collect", "prepare"]}


@pytest.mark.parametrize("args,want", [
    # (30,000 - 14,000) / 400: a stage a window lacks counts as zero
    ({"plus": TAIL, "minus": LAPS, "per": "n_msgs"}, 40.0),
    ({"plus": TAIL}, 75.0),  # no `minus`, `per` defaults to a message
    ({"plus": {"fields": ["loop_tail_us", "loop_acks_us"],
               "stages": ["collect"]},
      "minus": {"fields": ["loop_acks_us"]}, "per": "n_msgs"}, 75.25),
    # 0.03 s of a 2 s window, as per cent
    ({"plus": TAIL, "per": "window_s", "scale": 1e-4}, 1.5),
    # may come out below zero: a difference is not clipped
    ({"plus": {"stages": ["collect"]}, "minus": TAIL}, -74.75),
    # a program from before a field was counted: silent, not a number
    ({"plus": {"fields": ["loop_tail_us", "loop_poll_us"]}}, None),
    ({"plus": TAIL, "minus": {"fields": ["loop_egress_us"]}}, None),
    ({"plus": TAIL, "per": "absent"}, None),
])
def test_profiler_sum(args, want):
    got = harness.reader("profiler_sum")(
        {"ring": RING, "window_s": 2.0}, **args
    )
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_profiler_sum_on_an_empty_ring_reads_nothing():
    read = harness.reader("profiler_sum")
    assert read({"ring": [], "window_s": 2.0}, plus=TAIL) is None
    assert read({"ring": [], "window_s": 2.0},
                plus={"stages": ["deliver"]}) is None


def test_the_unclocked_metric_names_the_loop_threads_laps():
    """`loop_unclocked_us_per_msg`: `tail` less the laps that run on
    the loop thread with no await inside, and the collector's own
    stretches; the match laps (executor threads) and the waits stay
    out of it."""
    with open(os.path.join(REPO, "benchmark", "metrics",
                           "loop_unclocked_us_per_msg.json")) as f:
        m = json.load(f)
    assert m["reader"] == "profiler_sum"
    assert m["args"]["plus"] == {"fields": ["loop_tail_us"]}
    assert sorted(m["args"]["minus"]["stages"]) == sorted([
        "prepare", "expand", "decide", "deliver", "flush", "rules",
        "collect"])
    assert m["args"]["per"] == "n_msgs"
