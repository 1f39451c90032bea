"""`benchmark/readers/profiler_stage_per.py` on a ring made by hand:
stage laps summed over the window per unit of a ring field, and the
ways it has nothing to read."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import run as harness  # noqa: E402

RING = [
    {"n_msgs": 100, "n_clients": 50,
     "stages_us": {"deliver": 1000.0, "assemble": 400.0, "flush": 500.0}},
    {"n_msgs": 300, "n_clients": 250,
     "stages_us": {"deliver": 3000.0, "assemble": 900.0, "flush": 1500.0}},
    # a window that delivered nothing has neither lap nor run
    {"n_msgs": 10, "n_clients": 0, "stages_us": {"decide": 70.0}},
]


@pytest.mark.parametrize("ring,args,want", [
    # 6,000 us of deliver + flush over 300 client runs
    (RING, {"stages": ["deliver", "flush"], "per": "n_clients"}, 20.0),
    (RING, {"stages": ["flush"], "per": "n_clients"}, 2000.0 / 300),
    # per message it is `profiler_stage`'s us_per_msg
    (RING, {"stages": ["deliver", "flush"], "per": "n_msgs"}, 6000.0 / 410),
    # no window with the stages, no such field, a field that sums to 0
    (RING, {"stages": ["rules"], "per": "n_clients"}, None),
    (RING, {"stages": ["deliver"], "per": "absent"}, None),
    ([{"n_clients": 0, "stages_us": {"deliver": 5.0}}],
     {"stages": ["deliver"], "per": "n_clients"}, None),
    ([], {"stages": ["deliver", "flush"], "per": "n_clients"}, None),
])
def test_profiler_stage_per(ring, args, want):
    got = harness.reader("profiler_stage_per")({"ring": ring}, **args)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_agrees_with_profiler_stage_per_message():
    run = {"ring": RING}
    stages = ["deliver", "flush"]
    assert harness.reader("profiler_stage_per")(
        run, stages=stages, per="n_msgs"
    ) == pytest.approx(harness.reader("profiler_stage")(
        run, stages=stages, statistic="us_per_msg"
    ))
