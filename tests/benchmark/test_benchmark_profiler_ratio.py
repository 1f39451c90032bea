"""`benchmark/readers/profiler_ratio.py` on a ring made by hand: a field
summed over the window per message and per second, and the three ways
it has nothing to read."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import run as harness  # noqa: E402

RING = [
    {"n_msgs": 100, "loop_ingress_us": 4000.0, "loop_cpu_us": 600000.0},
    {"n_msgs": 300, "loop_ingress_us": 8000.0, "loop_cpu_us": 900000.0},
]


@pytest.mark.parametrize("ring,args,want", [
    (RING, {"field": "loop_ingress_us", "per": "n_msgs"}, 30.0),
    # 1.5 s of CPU in a window of 2 s, as per cent
    (RING, {"field": "loop_cpu_us", "per": "window_s", "scale": 1e-4}, 75.0),
    # a program from before the field was counted: silent, not 0
    (RING, {"field": "loop_egress_us", "per": "n_msgs"}, None),
    ([], {"field": "loop_ingress_us", "per": "n_msgs"}, None),
    ([{"n_msgs": 0, "loop_ingress_us": 5.0}],
     {"field": "loop_ingress_us", "per": "n_msgs"}, None),
    (RING, {"field": "loop_ingress_us", "per": "absent"}, None),
])
def test_profiler_ratio(ring, args, want):
    got = harness.reader("profiler_ratio")(
        {"ring": ring, "window_s": 2.0}, **args
    )
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
