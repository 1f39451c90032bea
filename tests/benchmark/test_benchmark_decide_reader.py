"""`benchmark/readers/trace_decide.py` and `kernel_work_decide.py`: the
work function's arithmetic, and the reader on the trace recorded on
the chip (`trace_fixture.json`, which holds two runs of XLA module
``jit_decide_batch``): a number where the module is there, nothing
where it is not."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)

import kernel_work  # noqa: E402
import kernel_work_decide  # noqa: E402
import run as harness  # noqa: E402
import trace_reduce as TR  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "trace_fixture.json")
PEAK = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]
RING = [
    {"at": 100.1, "n_msgs": 512, "n_deliveries": 128000},
    {"at": 100.3, "n_msgs": 300, "n_deliveries": 75000},
    {"at": 107.0, "n_msgs": 512, "n_deliveries": 128000},  # not traced
]


def recorded_run(drop=()):
    planes = json.load(open(FIXTURE))["planes"]
    trace = TR.reduce(planes)
    trace["window_wall"] = (100.0, 100.0 + trace["window_s"])
    for name in drop:
        del trace["modules"][name]
    return {"ring": RING, "peak": PEAK, "trace": trace}


def test_decide_work_from_shapes():
    work = kernel_work_decide.decide_window(128000, 512)
    assert work == {"bytes": 128000 * 23 + 512 * 6, "ops": 128000 * 12}
    least, bound = kernel_work.least_seconds(work, PEAK)
    assert bound == "hbm"
    assert least == pytest.approx((128000 * 23 + 3072) / 819e9)
    assert kernel_work_decide.decide_window(0, 0) == {"bytes": 0, "ops": 0}


def test_reader_on_the_recorded_trace():
    read = harness.reader("trace_decide")
    run = recorded_run()
    secs = run["trace"]["modules"]["jit_decide_batch"]["s"]
    assert run["trace"]["modules"]["jit_decide_batch"]["n"] == 2
    us = read(run, reduction="kernel_us_per_window")
    assert us == pytest.approx(secs * 1e6 / 2)
    share = read(run, reduction="roofline_pct")
    work = kernel_work_decide.decide_window(203000, 812)
    assert share == pytest.approx(100 * work["bytes"] / 819e9 / secs)
    assert 0 < share < 100
    assert run["notes"]["decide_kernel_bound"] == "hbm"
    with pytest.raises(ValueError):
        read(run, reduction="idle_pct")


@pytest.mark.parametrize("reduction", ["kernel_us_per_window",
                                       "roofline_pct"])
def test_reader_is_silent_where_there_is_nothing_to_read(reduction):
    read = harness.reader("trace_decide")
    # no such module in the trace: the host decided, or nothing did
    assert read(recorded_run(drop=["jit_decide_batch"]),
                reduction=reduction) is None
    # no trace at all (``--trace 0``)
    assert read({"ring": RING, "peak": PEAK, "trace": None},
                reduction=reduction) is None
    # no window of the ring inside the traced window
    run = recorded_run()
    run["ring"] = RING[2:]
    assert read(run, reduction=reduction) is None


def test_roofline_is_silent_where_no_window_delivered():
    run = recorded_run()
    run["ring"] = [{"at": 100.1, "n_msgs": 4, "n_deliveries": 0}]
    read = harness.reader("trace_decide")
    assert read(run, reduction="roofline_pct") is None
    assert read(run, reduction="kernel_us_per_window") > 0
