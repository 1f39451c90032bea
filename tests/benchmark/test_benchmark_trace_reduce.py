"""`benchmark/trace_reduce.py`: the arithmetic on events made by hand,
and the whole reduction on a trace recorded on the chip
(`trace_fixture.json`: the device planes of a `--trace 1` run of
`fleet-1m-rules.flood-qos1` on a TPU v5 lite, cut to its first
windows by `extract` + a slice; the numbers asserted below were read
off that same file, so they pin the reduction, not the chip)."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import trace_reduce as TR  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "trace_fixture.json")


def test_union_merges_overlaps():
    assert TR.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)]) == [
        [0, 3], [5, 8], [10, 11]
    ]


def test_module_names_lose_their_run_id():
    assert TR.module_of("jit_match_batch_compact(123456)") \
        == "jit_match_batch_compact"
    assert TR.module_of("jit_decide_batch") == "jit_decide_batch"


PLANES = {"/device:TPU:0": {
    "XLA Ops": [["fusion.1", 100, 50], ["gather.2", 150, 100],
                ["fusion.1", 400, 100], ["copy.3", 900, 200]],
    "XLA Modules": [["jit_match_batch_compact(7)", 100, 150],
                    ["jit_match_batch_compact(7)", 400, 100],
                    ["jit_decide_batch(9)", 900, 200]],
}}


def test_reduce_by_hand():
    out = TR.reduce(PLANES, (0, 1200), host=[("deliver", 250, 400),
                                             ("rules", 500, 900)])
    # busy 100-250, 400-500 and 900-1100
    assert out["busy_s"] == pytest.approx(450e-9)
    assert out["window_s"] == pytest.approx(1200e-9)
    assert out["modules"]["jit_match_batch_compact"] == {
        "s": pytest.approx(250e-9), "n": 2}
    assert out["modules"]["jit_decide_batch"]["n"] == 1
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["copy.3"] == pytest.approx(200e-9)
    assert ops["fusion.1"] == pytest.approx(150e-9)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # 0-100 and 1100-1200 nothing open, 250-400 deliver, 500-900 rules
    assert gaps == {"rules": pytest.approx(400e-9),
                    "deliver": pytest.approx(150e-9),
                    "no window open": pytest.approx(200e-9)}
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_window_clips_events_and_modules_outside_do_not_count():
    out = TR.reduce(PLANES, (120, 950))
    # fusion.1 clipped to 120-150, copy.3 to 900-950
    assert out["busy_s"] == pytest.approx((30 + 100 + 100 + 50) * 1e-9)
    assert out["modules"]["jit_match_batch_compact"]["n"] == 1
    assert "jit_decide_batch" not in out["modules"]


def test_no_device_plane_reads_nothing():
    assert TR.reduce({}) is None
    assert TR.reduce({"/device:TPU:0": {"XLA Ops": []}}) is None


def test_host_intervals_pair_begin_and_end():
    spans = [
        {"ph": "M", "name": "thread_name", "tid": 1},
        {"ph": "B", "name": "expand", "tid": 1, "ts": 10.0},
        {"ph": "E", "name": "expand", "tid": 1, "ts": 30.0},
        {"ph": "B", "name": "expand", "tid": 2, "ts": 20.0},
        {"ph": "E", "name": "expand", "tid": 2, "ts": 25.0},
        {"ph": "X", "name": "compile", "tid": 0, "ts": 5.0, "dur": 2.0},
    ]
    assert sorted(TR.host_intervals(spans)) == [
        ("compile", 5.0, 7.0), ("expand", 10.0, 30.0), ("expand", 20.0, 25.0)
    ]


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_recorded_trace():
    fx = json.load(open(FIXTURE))
    out = TR.reduce(fx["planes"])
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    for key, want in fx["expect"].items():
        if key == "modules":
            for name, m in want.items():
                assert out["modules"][name]["n"] == m["n"]
                assert out["modules"][name]["s"] == pytest.approx(m["s"])
        else:
            assert out[key] == pytest.approx(want)
