"""One case per reader kind under `benchmark/readers/`, on a run record
made by hand; and the work and peaks the roofline share stands on."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)

import kernel_work  # noqa: E402
import run as harness  # noqa: E402

RING = [
    {"at": 100.5, "n_msgs": 100, "path": "dev", "stages_us": {
        "batch_wait": 900.0, "match_submit": 50.0, "match_wait": 450.0,
        "expand": 100.0, "decide": 200.0, "deliver": 1000.0,
        "assemble": 400.0, "flush": 500.0, "rules": 300.0}},
    {"at": 101.5, "n_msgs": 300, "path": "dev", "stages_us": {
        "batch_wait": 100.0, "match_submit": 150.0, "match_wait": 1350.0,
        "expand": 300.0, "decide": 600.0, "deliver": 3000.0,
        "assemble": 900.0, "flush": 1500.0, "rules": 900.0}},
]
PEAK = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]


def a_run(trace=True):
    return {
        "ring": RING, "window_s": 10.0, "peak": PEAK,
        "engine": {"decide_dev_windows": 2, "rules_dev_windows": 2},
        "compiles": {"requests": 3, "fresh": 1, "seconds": 0.75},
        "loadgen": {"setup_s": 41.5, "deliver_rate": 12345.6,
                    "cpu_pct_busiest": 37.0,
                    "deliver_ms": np.arange(1.0, 1001.0),
                    "late_ms": np.zeros(0)},
        "shapes": {"f_width": 16, "kernel_levels": 6, "matches_per_row": 9},
        "trace": {
            "busy_s": 0.3, "window_s": 3.0, "window_wall": (100.0, 103.0),
            "modules": {"jit_match_batch_compact": {"s": 0.004, "n": 4},
                        "jit_decide_batch": {"s": 0.001, "n": 2}},
        } if trace else None,
    }


@pytest.mark.parametrize("reader,args,want", [
    ("loadgen", {"field": "setup_s"}, 41.5),
    ("loadgen", {"field": "deliver_rate"}, 12345.6),
    ("loadgen", {"field": "deliver_ms", "statistic": "p50"}, 500.5),
    ("loadgen", {"field": "deliver_ms", "statistic": "p99"}, 990.01),
    ("loadgen", {"field": "late_ms", "statistic": "p99"}, None),
    ("loadgen", {"field": "puback_ms", "statistic": "p99"}, None),
    ("profiler_ring", {"field": "n_msgs", "statistic": "mean"}, 200.0),
    ("profiler_ring", {"field": "n_msgs", "statistic": "max"}, 300.0),
    ("profiler_ring", {"field": "absent"}, None),
    ("profiler_stage", {"stages": ["match_submit", "match_wait"]}, 5.0),
    ("profiler_stage", {"stages": ["deliver", "flush"]}, 15.0),
    ("profiler_stage", {"stages": ["batch_wait"], "statistic": "p99_ms"},
     0.892),
    ("profiler_stage", {"stages": ["tokenize"]}, None),
    ("engine_stat", {"key": "decide_dev_windows"}, 2),
    ("engine_stat", {"key": "absent"}, None),
    ("compile_log", {"what": "requests"}, 3),
    ("compile_log", {"what": "seconds"}, 0.75),
    ("trace", {"reduction": "idle_pct"}, 90.0),
    ("trace", {"reduction": "kernel_us_per_window",
               "kernels": ["jit_match_batch_compact", "jit_match_batch"]},
     2000.0),
    ("trace", {"reduction": "kernel_us_per_window",
               "kernels": ["jit_sharded_match"]}, None),
])
def test_reader(reader, args, want):
    got = harness.reader(reader)(a_run(), **args)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("reduction", [
    "idle_pct", "kernel_us_per_window", "roofline_pct",
])
def test_trace_reader_is_silent_without_a_trace(reduction):
    read = harness.reader("trace")
    assert read(a_run(trace=False), reduction=reduction,
                kernels=["jit_match_batch_compact"]) is None


def test_roofline_share_from_work_and_peaks():
    work = kernel_work.match_window(400, 16, 6, 9)
    assert work["bytes"] == 400 * 16 * 6 * 96 + 400 * 32 + 400 * 40
    least, bound = kernel_work.least_seconds(work, PEAK)
    assert bound == "hbm" and least == pytest.approx(work["bytes"] / 819e9)
    got = harness.reader("trace")(
        a_run(), reduction="roofline_pct",
        kernels=["jit_match_batch_compact", "jit_match_batch"],
    )
    assert got == pytest.approx(100 * least / 0.004)
    assert 0 < got < 100


def test_peaks_name_their_source_and_an_unknown_kind_is_refused(monkeypatch):
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))
    assert all("source" in p for p in peaks.values())
    assert PEAK["hbm_GBps"] == 819 and PEAK["bf16_TFLOPs"] == 197
    # the harness has no CPU branch: on this box JAX finds no TPU
    with pytest.raises(harness.Refused, match="need platform 'tpu'"):
        harness.preflight(1)
    monkeypatch.setattr(harness, "PLATFORM", "cpu")
    with pytest.raises(harness.Refused, match="not in peaks.json"):
        harness.preflight(1)
