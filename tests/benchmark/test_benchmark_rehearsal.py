"""`benchmark/run.py` rehearsed on the CPU at a tiny size, every cell,
sound and with a guarantee broken underneath.

The harness has no CPU branch and no smaller size: the override of its
platform check, the stand-in peaks entry and the shrunken table live
HERE.  This finds wrong paths, arguments and control flow, and shows
that `correct` turns false for each fault a cell can have; it says
nothing about the chip, and none of its numbers is a device number.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)

FLEET = {"config": {"table": {"subscriptions": 5000},
                    "rules": {"count": 6}},
         # one child a side: the suite's other workers need the cores
         "workload": {"warmup_publishes": 300, "rate": 200,
                      "publisher_children": 1, "subscriber_children": 1}}
CELLS = {
    "fleet-1m-rules.flood-qos1": FLEET,
    "fleet-1m-rules.paced-qos1": FLEET,
}


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    """The platform override and a peaks entry for this box's device
    kind; the worker's JAX cache configuration is put back afterwards."""
    import jax

    import run as harness

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({
        jax.devices()[0].device_kind: {"hbm_GBps": 1, "bf16_TFLOPs": 1}
    }))
    monkeypatch.setattr(harness, "PLATFORM", "cpu")
    monkeypatch.setattr(harness, "PEAKS_FILE", str(peaks))
    yield harness
    for k, v in saved.items():
        jax.config.update(k, v)


def last_line(capsys, also_window=False):
    out = capsys.readouterr()
    lines = [json.loads(ln) for ln in out.out.splitlines()
             if ln.startswith("{")]
    if also_window:
        window, = [ln for ln in lines if ln.get("phase") == "window"]
        return lines[-1], out.err, window
    return lines[-1], out.err


def run_cell(harness, cell, seconds="2", trace="0", fault=None,
             seed="3000000019"):
    return harness.main(
        ["--workload", cell, "--seed", seed, "--seconds", seconds,
         "--trace", trace],
        fault=fault, overrides=CELLS[cell],
    )


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_runs_to_a_well_formed_correct_line(cell, on_cpu, capsys):
    assert run_cell(on_cpu, cell) == 0
    res, err, window = last_line(capsys, also_window=True)
    if "paced" in cell:
        # the generator kept its schedule: a publish left when it was
        # due, and the offered rate is the cell's
        assert window["loadgen"]["late_ms_p99"] < 250
        assert res["attempted"] == 200 * 2
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    # no churn group, no churn key: the line is what it was
    assert "churn" not in res and "churn" not in window
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    # the device is named as JAX reports it: here a CPU, so nothing of
    # this line is a device number
    assert res["device"]["platform"] == "cpu"
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    for name, m in res["metrics"].items():
        assert m["unit"] == units[name] and m["value"] > 0
    # each number compared stands beside its limit, on stderr too
    for name, (value, limit) in res["compared"].items():
        assert value <= limit
        assert f"compared {name} {value} limit {limit}" in err


def test_traced_run_reports_the_per_layer_metrics(on_cpu, capsys):
    cell = "fleet-1m-rules.flood-qos1"
    assert run_cell(on_cpu, cell, seconds="3", trace="1") == 0
    res, _ = last_line(capsys)
    assert res["correct"] is True
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    per = {m["name"]: m for m in bench["per_layer"] if cell in m["workloads"]}
    assert set(res["metrics"]) <= set(per)
    # no TPU plane in a CPU trace: the trace metrics stay silent, they
    # do not read 0
    assert not any(per[n]["source"] == "device_trace"
                   for n in res["metrics"])
    assert {"window_msgs_mean", "match_us_per_msg", "decide_us_per_msg",
            "rules_us_per_msg", "deliver_us_per_msg",
            "loadgen_cpu_pct.flood"} <= set(res["metrics"])


@pytest.mark.parametrize("cell,fault,fails", [
    ("fleet-1m-rules.flood-qos1", "lost_match", "missing"),
    ("fleet-1m-rules.flood-qos1", "weak_ack", "missing"),
    ("fleet-1m-rules.flood-qos1", "host_match", "windows_not_dev"),
    ("fleet-1m-rules.paced-qos1", "weak_ack", "missing"),
    ("fleet-1m-rules.paced-qos1", "lost_match", "missing"),
    ("fleet-1m-rules.flood-qos1", "host_decide", "decide_host_windows"),
])
def test_a_broken_guarantee_reads_not_correct(cell, fault, fails, on_cpu,
                                              capsys, monkeypatch):
    import control

    # a delivery that a fault took away never comes: do not wait a
    # minute for it in a test
    monkeypatch.setattr(on_cpu, "DRAIN_S", 5.0)
    assert run_cell(on_cpu, cell, fault=control.FAULTS[fault],
                    seconds="3" if "paced" in cell else "2") == 0
    res, _ = last_line(capsys)
    assert res["correct"] is False
    over = [n for n, (v, lim) in res["compared"].items() if v > lim]
    assert any(fails in n for n in over), over


def test_exits_nonzero_without_a_tpu_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "fleet-1m-rules.flood-qos1", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "need platform 'tpu'" in out.stderr


def test_exits_nonzero_where_only_the_benchmark_is(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "fleet-1m-rules.flood-qos1", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert out.returncode != 0 and '"correct"' not in out.stdout
