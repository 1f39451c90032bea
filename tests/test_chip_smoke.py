"""`chip_smoke.py` rehearsed on the CPU at a tiny size.

The script itself has no CPU branch: the override of its platform check
and the shrunken sizes live HERE.  Nothing is interpreted — the kernels
are plain XLA — so this finds wrong paths, arguments and control flow,
and says nothing about the chip.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.fixture
def jax_cache_config():
    """The script turns the persistent compile cache on for its
    process; put this worker's JAX configuration back afterwards."""
    import jax

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_default_phases_rehearsed_on_cpu(monkeypatch, capsys,
                                         jax_cache_config):
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "WINDOW_TOPICS", 32)
    monkeypatch.setattr(chip_smoke, "N_WINDOWS", 3)
    monkeypatch.setattr(chip_smoke, "N_RULES", 6)
    monkeypatch.setattr(chip_smoke, "N_PUBLISH", 200)
    monkeypatch.setattr(chip_smoke, "N_PUBLISHERS", 2)
    # the client child inherits the CPU pin and must not need it
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    # the smallest table the broker still builds, folds and warms by
    # itself (the engine's rebuild and fold thresholds)
    assert chip_smoke.main(["--subs", "5000"]) == 0
    lines = [
        json.loads(ln) for ln in capsys.readouterr().out.splitlines()
        if ln.startswith("{")
    ]
    by_phase = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert all(
        ln["platform"] == "cpu" and ln["kind"]
        for ln in lines if "phase" in ln
    )
    assert set(by_phase["preflight"]["native"].values()) == {"native"}
    # the compile cache: where the environment says, else one absolute
    # path inside the checkout, whatever the cwd
    cache = by_phase["preflight"]["compile_cache"]
    assert cache == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(REPO, "data", "xla_cache"),
    )
    eng = by_phase["engine_windows"]
    assert eng["all_windows_dev"] and eng["equal_to_referee"]
    assert eng["windows"] == 3 and eng["steady_compile_requests"] == 0
    assert eng["breaker"]["device_errors"] == 0
    load = by_phase["served_load"]
    assert load["base"] == 5000 + 6 and load["folded"] >= 1024
    served = by_phase["served_traffic"]
    assert "rules_eval_batch" not in served["served_compile_s_by_fn"]
    assert served["pubacks"] == served["publishes"] == 200
    assert served["window_paths"] == {"dev": served["windows"]}
    assert served["decide_dev_windows"] > 0
    assert served["rules_dev_windows"] > 0
    assert served["deliveries"] > 0 and served["rule_firings"] > 0
    # the contract's last line: the device as JAX reports it, no more
    last = lines[-1]
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["count"], int)


def test_exits_nonzero_without_a_tpu():
    """No override, a CPU-only box: non-zero, and no result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "need platform 'tpu'" in out.stderr
