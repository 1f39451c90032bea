"""BENCHMARK.json and the benchmark's data files agree with each other
and stay inside the contract's limits (checked here so that a later PR
that adds a cell or a metric as data finds its slip before the driver
does)."""

import ast
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
BENCH = os.path.join(REPO, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


sys.path.insert(0, BENCH)

import traffic  # noqa: E402


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


B = load(REPO, "BENCHMARK.json")
CELLS = [w["name"] for w in B["workloads"]]
E2E = {m["name"]: m for m in B["end_to_end"]}
PER = {m["name"]: m for m in B["per_layer"]}
METRIC_FILES = sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))
    if f.endswith(".json")
)


def reports(cell, metric):
    return cell in metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    assert len(json.dumps(B)) < 64 * 1024
    assert len(B["command"]) <= 32
    assert not any(w.startswith("/") or ".." in w for w in B["command"])
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(len(CELLS) // 2, 1)


@pytest.mark.parametrize("entry", [
    *B["configs"], *B["workloads"], *B["end_to_end"], *B["per_layer"],
], ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in entry.get("reduced", []):
        assert NAME.match(key)
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry:
            v = entry[key]
            assert 1 <= len(v) <= 200 and "\n" not in v and "\t" not in v
    if "bound" in entry:
        assert 0.01 <= entry["bound"] <= 0.25


@pytest.mark.parametrize("conf", B["configs"], ids=lambda c: c["name"])
def test_configuration_file(conf):
    assert conf["file"].startswith("benchmark/configs/")
    data = load(REPO, conf["file"])
    assert data["source"] == conf["source"]
    assert sorted(data["reduced"]) == sorted(conf["reduced"])
    assert len(conf["reduced"]) <= 16
    assert "guarantees" in data and data["engine"]["use_device"] is True
    assert any(w["config"] == conf["name"] for w in B["workloads"])


@pytest.mark.parametrize("cell", B["workloads"], ids=lambda w: w["name"])
def test_cell_file_and_what_it_reports(cell):
    assert cell["name"] == f'{cell["config"]}.{cell["traffic"]}'
    assert cell["config"] in {c["name"] for c in B["configs"]}
    assert cell["chips"] in (1, 4)
    work = load(BENCH, "workloads", cell["name"] + ".json")
    assert work["why"] == cell["why"]
    assert work["loop"] in ("flood", "paced")
    e2e = [n for n, m in E2E.items() if reports(cell["name"], m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(reports(cell["name"], m) for m in PER.values())


@pytest.mark.parametrize("name", METRIC_FILES)
def test_metric_file_and_its_entry(name):
    m = load(BENCH, "metrics", name + ".json")
    assert set(m) == {"reader", "args"}
    assert os.path.exists(os.path.join(BENCH, "readers", m["reader"] + ".py"))
    entry = E2E.get(name) or PER[name]
    for cell in entry.get("workloads", []):
        assert cell in CELLS
    if name in PER:
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        # every cell the metric is read in reports the metric it moves
        moved = E2E[entry["moves"]]
        for cell in entry["workloads"]:
            assert reports(cell, moved), (cell, entry["moves"])
    else:
        assert set(entry) - {"workloads"} == {"name", "unit", "better",
                                              "bound", "source"}
        assert entry["source"] in ("host_clock", "device_trace")


def test_every_declared_metric_has_a_file():
    assert sorted(list(E2E) + list(PER)) == METRIC_FILES


def test_layers_spelled_alike():
    layers = {m["layer"] for m in PER.values()}
    assert len({l.lower() for l in layers}) == len(layers)


def test_roofline_name_and_unit():
    for name, m in PER.items():
        if "roofline" in name:
            assert name.endswith("_roofline") and m["unit"] == "%"


def test_paths_hold_every_benchmark_file():
    for p in B["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
        for root, _dirs, files in os.walk(os.path.join(REPO, p)):
            if "__pycache__" in root:
                continue
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


DATA_FILES = sorted(
    os.path.join(d, f) for d in ("configs", "workloads")
    for f in os.listdir(os.path.join(BENCH, d)) if f.endswith(".json")
)
GENERATOR_FILES = sorted(
    f[:-3] for f in os.listdir(os.path.join(BENCH, "generators"))
    if f.endswith(".py")
)


@pytest.mark.parametrize("path", DATA_FILES)
def test_every_generator_a_data_file_names_resolves(path):
    data = load(BENCH, path)
    groups = {"table": "table", "live": "live", "topics": "pool",
              "churn": "churn"}
    named = [(kind, data[g]["generator"]) for g, kind in groups.items()
             if g in data]
    assert len(named) == (2 if path.startswith("configs")
                          else 1 + ("churn" in data))
    for kind, name in named:
        assert callable(traffic.generator(kind, name))


@pytest.mark.parametrize("name", GENERATOR_FILES)
def test_a_generator_file_keeps_the_contract_of_its_place(name):
    """Standard library and numpy only; not a built-in's name; has one of
    the four functions; and something names it: a configuration, a cell
    or a test (a generator nothing runs is dead weight in `paths`)."""
    assert NAME.match(name)
    assert not any(name in d for d in traffic.BUILT_IN.values())
    path = os.path.join(BENCH, "generators", name + ".py")
    tree = ast.parse(open(path).read())
    roots = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            roots |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, "a generator file stands alone"
            roots.add(node.module.split(".")[0])
    assert roots <= set(sys.stdlib_module_names) | {"numpy"}, roots
    defined = {n.name for n in tree.body if isinstance(n, ast.FunctionDef)}
    assert defined & {"table", "live", "pool", "churn"}
    quoted = re.compile(r"""["']%s["']""" % re.escape(name))
    users = [
        os.path.join(root, f)
        for top in (BENCH, os.path.join(REPO, "tests", "benchmark"))
        for root, _dirs, files in os.walk(top) for f in files
        if f.endswith((".json", ".py")) and "generators" not in root
        and quoted.search(open(os.path.join(root, f)).read())
    ]
    assert users, f"nothing names the generator {name!r}"
