"""Every cell of BENCHMARK.json resolves to files that exist, and the
file keeps to the contract's names and limits."""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_keys(benchmark_file):
    assert set(benchmark_file) == {"command", "paths", "run_seconds",
                                   "configs", "workloads", "end_to_end",
                                   "per_layer"}
    assert benchmark_file["paths"] == ["benchmarks"]
    assert 1 <= benchmark_file["run_seconds"] <= 51


def test_names_units_and_bounds(benchmark_file):
    metrics = benchmark_file["end_to_end"] + benchmark_file["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in benchmark_file["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in benchmark_file["end_to_end"]}
    assert "setup_s" in e2e
    for m in benchmark_file["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
    for w in benchmark_file["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for c in benchmark_file["configs"]:
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_cell_resolves(benchmark_file, kind):
    cells = {w["name"] for w in benchmark_file["workloads"]}
    configs = {c["name"]: c for c in benchmark_file["configs"]}
    for w in benchmark_file["workloads"]:
        cfg_file = os.path.join(ROOT, configs[w["config"]]["file"])
        assert os.path.isfile(cfg_file)
        with open(cfg_file) as f:
            cfg = json.load(f)
        for key in configs[w["config"]]["reduced"]:
            assert cfg[key] != cfg["source_scale"][key]
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    reported = {name: 0 for name in cells}
    for m in benchmark_file[kind]:
        for ext in (".json", ".py"):
            assert os.path.isfile(os.path.join(BENCH, "metrics",
                                               m["name"] + ext)), m["name"]
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            data = json.load(f)
        assert {k: data[k] for k in m if k != "bound"} == \
            {k: v for k, v in m.items() if k != "bound"}
        for name in m.get("workloads", cells):
            assert name in cells
            reported[name] += 1
    # every cell reports setup_s and one more end-to-end metric, and at
    # least one per-layer metric
    assert all(n >= (2 if kind == "end_to_end" else 1)
               for n in reported.values())


def test_a_metric_that_moves_something_is_read_where_it_is_reported(
        benchmark_file):
    e2e = {m["name"]: m for m in benchmark_file["end_to_end"]}
    cells = [w["name"] for w in benchmark_file["workloads"]]
    for m in benchmark_file["per_layer"]:
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", moved)) <= set(moved), m["name"]
