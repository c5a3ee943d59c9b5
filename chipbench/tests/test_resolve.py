"""Every cell, configuration, traffic mix and metric of BENCHMARK.json
resolves to its file by name, and the file keeps the benchmark's rules."""

from __future__ import annotations

import json
import re

import pytest

from chipbench import harness

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[0-9A-Za-z_][0-9A-Za-z_.-]{0,63}$")
UNIT = re.compile(r"^[0-9A-Za-z_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    cells = 24
    runs = 2 + 14 * cells
    assert (runs * (BENCH["run_seconds"] + 60) + cells * 2 * 90 + 1200
            <= 43200)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves(workload):
    cell = harness.resolve(workload)
    wl = next(w for w in BENCH["workloads"] if w["name"] == workload)
    assert cell.cfg["name"] == wl["config"]
    assert cell.cfg["chips"] == cell.chips == wl["chips"]
    assert hasattr(cell.loop, "window") and hasattr(cell.loop, "setup")
    assert hasattr(cell.rhs, "make")
    assert hasattr(cell.operator, "reference_matvec")
    assert NAME.match(wl["name"]) and len(wl["why"]) <= 200
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(entry):
    cfg = json.loads((harness.ROOT / entry["file"]).read_text())
    assert entry["file"].startswith("chipbench/configs/")
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert all(NAME.match(k) for k in entry["reduced"])
    assert all(0 < v < 1 for v in cfg["limits"]["rel_residual"].values())
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_resolves(metric):
    mod = harness.plugin("metrics", metric["name"])
    assert callable(mod.read)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    for w in metric.get("workloads", CELLS):
        assert w in CELLS


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_moves_a_reported_metric(metric):
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    moved = e2e[metric["moves"]]
    for w in metric["workloads"]:
        assert w in moved.get("workloads", CELLS)
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}


def test_end_to_end_bounds():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_four_chip_cells_at_most_half():
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 2)


def test_unknown_names_are_refused():
    with pytest.raises(harness.Refused):
        harness.resolve("no_such_cell")
    with pytest.raises(harness.Refused):
        harness.plugin("metrics", "no_such_metric")
