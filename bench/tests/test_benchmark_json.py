"""``BENCHMARK.json`` keeps to the benchmark's contract: names, units and
lines in the allowed characters, and a file for everything it names."""

import json
import re

import pytest

from bench import harness, reference
from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
OUTPUTS = {"monthly_mean": {"monthly_mean", "count"},
           "alod": {"alod", "n"},
           "moments": {"mean", "var", "count"}}


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 << 10
    assert 1 <= bench["run_seconds"] <= 51
    assert all(PATH.match(p) and ".." not in p for p in bench["paths"])
    assert all(line(w) and not w.startswith("/") for w in bench["command"])


def test_every_entry_has_exactly_its_keys(bench):
    for cfg in bench["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}, cfg
        assert line(cfg["why"]) and len(cfg["reduced"]) <= 16
    for cell in bench["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    e2e = {"name", "unit", "better", "bound", "source"}
    for metric in bench["end_to_end"]:
        assert set(metric) - {"workloads"} == e2e, metric
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    per_layer = {"name", "unit", "better", "source", "layer", "moves"}
    for metric in bench["per_layer"]:
        assert set(metric) - {"workloads"} == per_layer, metric
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")


def test_names_units_and_lines(bench):
    names = []
    for cfg in bench["configs"]:
        names.append(cfg["name"])
        assert line(cfg["source"])
        assert all(NAME.match(k) for k in cfg["reduced"])
    for cell in bench["workloads"]:
        names.append(cell["name"])
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
        assert line(cell["why"]) and cell["chips"] in (1, 4)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        names.append(metric["name"])
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
    for metric in bench["per_layer"]:
        assert line(metric["layer"])
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [m["name"] for m in bench[group]]
        assert len(got) == len(set(got)), group


def test_every_cell_metric_and_config_has_its_file(bench):
    cells = {c["name"] for c in bench["workloads"]}
    used = {c["config"] for c in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert set(metric.get("workloads", cells)) <= cells
    for metric in bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").exists()
    for name in cells:
        cell = harness.load_cell(ROOT, name)
        stat = cell.traffic["query"]["statistic"]
        assert set(cell.checks["limits"]) == OUTPUTS[stat]
        assert all(cell.checks["limits"][k] == 0 for k in reference.EXACT
                   if k in cell.checks["limits"])
        assert cell.end_to_end.keys() >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_every_per_layer_metric_moves_an_end_to_end_metric_of_its_cells(
        bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [c["name"] for c in bench["workloads"]]
    for metric in bench["per_layer"]:
        moved = e2e[metric["moves"]]
        for cell in metric.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (metric, cell)
