"""``BENCHMARK.json`` and the files it names agree: every metric has a
reader that declares its unit, direction, source, layer and the one
end-to-end metric it moves, in a cell that reports it; names and units
use only the characters allowed."""

import json
import pathlib
import re

import pytest

import benchtree  # noqa: F401  (puts the repository on sys.path)
from chipbench.bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = [w["name"] for w in BENCH["workloads"]]


def _cells(metric):
    return metric.get("workloads", CELLS)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_file_declares_what_benchmark_says(metric):
    mod = harness.load_metric(metric["name"], ROOT)
    assert (mod.UNIT, mod.BETTER, mod.SOURCE) == (
        metric["unit"], metric["better"], metric["source"])
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert callable(mod.read)
    if metric in BENCH["per_layer"]:
        assert (mod.LAYER, mod.MOVES) == (metric["layer"], metric["moves"])
        assert metric["moves"] in E2E
        for cell in _cells(metric):
            assert cell in _cells(E2E[metric["moves"]]), (cell, metric)
    else:
        assert metric["source"] in ("host_clock", "device_trace")


def test_one_layer_name_per_layer():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in x and len(x) <= 200 for x in layers)
    assert len({x.lower() for x in layers}) == len(layers)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough(cell):
    e2e = [m for m in BENCH["end_to_end"] if cell in _cells(m)]
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert any(cell in _cells(m) for m in BENCH["per_layer"])
    c = harness.load_cell(cell, ROOT)
    assert c.config["family"] and c.serve["limits"]


def test_names_and_files():
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200
    for conf in BENCH["configs"]:
        assert conf["file"].startswith("chipbench/")
        assert (ROOT / conf["file"]).is_file()
        assert all(NAME.match(k) for k in conf["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
