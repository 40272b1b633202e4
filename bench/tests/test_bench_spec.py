"""BENCHMARK.json keeps to the benchmark's contract, and every part it
names exists as a file the harness can find."""
from __future__ import annotations

import json
import re

import pytest

from bench import harness
from bench.tests.conftest import REPO

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
CELLS = [w["name"] for w in SPEC["workloads"]]


def _names():
    yield from (m["name"] for m in METRICS)
    for w in SPEC["workloads"]:
        yield from (w["name"], w["config"], w["traffic"])
    for c in SPEC["configs"]:
        yield c["name"]
        yield from c["reduced"]


@pytest.mark.parametrize("name", sorted(set(_names())))
def test_names_use_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_units_and_direction(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")


def _reports(metric, cell):
    return cell in metric.get("workloads", CELLS)


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_moves_names_an_end_to_end_metric_of_each_of_its_cells(metric):
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert metric["moves"] in e2e
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        assert _reports(e2e[metric["moves"]], cell), (metric["name"], cell)


def test_every_configuration_has_a_cell_and_names_are_unique():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for group in (SPEC["configs"], SPEC["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e = [m["name"] for m in SPEC["end_to_end"] if _reports(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(_reports(m, cell) for m in SPEC["per_layer"])


def test_bounds_and_run_length():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells must fit its time
    cells = 24
    assert (2 + 14 * cells) * (SPEC["run_seconds"] + 60) \
        + cells * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_finds_its_files(cell):
    found = harness.find_cell(REPO, cell)
    assert found.cfg["name"] == found.workload["config"]
    for m in found.end_to_end + found.per_layer:
        assert callable(harness.metric_reader(REPO, m["name"]))
    assert found.cfg["dtype"] == "float32"
    assert found.cfg["matmul_precision"] == "highest"
    assert 0 < found.cfg["limits"]["logit_rel_err"] < 1


def test_chip_peaks_name_their_source_and_unknown_devices_fail():
    peaks = harness.peaks_for(REPO, "TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["source"]
    with pytest.raises(KeyError):
        harness.peaks_for(REPO, "no such chip")


def test_command_and_paths():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
