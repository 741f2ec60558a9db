"""Everything a cell names is found by name, and BENCHMARK.json keeps to
the form the benchmark's contract gives it."""

import json
import re

import pytest

from harness import BENCH, REPO, find_cell, load_json, load_module

BM = load_json(REPO / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"][1] == "bench/run.py" and BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51


def test_names_units_and_bounds():
    metrics = BM["end_to_end"] + BM["per_layer"]
    names = ([c["name"] for c in BM["configs"]] + CELLS
             + [m["name"] for m in metrics])
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BM["end_to_end"])
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert all(m["moves"] in e2e for m in BM["per_layer"])


def test_check_fits_the_time_limit_with_24_cells():
    runs = 2 + 14 * 24
    assert runs * (BM["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found(cell):
    w, conf_entry, conf, traffic, e2e, layer = find_cell(cell)
    assert conf["name"] == w["config"]
    assert (BENCH / "data" / f"{conf['generator']['module']}.py").exists()
    assert set(conf_entry["reduced"]) == set(conf["reduced"])
    for m in layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
        assert m["moves"] in {e["name"] for e in e2e}
    assert {"setup_s"} < {m["name"] for m in e2e}
    assert layer


@pytest.mark.parametrize("kernel", ["hop_adc", "hop_adc_fs"])
def test_kernel_costs_are_found(kernel):
    cost = load_module(BENCH / "costs" / f"{kernel}.py")
    assert cost.TRACE_NAMES and cost.bytes_per_call(1, 1, 2, 16) > 0


def test_peaks_table_names_its_source():
    peaks = load_json(BENCH / "peaks.json")
    assert "TPU v5e" in peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_file_is_small():
    assert len(json.dumps(BM)) < 64 * 1024
