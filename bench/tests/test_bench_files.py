"""BENCHMARK.json against the benchmark's contract, and every file it names
found by name. The candidates of ``bench/candidates/`` are held to the same
rules, so that a later change can copy their entries in."""
import os
import re

import pytest

from bench_testlib import BENCH, ROOT

import harness

BM = harness.benchmark()
ALL = harness.benchmark(candidates=True)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in ALL["workloads"]]
PER_LAYER = [m["name"] for m in ALL["per_layer"]]


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_size():
    assert set(BM) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    assert isinstance(BM["run_seconds"], int) and 1 <= BM["run_seconds"] <= 51


def test_entry_keys():
    for c in ALL["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in ALL["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in ALL["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    for m in ALL["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}


def test_names_units_and_lines():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for e in ALL[k]]
    assert len(names) == len(set(names))
    for n in names + [w["config"] for w in ALL["workloads"]] + [w["traffic"] for w in ALL["workloads"]]:
        assert NAME.match(n), n
    for m in ALL["end_to_end"] + ALL["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for e in ALL["configs"] + ALL["workloads"]:
        assert _line(e["why"]), e["name"]
    for c in ALL["configs"]:
        assert _line(c["source"]) and len(c["reduced"]) <= 16
    for m in ALL["per_layer"]:
        assert _line(m["layer"])


def test_bounds_and_sources():
    for m in ALL["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert harness.entry(ALL["end_to_end"], "setup_s", "metric")["bound"] <= 0.25
    for m in ALL["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_by_name(cell):
    w = harness.entry(ALL["workloads"], cell, "workload")
    assert w["chips"] in (1, 4)
    config = harness.data("configs", w["config"])
    traffic = harness.data("traffic", w["traffic"])
    loop = harness.module("traffic", traffic["kind"])
    assert hasattr(loop, "Loop") and hasattr(loop, "control_system")
    ref = harness.module("reference", config["reference"])
    assert callable(ref.reference) and callable(ref.control)
    assert config["chips"] == w["chips"]
    assert {"source", "assumed", "reduced"} <= set(config) and len(config["source"]) <= 200


def test_config_files_match_entries():
    used = {w["config"] for w in ALL["workloads"]}
    files = set()
    for c in ALL["configs"]:
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert c["file"] not in files
        files.add(c["file"])
        assert harness.read_json(os.path.join(ROOT, c["file"]))["reduced"] == c["reduced"]


@pytest.mark.parametrize("metric", PER_LAYER)
def test_metric_reader_loads_and_moves(metric):
    m = harness.entry(ALL["per_layer"], metric, "metric")
    assert callable(harness.module("metrics", metric).read)
    e2e = {e["name"] for e in ALL["end_to_end"]}
    assert m["moves"] in e2e
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert m["moves"] in {e["name"] for e in harness.end_to_end(ALL, cell)}, (metric, cell)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough(cell):
    e2e = {m["name"] for m in harness.end_to_end(ALL, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.per_layer(ALL, cell)


def test_candidates_are_run_but_not_timed():
    timed = {w["name"] for w in BM["workloads"]}
    assert "topk.decode.steps" in CELLS and "topk.decode.steps" not in timed
    assert not {m["name"] for m in BM["end_to_end"]} & {"topk_p50_ms", "topk_p99_ms"}


def test_chips_and_budget():
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(1, len(BM["workloads"]) // 2)
    rs = BM["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_peaks_table():
    v5e = harness.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("cpu")


def test_paths_hold_only_the_benchmark():
    for dirpath, _, files in os.walk(BENCH):
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
