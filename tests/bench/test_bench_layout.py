"""BENCHMARK.json resolves: every cell to its configuration, traffic and
metric files, with names and units of the allowed characters."""
import json
import os
import re

import pytest

from _bench import CELLS, ROOT, run

BENCH = run.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench", "tests/bench"]
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= BENCH["run_seconds"] <= 51


def test_every_name_and_unit_uses_allowed_characters():
    named = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"]
    names = [e["name"] for e in named]
    assert all(NAME.match(n) for n in names), names
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        kind_names = [e["name"] for e in BENCH[kind]]
        assert len(kind_names) == len(set(kind_names))
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_end_to_end_metrics_and_bounds():
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert names == {"edges_per_s", "peak_bytes_per_edge", "setup_s"}
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = run.load_cell(cell)
    w = c["workload"]
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    conf = {e["name"]: e for e in BENCH["configs"]}[w["config"]]
    assert conf["file"].startswith("bench/configs/")
    assert c["config"]["name"] == w["config"]
    assert c["config"]["reduced"] == conf["reduced"]
    assert c["config"]["layout"]["chips"] == w["chips"]
    assert c["traffic"]["name"] == w["traffic"]
    assert c["traffic"]["algorithm"] in ("pagerank", "wcc")
    assert c["traffic"]["check"]["limit"] is not None
    e2e = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c["per_layer"], "every cell reports a per-layer metric"
    for m in c["per_layer"]:
        assert m["moves"] in e2e, (cell, m["name"])
        assert callable(run.metric_reader(m["name"]))


def test_per_layer_metrics_name_a_layer_and_a_moved_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        assert set(m.get("workloads", [])) <= cells


def test_four_chip_cells_are_at_most_half():
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_run_seconds_fits_a_full_check_of_24_cells():
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_peaks_are_keyed_by_device_kind():
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        run.load_peaks("cpu")
