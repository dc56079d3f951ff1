"""BENCHMARK.json against the files the harness looks for."""
import json
import os
import re

import pytest

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_finds_its_config_traffic_job_family_and_reference(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for cell in manifest["workloads"]:
        entry = configs[cell["config"]]
        cfg_path = os.path.join(ROOT, entry["file"])
        assert os.path.isfile(cfg_path), cfg_path
        with open(cfg_path) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
            traffic = json.load(f)
        for kind, name in (("jobs", traffic["job"]),
                           ("families", cfg["family"]),
                           ("reference", cfg["family"])):
            assert os.path.isfile(os.path.join(BENCH, kind, name + ".py")), \
                (cell["name"], kind, name)
    used = {c["config"] for c in manifest["workloads"]}
    assert used == set(configs), "a configuration no cell uses"


LIMIT_KEYS = {"loss_gap_first", "loss_gap_later",
              "first_grad_norm_gap_median", "first_grad_norm_gap_worst",
              "delta_norm_gap_median", "delta_norm_gap_worst"}


def test_every_cell_has_limits_of_its_own_set_from_its_own_readings(manifest):
    for cell in manifest["workloads"]:
        with open(os.path.join(BENCH, "limits", cell["name"] + ".json")) as f:
            doc = json.load(f)
        assert doc["cell"] == cell["name"]
        assert run.cell_limits(cell) == doc["limits"]
        assert set(doc["limits"]) == LIMIT_KEYS == set(doc["readings"])
        assert len(doc["program_seeds"]) >= 12 and len(doc["control_seeds"]) >= 3
        failed_by_the_control = []
        for key, limit in doc["limits"].items():
            seen = doc["readings"][key]
            # above what sound runs read, with room: about three times
            assert 1.5 * seen["program_largest"] <= limit \
                <= 3.5 * seen["program_largest"], (cell["name"], key)
            if limit * 1.4 <= seen["control_smallest"]:
                failed_by_the_control.append(key)
        # a lower precision has to fail one of a cell's numbers, with room
        assert any(k.startswith("first_grad_norm_gap")
                   for k in failed_by_the_control), cell["name"]


def test_a_cell_without_a_limits_file_is_refused():
    with pytest.raises(SystemExit, match="limits"):
        run.cell_limits({"name": "no.such_cell"})


def test_names_units_and_sources_use_only_the_allowed_characters(manifest):
    names = []
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for c in manifest["workloads"]:
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert len(c["why"]) <= 200 and "\n" not in c["why"]
        names.append(c["name"])
    assert len(names) == len(set(names))
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]


def test_at_most_one_cell_asks_for_four_chips(manifest):
    chips = [c["chips"] for c in manifest["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= 1


def test_every_layer_metric_has_a_reader_that_agrees_and_moves_what_its_cells_report(manifest):
    cells = [c["name"] for c in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        reader = run.load_module("layer_metrics", m["name"])
        assert callable(reader.read)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
            (m["layer"], m["unit"], m["moves"]), m["name"]
        assert m["moves"] in e2e, m["name"]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]], (m["name"], cell)
    on_disk = {f[:-3] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
               if f.endswith(".py")}
    assert on_disk == {m["name"] for m in manifest["per_layer"]}


def test_every_cell_reports_setup_one_more_end_to_end_and_a_layer_metric(manifest):
    for cell in (c["name"] for c in manifest["workloads"]):
        e2e = [m["name"] for m in manifest["end_to_end"]
               if cell in m.get("workloads", [cell])]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert any(cell in m.get("workloads", [cell])
                   for m in manifest["per_layer"]), cell


def test_the_command_and_paths_stay_inside_the_benchmark(manifest):
    assert manifest["paths"] == ["benchmark"]
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= manifest["run_seconds"] <= 51
    # 2 + 14 runs a cell, 24 cells, must fit the check's 43200 s
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
