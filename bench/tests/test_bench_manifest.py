"""BENCHMARK.json against the benchmark's contract: names, units, the
files each entry points at, and which cells report what."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"},
}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", CELLS)


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_only_allowed_keys_and_unique_names(section):
    entries = BENCH[section]
    assert entries
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        assert set(e) <= KEYS[section], e["name"]
        assert NAME.match(e["name"]), e["name"]


@pytest.mark.parametrize("section", ["end_to_end", "per_layer"])
def test_metric_units_sources_and_direction(section):
    for m in BENCH[section]:
        assert UNIT.match(m["unit"]), m["name"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        for c in m.get("workloads", []):
            assert c in CELLS, (m["name"], c)


def test_end_to_end_bounds_and_sources():
    assert "setup_s" in E2E
    assert E2E["setup_s"]["bound"] <= 0.25
    for m in E2E.values():
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
        assert m["source"] in ("host_clock", "device_trace")


def test_every_per_layer_metric_moves_an_end_to_end_metric_its_cells_report():
    for m in BENCH["per_layer"]:
        assert m["moves"] in E2E, m["name"]
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        for c in m.get("workloads", CELLS):
            assert reports(E2E[m["moves"]], c), (m["name"], c)
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_every_cell_reports_setup_another_end_to_end_and_a_per_layer():
    for c, w in CELLS.items():
        e2e = [m for m in E2E.values() if reports(m, c)]
        assert "setup_s" in [m["name"] for m in e2e]
        assert len(e2e) >= 2, c
        assert any(reports(m, c) for m in BENCH["per_layer"]), c


def test_cells_name_files_and_chips():
    configs = {c["name"] for c in BENCH["configs"]}
    pairs = set()
    for w in BENCH["workloads"]:
        assert w["config"] in configs
        assert NAME.match(w["traffic"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(BENCH["workloads"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


def test_every_configuration_has_a_cell_and_a_file_of_its_own():
    used = {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert c["name"] in used, c["name"]
        assert c["file"].startswith("bench/")
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert conf["source"] == c["source"]
        assert 1 <= len(c["source"]) <= 200
        # every cut named in the manifest is explained in the file
        assert set(c["reduced"]) == set(conf["reduced"]), c["name"]
        assert all(NAME.match(k) for k in c["reduced"])
