"""BENCHMARK.json against the benchmark's contract, and every cell's files
found by name. Reads files only: nothing here touches a device."""
import json
import re
from pathlib import Path

import pytest

from bench import harness

ROOT = harness.ROOT
MANIFEST = harness.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WORKLOADS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51 and isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and not p.startswith("/")
        assert ".." not in p.split("/") and (ROOT / p).is_dir()
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(TEXT.match(w) for w in cmd)
    files = [w for w in cmd if "/" in w]
    assert files and all(any(f.startswith(p + "/") for p in MANIFEST["paths"]) for f in files)
    assert len(json.dumps(MANIFEST)) <= 64 * 1024


def test_names_units_and_entry_keys():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert TEXT.match(c["source"]) and TEXT.match(c["why"])
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4) and TEXT.match(w["why"])
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert all(n in WORKLOADS for n in m.get("workloads", []))
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert TEXT.match(m["layer"])


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_files_found_by_name(workload):
    cell = harness.find_cell(workload)
    conf = {c["name"]: c for c in MANIFEST["configs"]}[
        {w["name"]: w for w in MANIFEST["workloads"]}[workload]["config"]]
    assert any(conf["file"].startswith(p + "/") for p in MANIFEST["paths"])
    # every key changed from the source: cuts of scale, and departures the
    # program forces
    assert set(conf["reduced"]) == set(cell.config["reduced"]) | set(
        cell.config.get("departures", {}))
    assert (harness.BENCH / "drivers" / f"{cell.traffic['driver']}.py").is_file()
    e2e = {m["name"] for m in harness.cell_metrics(cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = harness.cell_metrics(cell, "per_layer")
    assert per_layer
    for m in per_layer:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e


def test_each_per_layer_metric_moves_one_metric_of_all_its_cells():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        for w in m.get("workloads", WORKLOADS):
            assert "workloads" not in moved or w in moved["workloads"], (m["name"], w)


def test_every_config_is_used_and_files_are_distinct():
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    assert all(Path(ROOT / f).is_file() for f in files)


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(WORKLOADS) // 2)
