"""BENCHMARK.json against the contract's mechanical rules, and the files it
names."""

import json
import re

import pytest

from benchmark import manifest

MF = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_limits():
    assert set(MF) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(MF["run_seconds"], int) and 1 <= MF["run_seconds"] <= 51
    assert 1 <= len(MF["paths"]) <= 16 and len(MF["command"]) <= 32
    assert len(json.dumps(MF)) < 64 * 1024
    full = 2 + 14 * 24
    assert full * (MF["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("entry", MF["configs"] + MF["workloads"] + MF["end_to_end"] + MF["per_layer"],
                         ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]


def test_end_to_end_metrics_have_bounds_and_setup_s_is_one():
    names = [m["name"] for m in MF["end_to_end"]]
    assert "setup_s" in names and len(set(names)) == len(names)
    for m in MF["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")


def test_per_layer_metrics_move_an_end_to_end_metric_and_have_a_reader():
    e2e = {m["name"] for m in MF["end_to_end"]}
    cells = {w["name"] for w in MF["workloads"]}
    for m in MF["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
        assert callable(manifest.load_module("readers", m["name"]).read)
    rooflines = [m for m in MF["per_layer"] if m["name"].endswith("_roofline")]
    for r in rooflines:  # a whole-step mfu stands beside every kernel roofline
        assert any("mfu" in m["name"] and m["moves"] == r["moves"] for m in MF["per_layer"])


@pytest.mark.parametrize("cell", [w["name"] for w in MF["workloads"]])
def test_every_cell_resolves_and_reports_enough(cell):
    resolved = manifest.resolve_cell(MF, cell)
    assert resolved["chips"] in (1, 4)
    config = resolved["config"]
    assert (manifest.HERE / "drivers" / f"{resolved['traffic']['driver']}.py").exists()
    assert (manifest.HERE / "reference" / f"{config['reference']}.py").exists()
    if resolved["traffic"]["driver"] == "fit":   # a later driver brings its own numbers
        assert set(config["check_limits"]) == {
            f"{side}_{n}" for side in ("user", "item")
            for n in ("rows_worst", "rows_p99", "rows_median", "all_rows_worst")}
        assert config["check_min_stars"] >= 1
    e2e = [m["name"] for m in manifest.metrics_for(MF, cell, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert len(manifest.metrics_for(MF, cell, True)) >= 1


def test_config_files_are_under_paths_and_reduce_no_width():
    files = [c["file"] for c in MF["configs"]]
    assert len(set(files)) == len(files)
    for c in MF["configs"]:
        assert any(c["file"].startswith(p + "/") for p in MF["paths"])
        assert not any(k.endswith(("_dim", "_rank")) or k == "rank" for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in MF["workloads"])


def test_a_metric_without_a_workloads_key_belongs_to_every_cell_that_reports_what_it_moves():
    mf = {"end_to_end": [{"name": "a", "unit": "s"}, {"name": "b", "unit": "s", "workloads": ["x"]}],
          "per_layer": [{"name": "p", "moves": "b"}, {"name": "q", "moves": "a", "workloads": ["y"]}]}
    assert [m["name"] for m in manifest.metrics_for(mf, "x", False)] == ["a", "b"]
    assert [m["name"] for m in manifest.metrics_for(mf, "y", False)] == ["a"]
    assert [m["name"] for m in manifest.metrics_for(mf, "x", True)] == ["p"]
    assert [m["name"] for m in manifest.metrics_for(mf, "y", True)] == ["q"]


def test_an_unknown_cell_or_reader_is_an_error():
    with pytest.raises(manifest.ManifestError):
        manifest.resolve_cell(MF, "no-such.cell")
    with pytest.raises(manifest.ManifestError):
        manifest.load_module("readers", "no_such_metric")
