"""The harness is driven by data: every cell of ``BENCHMARK.json`` resolves
to its files by name, and a cell, a configuration and a per-layer metric
added as files are found without editing a file that is there."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

MANIFEST = harness.load_manifest(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = harness.Cell(name, ROOT)
    assert cell.chips == 1
    assert hasattr(cell.driver, "Job")
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for entry, _, reader in cell.per_layer:
        assert callable(reader.read)
        assert entry["moves"] in reported
    assert any("mfu" in e["name"].split(".") for e, _, _ in cell.per_layer)
    assert set(cell.workload["limits"]), "a cell states its limits"


@pytest.mark.parametrize("entry", METRICS, ids=[m["name"] for m in METRICS])
def test_names_and_units_keep_to_the_allowed_characters(entry):
    assert harness.NAME.match(entry["name"])
    assert harness.UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in (
        "device_trace", "program_span", "program_counter", "host_clock")
    if entry in MANIFEST["end_to_end"]:
        assert 0.01 <= entry["bound"] <= 0.1
        assert entry["source"] in ("host_clock", "device_trace")
    else:
        assert set(entry) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    if "roofline" in entry["name"] or "mfu" in entry["name"]:
        assert entry["unit"] == "%"


def test_manifest_keeps_to_the_contract():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in MANIFEST["workloads"]}
    for config in MANIFEST["configs"]:
        assert config["name"] in used
        assert harness.NAME.match(config["name"])
        assert config["file"].startswith("chipbench/")
        assert os.path.isfile(os.path.join(ROOT, config["file"]))
        assert len(config["why"]) <= 200 and len(config["source"]) <= 200
    for w in MANIFEST["workloads"]:
        assert harness.NAME.match(w["name"]) and harness.NAME.match(w["traffic"])
        assert len(w["why"]) <= 200
    for path in MANIFEST["paths"]:
        for directory, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in directory:
                continue
            for name in files:
                assert all(c.isalnum() or c in "_.-" for c in name), name


def test_a_cell_a_config_and_a_metric_added_as_files_are_found(tmp_path):
    """A later PR appends entries to BENCHMARK.json and adds files; it edits
    nothing under chipbench/ that is there."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {
        str(p.relative_to(root)): p.read_bytes()
        for p in (root / "chipbench").rglob("*") if p.is_file()
    }
    manifest = json.loads(json.dumps(MANIFEST))
    bench = root / "chipbench"
    (bench / "configs" / "new_model.json").write_text(json.dumps(
        {"name": "new_model", "batchSize": 8}))
    (bench / "traffic" / "new_mix.json").write_text(json.dumps(
        {"kind": "cached_frame", "rows": 8, "distinct": 8}))
    (bench / "workloads" / "new-cell.json").write_text(json.dumps(
        {"driver": "featurize", "limits": {"x": 1}}))
    (bench / "metrics" / "new_share.json").write_text(json.dumps(
        {"reader": "new_reader", "args": {"k": 2}}))
    (bench / "readers" / "new_reader.py").write_text(
        "def read(facts, args):\n    return facts.get('n', 0) * args['k'] or None\n")
    manifest["configs"].append({
        "name": "new_model", "source": "a paper",
        "file": "chipbench/configs/new_model.json", "reduced": [], "why": "w"})
    manifest["workloads"].append({
        "name": "new-cell", "config": "new_model", "traffic": "new_mix",
        "chips": 1, "why": "w"})
    for m in manifest["end_to_end"]:
        if m["name"] == "images_per_s":
            m["workloads"] = m["workloads"] + ["new-cell"]
    manifest["per_layer"].append({
        "name": "new_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "images_per_s", "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    import importlib

    import chipbench.readers as readers

    readers.__path__.append(str(bench / "readers"))
    try:
        importlib.invalidate_caches()
        cell = harness.Cell("new-cell", str(root))
    finally:
        readers.__path__.remove(str(bench / "readers"))
    assert cell.config["batchSize"] == 8 and cell.traffic["rows"] == 8
    assert [e["name"] for e, _, _ in cell.per_layer] == ["new_share"]
    (entry, args, reader), = cell.per_layer
    assert reader.read({"n": 3}, args) == 6
    assert reader.read({}, args) is None  # nothing to read: left out
    after = {
        str(p.relative_to(root)): p.read_bytes()
        for p in (root / "chipbench").rglob("*")
        if p.is_file() and "__pycache__" not in str(p)
    }
    assert {k: after[k] for k in before} == before


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
    assert harness.peaks_for("TPU v5 lite")["flops_per_s"]["bfloat16"] == 197e12


@pytest.mark.parametrize("reader, facts, args, want", [
    ("share_of_wall", {"read_s": 2.0, "wall_s": 8.0}, {"seconds_key": "read_s"}, 25.0),
    ("share_of_wall", {"wall_s": 8.0}, {"seconds_key": "read_s"}, None),
    ("timer_per_unit", {"timer_s": {"a": 1.0, "b": 0.5}, "images": 100},
     {"timers": ["a", "b"], "per": "images"}, 15.0),
    ("timer_per_unit", {"timer_s": {"a": 1.0}, "images": 100},
     {"timers": ["a", "b"], "per": "images"}, None),
    ("compiles_in_window", {"compiles": {"backend_compiles": 2}}, {}, 2.0),
    ("compiles_in_window", {}, {}, None),
    ("mfu", {"peaks": {"flops_per_s": {"bfloat16": 100.0}}, "needed_flops": 50.0,
             "wall_s": 2.0}, {"peak": "bfloat16"}, 25.0),
    ("mfu", {"peaks": None, "needed_flops": 50.0, "wall_s": 2.0},
     {"peak": "bfloat16"}, None),
    ("roofline", {"peaks": {"flops_per_s": {"bfloat16": 100.0}, "hbm_bytes_per_s": 10.0},
                  "dispatch": {"flops": 50.0, "bytes": 1.0}, "program": "jit_f",
                  "trace": {"programs": {"jit_f": {"whole_runs": 2, "whole_seconds": 4.0}}}},
     {"peak": "bfloat16"}, 25.0),
    ("roofline", {"peaks": {"flops_per_s": {"bfloat16": 100.0}, "hbm_bytes_per_s": 10.0},
                  "dispatch": {"flops": 50.0, "bytes": 1.0}, "program": "jit_f",
                  "trace": {"programs": {"other": {"whole_runs": 2, "whole_seconds": 4.0}}}},
     {"peak": "bfloat16"}, None),
    ("device_idle_share", {"trace": {"idle_share": 0.4}}, {}, 40.0),
    ("device_idle_share", {}, {}, None),
    ("fact_per_unit", {"host_stall_ms": 30.0, "steps": 10},
     {"key": "host_stall_ms", "per": "steps"}, 3.0),
    ("fact_per_unit", {"steps": 10}, {"key": "host_stall_ms", "per": "steps"}, None),
])
def test_reader(reader, facts, args, want):
    import importlib

    got = importlib.import_module("chipbench.readers." + reader).read(facts, args)
    assert got == want if want is None else got == pytest.approx(want)
