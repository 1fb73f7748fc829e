"""BENCHMARK.json against the benchmark's contract, as far as a test
without a chip can hold it, and every cell's files resolved by name."""

import os
import re

import pytest
from perf_testlib import (
    NEW_LAYER,
    ROOT,
    TINY_CELL,
    TINY_FAMILY_CELL,
    manifest_with_tiny_cell,
    repo_manifest,
)

from perf import manifest as manifest_lib

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFEST = repo_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= MANIFEST["run_seconds"] <= 51
    cells = len(MANIFEST["workloads"])
    assert (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= cells <= 24 and 1 <= len(MANIFEST["configs"]) <= 24
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, cells // 4)
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])


def test_command_stays_inside_paths():
    assert MANIFEST["command"][:1] == ["python3"]
    for word in MANIFEST["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in MANIFEST["paths"])
        assert os.path.exists(os.path.join(ROOT, word))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_is_well_formed(metric):
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in MANIFEST["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        assert set(metric) <= allowed | {"bound"}
    else:
        assert set(metric) <= allowed | {"layer", "moves"}
        assert "\n" not in metric["layer"] and 1 <= len(metric["layer"]) <= 200
    for cell in metric.get("workloads", []):
        assert cell in CELLS


def test_names_are_unique_and_mfu_is_not_end_to_end():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))
    assert not any("mfu" in m["name"] for m in MANIFEST["end_to_end"])
    assert "setup_s" in [m["name"] for m in MANIFEST["end_to_end"]]
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_moves_a_metric_its_cells_report(metric):
    moved = next(m for m in MANIFEST["end_to_end"] if m["name"] == metric["moves"])
    for cell in metric.get("workloads", CELLS):
        assert cell in moved.get("workloads", CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve(name):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == name)
    assert NAME.match(name) and NAME.match(entry["traffic"])
    assert 1 <= len(entry["why"]) <= 200
    cell = manifest_lib.Cell(MANIFEST, name)
    assert cell.config["name"] == entry["config"]
    assert cell.traffic["name"] == entry["traffic"]
    assert cell.config["work"]["rate_metric"] in [
        m["name"] for m in cell.metrics("end_to_end")
    ]
    reported = {m["name"] for m in cell.metrics("end_to_end")}
    assert "setup_s" in reported and len(reported) >= 2
    layer = cell.metrics("per_layer")
    assert layer
    for metric in layer:
        assert callable(cell.reader(metric["name"]))
    # the record kind, the FLOP arithmetic and the driver resolve by name
    kind = cell.record_kind()
    assert cell.config["work"]["unit"] in kind.units(cell.traffic["records"])
    assert cell.flops_per_record()["train"] > 0
    assert callable(cell.driver().prepare) and callable(cell.driver().run)


@pytest.mark.parametrize(
    "config", MANIFEST["configs"], ids=lambda c: c["name"]
)
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(config["file"].startswith(p + "/") for p in MANIFEST["paths"])
    assert config["name"] in [w["config"] for w in MANIFEST["workloads"]]
    loaded = manifest_lib.load_json(os.path.join(ROOT, config["file"]))
    assert loaded["source"] == config["source"]
    assert loaded["reduced"] == config["reduced"]


def test_a_cell_is_added_by_files_and_entries_alone():
    extended = manifest_with_tiny_cell()
    cell = manifest_lib.Cell(extended, TINY_CELL)
    assert cell.traffic["name"] == "tiny"
    assert cell.config["run"]["model_params"]["embed_dim"] == 64
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert names == ["readings_per_window.tiny", "units_per_reading.tiny"]
    read = cell.reader("readings_per_window.tiny")
    assert read({"untraced": {"readings": 31}}) == 31.0
    assert read({}) is None
    # a per-layer entry after all of the manifest's, under a layer none of
    # them names, and its reader
    entry = extended["per_layer"][-1]
    assert entry["layer"] == NEW_LAYER
    assert NEW_LAYER not in {m["layer"] for m in repo_manifest()["per_layer"]}
    read = cell.reader(entry["name"])
    assert read({"untraced": {"units": 1280, "readings": 10}}) == 128.0
    assert read({}) is None
    # its plain reference is a file under tests/perf, found by the name the
    # configuration file gives; a configuration may name none
    reference = cell.reference()
    assert reference.__file__ == os.path.join(
        ROOT, "tests", "perf", "references", "plain_lm.py"
    )
    assert callable(reference.loss_and_grads)
    assert set(cell.config["reference"]) >= {"module", "sample", "tolerance", "why"}
    assert manifest_lib.Cell(extended, TINY_FAMILY_CELL).reference() is None


@pytest.mark.parametrize(
    "config", MANIFEST["configs"], ids=lambda c: c["name"]
)
def test_shipped_configuration_names_a_reference_with_two_limits_or_none(config):
    cell = manifest_lib.Cell(
        MANIFEST,
        next(w["name"] for w in MANIFEST["workloads"] if w["config"] == config["name"]),
    )
    group = cell.config.get("reference")
    if group is None:
        # nothing is claimed for it, and the file says why
        assert cell.reference() is None and len(cell.config["not_compared"]) > 80
        return
    assert set(group) == {"module", "sample", "tolerance", "does_not_cover", "why"}
    assert callable(cell.reference().loss_and_grads)
    assert cell.reference().__file__.startswith(os.path.join(ROOT, "perf", "references"))
    # each limit is a number found on the chip: none is optional
    assert set(group["tolerance"]) == {"loss", "grad"}
    assert 0 < group["tolerance"]["loss"] < 0.05
    assert 0 < group["tolerance"]["grad"] < 0.5
    assert group["does_not_cover"] and all(len(x) > 20 for x in group["does_not_cover"])
    assert int(group["sample"]["units"]) > 0 and len(group["why"]) > 80


def test_an_unknown_name_is_an_error():
    with pytest.raises(manifest_lib.ManifestError):
        manifest_lib.Cell(MANIFEST, "no_such_cell")
