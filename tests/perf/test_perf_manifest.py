"""BENCHMARK.json against the benchmark's contract, as far as a test
without a chip can hold it, and every cell's files resolved by name."""

import collections
import copy
import glob
import json
import os
import re
import subprocess
import sys

import pytest
from perf_testlib import (
    HBM_READERS,
    INNER_RUN,
    MANIFEST_COPY,
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


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_has_a_reader_found_by_name(metric):
    """The rule for ``per_layer``, stated once.  An entry has a reader, a file
    ``layer_metrics/<name>.py`` under one of ``paths``; its name is its own
    (``test_names_are_unique...``); every cell it lists exists
    (``test_metric_is_well_formed``) and reports the end-to-end metric it
    ``moves`` (``test_layer_metric_moves...``).  WHERE in the list it stands
    is held by nothing: the driver takes a new entry at the end of the list,
    so a test that pins an entry's position refuses the next PR's."""
    cells = metric.get("workloads", CELLS)
    assert cells and len(cells) == len(set(cells))
    # ``Cell.reader`` looks under ``paths`` and nowhere else
    assert callable(manifest_lib.Cell(MANIFEST, cells[0]).reader(metric["name"]))


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
    needed = {"module", "sample", "tolerance", "does_not_cover", "why"}
    # ``buffers``: the collection outside ``params`` the reference reads
    assert needed <= set(group) <= needed | {"buffers"}
    assert callable(cell.reference().loss_and_grads)
    assert cell.reference().__file__.startswith(os.path.join(ROOT, "perf", "references"))
    # each limit is a number found on the chip: none is optional
    assert set(group["tolerance"]) == {"loss", "grad"}
    assert 0 < group["tolerance"]["loss"] < 0.05
    assert 0 < group["tolerance"]["grad"] < 0.5
    assert group["does_not_cover"] and all(len(x) > 20 for x in group["does_not_cover"])
    assert int(group["sample"]["units"]) > 0 and len(group["why"]) > 80
    # the limits were found on a chip run, and the group says on which
    assert "Found on the chip" in group["why"] and "chip run" in group["why"]


def test_an_unknown_name_is_an_error():
    with pytest.raises(manifest_lib.ManifestError):
        manifest_lib.Cell(MANIFEST, "no_such_cell")


# ---- the door a later PR comes in by ------------------------------------------

# set on a test that compiles a model, runs a reference or starts a process
# (``tests/perf/conftest.py`` registers it): the run over a grown copy leaves
# those out, and takes every other test of every ``test_perf_*.py`` it finds,
# so a file that a later PR adds is in it without being named anywhere
LEFT_OUT = "not slow and not compiles_a_model"
# somewhat under what the run over the copy counted when PR 64 read it (599
# passed; 581 when PR 59 wrote it; the eight cases of the guards skipped):
# a later PR's tests add to it
GROWN_RUN_PASSES = 595
# the guards: they start runs of their own, so a run that one of them started
# leaves them out, but for the ONE run that is told to take them (``run_over``)
GUARD = pytest.mark.skipif(
    os.environ.get(INNER_RUN) == "plain", reason="this is the inner run of a guard"
)


def suffix_of(name: str) -> str | None:
    return name.rpartition(".")[2] if "." in name else None


def growth(manifest) -> str:
    """What tells one growth's names from the one before: nothing for the
    first (``added_cell``, ``made_up_share.lm``), then ``2``, ``3`` ..."""
    grown = sum(w["name"].startswith("added_cell") for w in manifest["workloads"])
    return str(grown + 1) if grown else ""


def added_cell(manifest) -> str:
    return "added_cell" + growth(manifest)


def made_up_names(manifest) -> list:
    """One made-up ``per_layer`` name for every suffix the list's names carry
    (``.lm``, ``.swa``, ``.conv`` ...), so that a test which finds "its"
    entries by a suffix, or counts them, meets one more; then two under a
    suffix no name carries, a new kernel's, the last of them the list's new
    tail."""
    tag = growth(manifest)
    # each suffix once, in the order the list first carries it
    suffixes = list(dict.fromkeys(
        filter(None, (suffix_of(m["name"]) for m in manifest["per_layer"]))
    ))
    assert "added" + tag not in suffixes
    return [f"made_up_share{tag}.{suffix}" for suffix in suffixes] + [
        f"made_up_roofline{tag}.added{tag}", f"made_up_share{tag}.added{tag}",
    ]


def grown_manifest(directory: str, cell: str = "gpt2s_seq8192", base=None) -> dict:
    """``base`` (the manifest these tests read, if none is given) as the next
    ``model_config`` PR leaves it: one more configuration and one more cell
    (copies of ``cell``'s under new names, the cell's name appended to every
    list the original is on) and made-up ``per_layer`` entries at the end of
    the list (``made_up_names``), the added cell's alone.  The new files sit
    in ``directory``, which joins ``paths`` (absolute, in this copy alone).
    A grown manifest grows again: the names carry ``growth``'s number."""
    manifest = copy.deepcopy(repo_manifest() if base is None else base)
    tag, new_cell, made_up = growth(manifest), added_cell(manifest), made_up_names(manifest)
    new_config = "added_config" + tag
    source = next(w for w in manifest["workloads"] if w["name"] == cell)
    entry = next(c for c in manifest["configs"] if c["name"] == source["config"])
    config = manifest_lib.load_json(os.path.join(ROOT, entry["file"]))
    config["name"] = new_config
    os.makedirs(os.path.join(directory, "configs"))
    os.makedirs(os.path.join(directory, "layer_metrics"))
    with open(os.path.join(directory, "configs", new_config + ".json"), "w") as f:
        json.dump(config, f)
    manifest["paths"].append(directory)
    manifest["configs"].append({
        **entry, "name": new_config,
        "file": os.path.join(directory, "configs", new_config + ".json"),
    })
    manifest["workloads"].append({**source, "name": new_cell, "config": new_config})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if cell in metric.get("workloads", []):
            metric["workloads"].append(new_cell)
    layer = manifest["per_layer"][0]["layer"]
    for name in made_up:
        with open(os.path.join(directory, "layer_metrics", name + ".py"), "w") as f:
            f.write("def read(run):\n    return (run.get('made_up') or {}).get('share')\n")
        manifest["per_layer"].append({
            "name": name, "unit": "%", "better": "lower", "source": "device_trace",
            "layer": layer, "moves": config["work"]["rate_metric"],
            "workloads": [new_cell],
        })
    return manifest


def run_over(manifest_path, directory=os.path.join(ROOT, "tests", "perf"), guards=False):
    """Every ``test_perf_*.py`` of ``directory`` but what ``LEFT_OUT`` leaves
    out, in a process of its own whose manifest is ``manifest_path``.  The
    guards are left out of it too, unless ``guards`` asks for them and this
    process is no inner run itself: one level of them, never a second."""
    files = sorted(glob.glob(os.path.join(directory, "test_perf_*.py")))
    assert files, directory
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env[MANIFEST_COPY] = str(manifest_path)
    env[INNER_RUN] = "guards" if guards and INNER_RUN not in os.environ else "plain"
    # a file outside tests/perf finds ``perf_testlib`` as those inside do
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "tests", "perf"), ROOT, env.get("PYTHONPATH", "")]
    )
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "-m", LEFT_OUT, *files],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )


def passed(done) -> int:
    found = re.search(r"(\d+) passed", done.stdout.strip().splitlines()[-1])
    return int(found.group(1)) if found else 0


@GUARD
@pytest.mark.parametrize(
    "source,guards", [("gpt2s_seq8192", False), ("gpt2s_seq1024_dp4", True)],
    ids=["gpt2s_seq8192", "gpt2s_seq1024_dp4_and_the_guards"],
)
def test_a_grown_manifest_passes_every_test_that_reads_the_manifest(tmp_path, source, guards):
    """The lists of configurations, cells and ``per_layer`` entries are open
    at their ends: the tests that read the manifest, run over a copy with one
    more of each, pass as they do over ``BENCHMARK.json``.  The files are
    found by their names, so the check holds for a file that a later PR adds;
    a PR that adds a cell runs THIS test first.  Once with a cell on one chip
    and once with one on four (the quota has room for it); **the second run
    takes the guards too** (PR 64), so each of them runs once over a manifest
    that has ALREADY grown by a configuration, a cell and a new suffix's
    entries, and grows it again: the rehearsal of the PR after the next one,
    which sees a pin that sits in a guard (PR 59's planted literal did, and
    no run over a copy ever ran the test that held it)."""
    if guards and INNER_RUN in os.environ:
        pytest.skip("the guards are taken one level deep")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(grown_manifest(str(tmp_path / "added"), source)))
    done = run_over(path, guards=guards)
    assert done.returncode == 0, done.stdout[-6000:]
    # every case ran, the copy's own among them (the added cell, the added
    # configuration, the made-up entries)
    assert passed(done) >= GROWN_RUN_PASSES, done.stdout.strip().splitlines()[-1]
    if guards:
        # the guards' own cases ran and passed, all but the one that is
        # taken one level deep and no deeper
        assert passed(done) >= GROWN_RUN_PASSES + 7, done.stdout.strip().splitlines()[-1]
        assert "1 skipped" in done.stdout.strip().splitlines()[-1]


PINS = """
from perf_testlib import repo_manifest


def test_the_lists_tail_is_where_it_was():
    assert repo_manifest()["per_layer"][-1]["name"] == {tail!r}


def test_the_suffix_counts_what_it_counted():
    names = [m["name"] for m in repo_manifest()["per_layer"]]
    assert len([name for name in names if name.endswith({suffix!r})]) == {count}
"""
# PR 59's planted file, a literal that had to PASS over ``BENCHMARK.json`` as
# shipped: no PR but a ``benchmark`` PR could append a ``per_layer`` entry
# while it stood (PR 60 and PR 61 met it)
PINS_OF_PR_59 = PINS.format(tail="hbm_unexplained_gb", suffix=".swa", count=8)


def planted_pins(manifest) -> str:
    """A test file that pins what ``manifest`` holds today: the name of its
    last ``per_layer`` entry, and the count of the suffix it has most entries
    of.  Made from the manifest the guard is run over, so it passes there
    whatever a PR appended, and fails over that manifest grown."""
    names = [m["name"] for m in manifest["per_layer"]]
    counts = collections.Counter(filter(None, map(suffix_of, names)))
    suffix, count = counts.most_common(1)[0]
    return PINS.format(tail=names[-1], suffix="." + suffix, count=count)


def plant(tmp_path, text: str) -> str:
    planted = tmp_path / "planted"
    planted.mkdir()
    (planted / "test_perf_planted_pin.py").write_text(text)
    return str(planted)


@GUARD
@pytest.mark.parametrize("grown_before", [0, 1], ids=["as_it_stands", "grown_once_before"])
def test_a_test_that_pins_the_lists_tail_fails_the_run_over_a_grown_manifest(
    tmp_path, grown_before
):
    """The guard bites, at any length: a test file that holds an entry to the
    end of ``per_layer``, as ``test_perf_trinity.py`` held the ``.swa``
    entries up to PR 59, or counts the entries of one suffix, passes over the
    manifest it was made from and fails the run over that manifest grown,
    found by the same glob.  Over the manifest as these tests read it, and
    over one that a ``model_config`` PR has already appended to."""
    manifest = repo_manifest()
    for n in range(grown_before):
        manifest = grown_manifest(str(tmp_path / f"before{n}"), base=manifest)
    planted = plant(tmp_path, planted_pins(manifest))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    done = run_over(path, planted)
    assert (done.returncode, passed(done)) == (0, 2), done.stdout[-3000:]
    path.write_text(json.dumps(grown_manifest(str(tmp_path / "added"), base=manifest)))
    done = run_over(path, planted)
    assert done.returncode == 1 and "2 failed" in done.stdout, done.stdout[-3000:]
    assert "test_the_lists_tail_is_where_it_was" in done.stdout
    assert "test_the_suffix_counts_what_it_counted" in done.stdout


@GUARD
def test_the_literal_pr_59_planted_fails_over_a_manifest_grown_once(tmp_path):
    """The wall PR 64 took down.  PR 59's guard planted its pins as text and
    needed them to pass over ``BENCHMARK.json``: over a manifest with one
    entry appended, which is every ``model_config`` PR that brings a kernel,
    that first half could not pass, and the guard failed the PR for it."""
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(grown_manifest(str(tmp_path / "added"))))
    done = run_over(path, plant(tmp_path, PINS_OF_PR_59))
    assert done.returncode == 1, done.stdout[-3000:]
    assert "FAILED" in done.stdout and "test_the_lists_tail_is_where_it_was" in done.stdout
    assert "'hbm_unexplained_gb'" in done.stdout


@GUARD
@pytest.mark.parametrize(
    "source", ["gpt2s_seq8192", "resnet50_imagenet_resident", "lfm2_24b_a2b_seq4096x4"]
)
def test_a_cell_added_to_a_grown_manifest_reports_what_its_source_reports(tmp_path, source):
    """The next ``model_config`` PR, rehearsed: ``perf/manifest.py`` loads the
    grown copy, and the added cell reports every metric its source reports,
    ``peak_hbm_gb``'s five among them, and the entries added after them all."""
    if source not in CELLS:
        pytest.skip(f"{source} is not a cell of this manifest")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(grown_manifest(str(tmp_path / "added"), source)))
    grown = manifest_lib.load_manifest(str(path))
    made_up, added_name = made_up_names(MANIFEST), added_cell(MANIFEST)
    for group, more in (("configs", 1), ("workloads", 1), ("per_layer", len(made_up))):
        assert len(grown[group]) == len(MANIFEST[group]) + more
    assert grown["workloads"][-1]["name"] == added_name
    assert [m["name"] for m in grown["per_layer"][-len(made_up):]] == made_up
    # nothing that was there moved or changed but by the added cell's name
    for group in ("end_to_end", "per_layer"):
        for old, new in zip(MANIFEST[group], grown[group]):
            lists = new.get("workloads", [])
            assert {**new, "workloads": [c for c in lists if c != added_name]} == {
                **old, "workloads": old.get("workloads", [])
            }
            assert added_name not in lists[:-1]
    added, original = manifest_lib.Cell(grown, added_name), manifest_lib.Cell(grown, source)
    for group in ("end_to_end", "per_layer"):
        theirs = [m["name"] for m in original.metrics(group)]
        mine = [m["name"] for m in added.metrics(group)]
        assert [name for name in mine if name not in made_up] == theirs
    reported = [m["name"] for m in added.metrics("per_layer")]
    assert set(HBM_READERS) <= set(reported) and reported[-len(made_up):] == made_up
    assert all(callable(added.reader(name)) for name in reported)
    assert added.flops_per_record()["train"] > 0
