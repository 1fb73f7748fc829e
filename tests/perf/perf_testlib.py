"""Shared by the tests of the benchmark: the repository's manifest plus the
tiny rehearsal cells, added as a later PR would add a cell — new files under
one of ``paths`` and new entries, no edit to a file that is there.  They
bring what the next configuration will: token cells that are not 8,192
tokens a step, a plain reference found by name under ``tests/perf``
(``references/plain_lm.py``, named by ``configs/tiny_lm.json``), a family
with no reference, and a per-layer entry under a layer no entry before it
names."""

from __future__ import annotations

import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_CELL = "tiny_lm_tiny"
TINY_RESIDENT_CELL = "tiny_lm_resident"
# another model family: its record kind and FLOP arithmetic are files under
# tests/perf too, found by the names its traffic and configuration files give
TINY_FAMILY_CELL = "tiny_mnist_digits"
# a layer no entry of BENCHMARK.json names
NEW_LAYER = "rehearsal layer (tests/perf only)"


def repo_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def manifest_with_tiny_cell() -> dict:
    manifest = copy.deepcopy(repo_manifest())
    manifest["configs"].append(
        {
            "name": "tiny_lm",
            "source": "none: CPU rehearsal of the harness only",
            "file": "tests/perf/configs/tiny_lm.json",
            "reduced": [],
            "why": "two layers of width 64: control flow only",
        }
    )
    manifest["workloads"].append(
        {
            "name": TINY_CELL,
            "config": "tiny_lm",
            "traffic": "tiny",
            "chips": 1,
            "why": "2 x 64 tokens a step on the CPU backend: rehearses the harness",
        }
    )
    manifest["workloads"].append(
        {
            "name": TINY_RESIDENT_CELL,
            "config": "tiny_lm",
            "traffic": "tiny_resident",
            "chips": 1,
            "why": "the same tokens as one batch resident on the device: "
            "rehearses traffic mode resident",
        }
    )
    manifest["configs"].append(
        {
            "name": "tiny_mnist",
            "source": "none: CPU rehearsal of the harness only",
            "file": "tests/perf/configs/tiny_mnist.json",
            "reduced": [],
            "why": "the MNIST zoo CNN: a second model family, control flow only",
        }
    )
    manifest["workloads"].append(
        {
            "name": TINY_FAMILY_CELL,
            "config": "tiny_mnist",
            "traffic": "tiny_digits",
            "chips": 1,
            "why": "8 x 28x28 uint8 images a step on the CPU backend: a family "
            "added by files alone",
        }
    )
    for metric in manifest["end_to_end"]:
        if metric["name"] == "tokens_per_s_chip":
            metric["workloads"] += [TINY_CELL, TINY_RESIDENT_CELL]
        if metric["name"] == "records_per_s_chip":
            metric["workloads"] += [TINY_FAMILY_CELL]
    manifest["per_layer"].append(
        {
            "name": "readings_per_window.tiny",
            "unit": "readings",
            "better": "higher",
            "source": "program_counter",
            "layer": "harness",
            "moves": "tokens_per_s_chip",
            "workloads": [TINY_CELL, TINY_FAMILY_CELL],
        }
    )
    manifest["per_layer"].append(
        {
            "name": "units_per_reading.tiny",
            "unit": "units",
            "better": "higher",
            "source": "program_counter",
            "layer": NEW_LAYER,
            "moves": "tokens_per_s_chip",
            "workloads": [TINY_CELL, TINY_RESIDENT_CELL],
        }
    )
    return manifest
