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
# ``peak_hbm_gb``'s five per-layer metrics, which every cell reports
HBM_READERS = (
    "state_hbm_gb", "step_temp_hbm_gb", "residuals_at_peak_hbm_gb",
    "head_loss_at_peak_hbm_gb", "hbm_unexplained_gb",
)
# ``trinity_mini_seq16384``'s eight, the last of the list up to PR 58
SWA_READERS = (
    "attention_kernels_time_share.swa", "window_attention_time_share.swa",
    "swa_fwd_roofline.swa", "swa_dq_roofline.swa", "swa_dkv_roofline.swa",
    "held_pair_share.swa", "router_load_max_over_mean.swa",
    "expert_gmm_time_share.swa",
)
# ``lfm2_24b_a2b_seq4096x4``'s seven
CONV_READERS = (
    "short_conv_time_share.conv", "short_conv_fwd_roofline.conv",
    "short_conv_bwd_roofline.conv", "conv_operator_share.scope_conv",
    "held_pair_share.conv", "router_load_max_over_mean.conv",
    "expert_gmm_time_share.conv",
)
# a layer no entry of BENCHMARK.json names
NEW_LAYER = "rehearsal layer (tests/perf only)"


# set by ``test_perf_manifest.py``'s test of a grown manifest alone: the tests
# that read the manifest are run once more over a copy that a later PR's
# additions were made to
MANIFEST_COPY = "PERF_TESTS_MANIFEST_COPY"
# set by that test's ``run_over`` beside it: ``"plain"`` in a run that leaves
# the guards out (they would start runs of their own without end), ``"guards"``
# in the one run over a grown copy that takes them too, each growing its copy
# again (PR 64).  "My manifest is a copy" and "I am the inner run of a guard"
# are two things: a pin that sits in a guard is seen only where a guard runs
# over a manifest that has grown.
INNER_RUN = "PERF_TESTS_INNER_RUN"


def repo_manifest() -> dict:
    with open(os.environ.get(MANIFEST_COPY) or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def stand_together_after(listed, names, earlier) -> bool:
    """Whether ``names`` stand in ``listed`` (the names of ``per_layer``) one
    after another in their own order, each of ``earlier`` before the first of
    them.  Names only, no count and no scan by suffix: what a later PR puts
    after them, whatever it is called, is held by nothing."""
    names = list(names)
    first = listed.index(names[0])
    return listed[first:first + len(names)] == names and all(
        listed.index(name) < first for name in earlier
    )


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
