"""Reads ``BENCHMARK.json`` and resolves a cell to its files, by name.

Nothing here knows a configuration, a traffic mix, a metric, a model
family's FLOP arithmetic or a kind of record: a later PR adds files under
one of the manifest's ``paths`` and an entry to the manifest, and edits no
file that is already there."""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ManifestError(ValueError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, manifest: dict, workload: str, root: str = ROOT):
        self.root = root
        self.manifest = manifest
        entry = _by_name(manifest["workloads"], workload, "workload")
        self.name = entry["name"]
        self.chips = int(entry["chips"])
        self.traffic_name = entry["traffic"]
        config_entry = _by_name(manifest["configs"], entry["config"], "config")
        self.config = load_json(os.path.join(root, config_entry["file"]))
        self.traffic = load_json(
            self.find(os.path.join("traffic", entry["traffic"] + ".json"))
        )

    def find(self, relative: str) -> str:
        """``relative`` under the first of the manifest's ``paths`` that
        holds it."""
        for base in self.manifest["paths"]:
            path = os.path.join(self.root, base, relative)
            if os.path.exists(path):
                return path
        raise ManifestError(
            f"{relative} is under none of {self.manifest['paths']}"
        )

    def metrics(self, group: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports: a
        metric without a ``workloads`` key is every cell's."""
        return [
            m
            for m in self.manifest[group]
            if "workloads" not in m or self.name in m["workloads"]
        ]

    def module(self, directory: str, name: str):
        """The module ``<directory>/<name>.py`` under one of ``paths``: what
        belongs to one per-layer metric, one family of FLOP arithmetic, one
        kind of record, one way of driving the load or one plain reference
        is a file of its own, found by the name a data file gives."""
        path = self.find(os.path.join(directory, name + ".py"))
        spec = importlib.util.spec_from_file_location(
            f"perf_{directory}_" + name.replace(".", "_"), path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def reader(self, metric_name: str):
        """The ``read(run)`` function of ``layer_metrics/<name>.py``."""
        return self.module("layer_metrics", metric_name).read

    def record_kind(self):
        """``record_kinds/<kind>.py`` for the traffic file's records."""
        return self.module("record_kinds", self.traffic["records"]["kind"])

    def driver(self):
        """``drivers/<mode>.py``: how the traffic file's load reaches the
        trainer (``path``, the default, or a named other way)."""
        return self.module("drivers", self.traffic.get("mode", "path"))

    def reference(self):
        """``references/<module>.py`` named by the configuration's
        ``reference`` group, whose ``loss_and_grads(params, features,
        labels)`` is the configuration in plain float32
        (``perf/reference.py``); None for a configuration with no group."""
        group = self.config.get("reference")
        if group is None:
            return None
        return self.module("references", group["module"])

    def flops_per_record(self) -> dict:
        """``flop_functions/<function>.py`` applied to the configuration's
        ``flops`` group and the traffic file: FLOPs one record costs in
        training, ``{"train": ..., <part>: ...}``."""
        spec = self.config["flops"]
        return self.module("flop_functions", spec["function"]).per_record(
            spec, self.traffic
        )


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise ManifestError(
        f"no {what} named {name!r}: have {[e['name'] for e in entries]}"
    )


def load_manifest(path: str | None = None) -> dict:
    return load_json(path or os.path.join(ROOT, "BENCHMARK.json"))
