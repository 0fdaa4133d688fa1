"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

A cell names a configuration and a traffic mix. The configuration's
file is the ``file`` of its ``configs`` entry; the mix is
``traffic/<traffic>.json``; a per-layer metric is
``layer_metrics/<name>.json``; a driver or reader ``kind`` is the module
``drivers/<kind>.py`` or ``readers/<kind>.py``. Adding any of them is
adding a file (and, for a cell, its ``workloads`` entry).
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


class ManifestError(Exception):
    """BENCHMARK.json names something that has no file."""


def _read_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise ManifestError(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


class Benchmark:
    def __init__(self, manifest: dict, root: str = ROOT, base: str = HERE):
        #: ``base`` holds ``traffic/`` and ``layer_metrics/``
        self.manifest, self.root, self.base = manifest, root, base

    @classmethod
    def load(cls, root: str = ROOT, base: str = HERE) -> "Benchmark":
        return cls(_read_json(os.path.join(root, "BENCHMARK.json"),
                              "the benchmark"), root, base)

    def cell(self, name: str) -> dict:
        for w in self.manifest["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json (it has "
            f"{[w['name'] for w in self.manifest['workloads']]})")

    def config(self, name: str) -> dict:
        for c in self.manifest["configs"]:
            if c["name"] == name:
                return _read_json(os.path.join(self.root, c["file"]),
                                  f"configuration {name!r}")
        raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _read_json(os.path.join(self.base, "traffic", f"{name}.json"),
                          f"traffic mix {name!r}")

    def _metrics_for(self, group: str, cell_name: str) -> list[dict]:
        """The ``group`` metrics ``cell_name`` reports: those that list
        it under ``workloads``, and those with no such key."""
        return [m for m in self.manifest[group]
                if cell_name in m.get("workloads", [cell_name])]

    def end_to_end(self, cell_name: str) -> list[dict]:
        return self._metrics_for("end_to_end", cell_name)

    def per_layer(self, cell_name: str) -> list[tuple[dict, dict]]:
        """(manifest entry, the metric's own file) per per-layer metric
        of the cell."""
        return [(m, self.layer_metric(m["name"]))
                for m in self._metrics_for("per_layer", cell_name)]

    def layer_metric(self, name: str) -> dict:
        return _read_json(
            os.path.join(self.base, "layer_metrics", f"{name}.json"),
            f"per-layer metric {name!r}")

    def check_files(self) -> None:
        """Every name in BENCHMARK.json has its file, and every kind its
        module; raises ManifestError for the first that has not."""
        for w in self.manifest["workloads"]:
            self.config(w["config"])
            load_kind("drivers", self.traffic(w["traffic"])["kind"])
        for m in self.manifest["per_layer"]:
            load_kind("readers", self.layer_metric(m["name"])["reader"])


def load_kind(package: str, kind: str):
    """``drivers/<kind>.py`` or ``readers/<kind>.py`` as a module."""
    if not os.path.isfile(os.path.join(HERE, package, f"{kind}.py")):
        raise ManifestError(f"no {package}/{kind}.py for kind {kind!r}")
    return importlib.import_module(f"benchmarks.{package}.{kind}")
