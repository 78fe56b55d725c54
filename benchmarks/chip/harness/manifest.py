"""BENCHMARK.json and the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in the manifest:

    configs/<config>.json   traffic/<traffic>.json   metrics/<metric>.json

so a later PR adds a cell by adding files and manifest entries, and edits
no file that is there.
"""
from __future__ import annotations

import json
import os

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP_DIR))


class ManifestError(ValueError):
    pass


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing benchmark file: {path}") from None


class Manifest:
    """The manifest and a lookup of the data files beside it.

    `data_dirs` are searched in order for `configs/`, `traffic/` and
    `metrics/`; the benchmark's own directory is always the last, so a
    test (or a later PR's directory) can add files without touching it.
    """

    def __init__(self, path: str = None, data_dirs=()):
        self.path = path or os.path.join(ROOT, "BENCHMARK.json")
        self.doc = _load(self.path)
        self.data_dirs = list(data_dirs) + [CHIP_DIR]
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.config_entries = {c["name"]: c for c in self.doc["configs"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}

    def _find(self, kind: str, name: str) -> str:
        for d in self.data_dirs:
            path = os.path.join(d, kind, name + ".json")
            if os.path.exists(path):
                return path
        raise ManifestError(f"no {kind}/{name}.json under {self.data_dirs}")

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise ManifestError(
                f"unknown workload {name!r}; BENCHMARK.json has "
                f"{sorted(self.cells)}")
        return self.cells[name]

    def config(self, name: str) -> dict:
        entry = self.config_entries.get(name)
        if entry is None:
            raise ManifestError(f"unknown configuration {name!r}")
        path = os.path.join(os.path.dirname(self.path), entry["file"])
        if not os.path.exists(path):
            path = self._find("configs", name)
        return _load(path)

    def traffic(self, name: str) -> dict:
        return _load(self._find("traffic", name))

    def metric_file(self, name: str) -> dict:
        return _load(self._find("metrics", name))

    def metrics_of_cell(self, cell_name: str, group: str) -> list:
        """Names of the `end_to_end` or `per_layer` metrics a cell
        reports: those without a `workloads` key, and those that list it."""
        return [m["name"] for m in self.doc[group]
                if "workloads" not in m or cell_name in m["workloads"]]
