"""``BENCHMARK.json`` and the data files it names.

Everything that belongs to one configuration, one traffic mix, one
family or one per-layer metric is a file of its own under the
benchmark's first path, found by the name the manifest gives it::

    <root>/BENCHMARK.json
    <root>/<paths[0]>/configs/<config>.json      (the manifest's "file")
    <root>/<paths[0]>/traffic/<traffic>.json
    <root>/<paths[0]>/families/<family>.py       (named by the config)
    <root>/<paths[0]>/layers/<metric>.py
    <root>/<paths[0]>/peaks.json

``root`` is the directory that holds the manifest, so a test can stand
a whole benchmark up in a temporary directory.  Adding a cell, a
configuration or a per-layer metric is adding files and manifest
entries; no code here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names resolved."""
    name: str
    chips: int
    config: dict          # the configuration file
    job: dict             # the traffic file: the job's shape
    end_to_end: tuple     # manifest entries of the metrics of this cell
    per_layer: tuple
    home: str             # <root>/<paths[0]>
    manifest_path: str

    @property
    def out_dir(self) -> str:
        """Where a run of this cell leaves its records, logs and trace."""
        return os.path.join(self.home, "out", self.name)


def _read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"{what} {name!r} is not in the manifest; it has "
                   f"{[e['name'] for e in entries]}")


def load_cell(workload: str, manifest_path: str = MANIFEST) -> Cell:
    manifest = _read_json(manifest_path)
    root = os.path.dirname(os.path.abspath(manifest_path))
    home = os.path.join(root, manifest["paths"][0])
    entry = _by_name(manifest["workloads"], workload, "workload")
    config_entry = _by_name(manifest["configs"], entry["config"], "config")

    def of_this_cell(metrics: list) -> tuple:
        return tuple(m for m in metrics
                     if workload in m.get("workloads", [workload]))

    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=_read_json(os.path.join(root, config_entry["file"])),
        job=_read_json(os.path.join(home, "traffic",
                                    entry["traffic"] + ".json")),
        end_to_end=of_this_cell(manifest["end_to_end"]),
        per_layer=of_this_cell(manifest["per_layer"]),
        home=home, manifest_path=os.path.abspath(manifest_path))


def _load_module(path: str):
    name = "benchmark_file_" + os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_family(cell: Cell):
    """The adapter that builds this configuration's trainer."""
    return _load_module(os.path.join(
        cell.home, "families", cell.config["family"] + ".py"))


def load_layer_reader(cell: Cell, metric: str):
    """``read(trace, counters, cell)`` of one per-layer metric."""
    return _load_module(os.path.join(
        cell.home, "layers", metric + ".py")).read


def load_peaks(cell: Cell, device_kind: str) -> dict:
    """The peaks of one ``device_kind``.  A device that is not in the
    table is an error, not a default."""
    peaks = _read_json(os.path.join(cell.home, "peaks.json"))
    if device_kind not in peaks:
        raise KeyError(f"device_kind {device_kind!r} is not in peaks.json "
                       f"({sorted(peaks)}): nothing is measured on it")
    return peaks[device_kind]
