"""What a cell is, read from files found by name.

``BENCHMARK.json`` names the cells (``workloads``), their configurations
and the metrics. Everything that belongs to one configuration, one
traffic mix, one cell or one per-layer metric is a file of its own under
the benchmark's directory, resolved here by the name in
``BENCHMARK.json`` — no list of them exists in code, so a later PR adds
a cell, a mix, a configuration or a metric as files plus an entry.

    configs[].file                         the configuration as it is run
    <bench>/mixes/<traffic>.json           traffic-mix parameters
    <bench>/cells/<workload>.json          the cell's offered rate + knee
    <bench>/layer_metrics/<metric>.json    reader kind + arguments
    <bench>/host_spans/<span>.json         a host span the trace reads

Code a later PR adds — a mix's kind, a roofline's ``needs``, a metric's
reader — is a function in a file of its own, named where it is used as
``"package.module:function"`` (``named``).
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib

#: the checkout root (the directory that holds BENCHMARK.json)
ROOT = pathlib.Path(__file__).resolve().parents[2]


def named(path: str):
    """The function ``"package.module:function"`` names; a module or a
    function that is not there raises (ImportError, AttributeError)."""
    module, _, func = path.partition(":")
    return getattr(importlib.import_module(module), func)


def _read(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""

    name: str
    chips: int
    config_name: str
    config: dict            # configs/<config>.json
    mix_name: str
    mix: dict               # mixes/<traffic>.json
    load: dict              # cells/<workload>.json (rate_rps, knee_rps)
    end_to_end: tuple       # metric entries this cell reports, trace 0
    per_layer: tuple        # metric entries this cell reports, trace 1
    layer_metrics: dict     # metric name -> layer_metrics/<name>.json
    run_seconds: int


class Spec:
    """``BENCHMARK.json`` of one checkout root."""

    def __init__(self, root: pathlib.Path | str = ROOT):
        self.root = pathlib.Path(root)
        self.doc = _read(self.root / "BENCHMARK.json")
        #: the benchmark's own directory: the first of ``paths``
        self.bench = self.root / self.doc["paths"][0]

    def workloads(self) -> list:
        return [w["name"] for w in self.doc["workloads"]]

    @staticmethod
    def _applies(metric: dict, cell: str) -> bool:
        return cell in metric.get("workloads", (cell,))

    def cell(self, name: str) -> Cell:
        entry = next(
            (w for w in self.doc["workloads"] if w["name"] == name), None)
        if entry is None:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json "
                f"(has: {', '.join(self.workloads())})")
        cfg_entry = next(
            c for c in self.doc["configs"] if c["name"] == entry["config"])
        per_layer = tuple(
            m for m in self.doc["per_layer"] if self._applies(m, name))
        return Cell(
            name=name,
            chips=int(entry["chips"]),
            config_name=entry["config"],
            config=_read(self.root / cfg_entry["file"]),
            mix_name=entry["traffic"],
            mix=_read(self.bench / "mixes" / f"{entry['traffic']}.json"),
            load=_read(self.bench / "cells" / f"{name}.json"),
            end_to_end=tuple(
                m for m in self.doc["end_to_end"]
                if self._applies(m, name)),
            per_layer=per_layer,
            layer_metrics={
                m["name"]: _read(
                    self.bench / "layer_metrics" / f"{m['name']}.json")
                for m in per_layer
            },
            run_seconds=int(self.doc["run_seconds"]),
        )

    def peaks(self, device_kind: str) -> dict:
        """The published peaks of ``device_kind``; a device that is not
        in the table is an error, not a default."""
        table = _read(self.bench / "peaks.json")
        if device_kind not in table["devices"]:
            raise KeyError(
                f"device kind {device_kind!r} is not in peaks.json "
                f"(has: {', '.join(table['devices'])})")
        return table["devices"][device_kind]
