"""``BENCHMARK.json`` and the files it names, found by name.

Nothing is registered in code: a cell names a configuration and a traffic
mix, and each is one file under ``benchmark/``; a per-layer metric is one
reader under ``benchmark/layer_metrics/``. A later PR adds entries and files
and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List

from .device import CHECKOUT

BENCH_DIR = os.path.join(CHECKOUT, "benchmark")


def load_manifest() -> Dict[str, Any]:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _named(entries: List[Dict[str, Any]], name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}; it has "
                   f"{[e['name'] for e in entries]}")


def load_json(relpath: str) -> Dict[str, Any]:
    with open(os.path.join(CHECKOUT, relpath)) as fh:
        return json.load(fh)


def load_module(relpath: str, name: str):
    """Import one file under the checkout as a module of its own."""
    path = os.path.join(CHECKOUT, relpath)
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic mix,
    its reference and the metrics it reports."""

    def __init__(self, manifest: Dict[str, Any], name: str):
        self.manifest = manifest
        self.workload = _named(manifest["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg_entry = _named(manifest["configs"], self.workload["config"],
                           "config")
        self.config_name = cfg_entry["name"]
        self.config = load_json(cfg_entry["file"])
        self.traffic_name = self.workload["traffic"]
        self.traffic = load_json(
            f"benchmark/traffic/{self.traffic_name}.json")
        self.reference = load_module(
            f"benchmark/references/{self.config_name}.py",
            f"benchmark_reference_{self.config_name}")

    def _reports(self, metric: Dict[str, Any]) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.manifest["end_to_end"] if self._reports(m)]

    def per_layer(self) -> List[Dict[str, Any]]:
        """The per-layer metrics this cell reports: those that list it, and
        those that list no cell and move an end-to-end metric it reports."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.manifest["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def reader(self, metric_name: str):
        return load_module(f"benchmark/layer_metrics/{metric_name}.py",
                           f"benchmark_layer_metric_{metric_name}").read
