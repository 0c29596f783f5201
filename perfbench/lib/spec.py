"""BENCHMARK.json and the files it names.

A cell is one ``workloads`` entry.  Its configuration, its traffic mix and
its per-layer metric readers are files found by NAME:

  perfbench/configs/<config>.json
  perfbench/traffic/<traffic>.json
  perfbench/metrics/<metric name>.py     (one ``read(ctx)`` each)
  perfbench/reference/<reference>.py     (named by the configuration)

so a later PR adds a cell by adding files and one entry, and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict

from . import traffic as _traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class SpecError(Exception):
    pass


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise SpecError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload with everything it names resolved."""

    def __init__(self, root: str, bench: Dict[str, Any], name: str):
        self.root = root
        self.name = name
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SpecError(
                f"no workload {name!r} in BENCHMARK.json; it has "
                f"{[w['name'] for w in bench['workloads']]}")
        self.entry = entry
        self.chips = int(entry["chips"])
        cfg_entry = next((c for c in bench["configs"]
                          if c["name"] == entry["config"]), None)
        if cfg_entry is None:
            raise SpecError(f"workload {name}: no config {entry['config']!r}")
        self.config_file = os.path.join(root, cfg_entry["file"])
        self.config = load_json(self.config_file)
        self.traffic_file = os.path.join(
            root, "perfbench", "traffic", entry["traffic"] + ".json")
        if not os.path.exists(self.traffic_file):
            raise SpecError(f"workload {name}: no {self.traffic_file}")
        self.traffic = load_json(self.traffic_file)
        _traffic.validate(self.traffic)
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def readers(self) -> Dict[str, Callable]:
        """metric name -> its reader's ``read``."""
        out = {}
        for m in self.per_layer:
            path = os.path.join(self.root, "perfbench", "metrics",
                                m["name"] + ".py")
            if not os.path.exists(path):
                raise SpecError(f"per-layer metric {m['name']}: no {path}")
            out[m["name"]] = _load_module(
                path, "perfbench_metric_" + m["name"].replace(".", "_")
                .replace("-", "_")).read
        return out

    def reference(self):
        """The configuration's plain reference module."""
        ref = self.config["reference"]
        path = os.path.join(self.root, "perfbench", "reference", ref + ".py")
        if not os.path.exists(path):
            raise SpecError(f"config {self.config['name']}: no {path}")
        return _load_module(path, "perfbench_reference_" + ref)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root: str = ROOT) -> Cell:
    return Cell(root, load_benchmark(root), name)
