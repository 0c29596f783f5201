"""BENCHMARK.json and the files it names.

A cell is one ``workloads`` entry.  Its configuration, its traffic mix and
its per-layer metric readers are files found by NAME:

  perfbench/configs/<config>.json
  perfbench/traffic/<traffic>.json
  perfbench/metrics/<metric name>.py     (one ``read(ctx)`` each)
  perfbench/reference/<reference>.py     (named by the configuration)
  perfbench/controls/<control>.py        (named by the configuration:
                                          ``REFERENCE_KW``, what the
                                          reference's ``auction_schedule``
                                          is called with, and
                                          ``program_control()``, the
                                          context manager that patches the
                                          program; ``tools/control.py``)

so a later PR adds a cell by adding files and one entry, and edits none.
What a configuration file may state about its pods and nodes is in
``lib/world.py``'s docstring; ``tests/perfbench/perfbench_toy.py`` adds
three toy configurations this way (``toy-mixed-96`` is the worked
example of literal templates, an init-pod list and node labels), and
``perfbench/tools/later_pr_tree.py`` builds a copy of the benchmark with
a row added so at its real size, in which the benchmark's own tests run:
a test that holds only for today's rows or entries fails there.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict

from . import traffic as _traffic
from . import world as _world

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
        try:
            _world.validate(self.config)
        except ValueError as e:
            raise SpecError(f"{self.config_file}: {e}") from e
        self.control_file = os.path.join(
            root, "perfbench", "controls",
            str(self.config.get("control")) + ".py")
        if not os.path.exists(self.control_file):
            raise SpecError(
                f"config {self.config.get('name')}: control "
                f"{self.config.get('control')!r}: no {self.control_file}")
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

    def control(self):
        """The configuration's control module: ``REFERENCE_KW`` and
        ``program_control()``."""
        name = self.config["control"]
        mod = _load_module(self.control_file, "perfbench_control_"
                           + name.replace(".", "_").replace("-", "_"))
        for attr in ("REFERENCE_KW", "program_control"):
            if not hasattr(mod, attr):
                raise SpecError(f"{self.control_file}: no {attr}")
        return mod


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root: str = ROOT) -> Cell:
    return Cell(root, load_benchmark(root), name)
