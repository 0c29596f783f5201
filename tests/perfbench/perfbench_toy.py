"""Two toy cells added as DATA to a temporary copy of the benchmark: a
BENCHMARK.json entry and a configuration file each, one traffic file,
one per-layer metric reader -- and no edit to a file that was there.
What a later PR does to add a cell; the tests run them on the CPU at toy
sizes."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NODE = {"cpu_milli": 4000, "memory_bytes": 34359738368, "pods": 110}
# upstream's anti-affinity row in small: every pod excludes every other
TOY_CONFIG = {
    "name": "toy-anti-96",
    "source": "tests/perfbench: a toy, not a deployment",
    "cluster": {"nodes": 96, "zones": 4, "node": NODE},
    "init_pods": {"count": 24, "template": "toy-anti"},
    "measured_pods": {"template": "toy-anti"},
    "templates": {"toy-anti": {"cpu_milli": 100, "memory_bytes": 524288000,
                               "group_labels": 1, "features": ["anti"]}},
    "scheduler": {"mode": "gang", "batch_size": 16},
    "warmup": {"surge": 4},
    "chips": 1, "mesh_shape": None,
    "precision": "as sp-antiaffinity-5000",
    "guarantees": ["as sp-antiaffinity-5000"],
    "reference": "default_plugins",
    "control": "blind-batch",
    "assumed": {}, "reduced": [],
}
# ...and the basic row: plain pods, one init pod a node
TOY_BASIC = dict(
    TOY_CONFIG, name="toy-basic-96",
    init_pods={"count": 96, "template": "toy-plain"},
    measured_pods={"template": "toy-plain"},
    templates={"toy-plain": {"cpu_milli": 100, "memory_bytes": 524288000,
                             "group_labels": 10, "features": []}},
    warmup={}, precision="as sp-basic-5000", control="bf16-scores")

TOY_TRAFFIC = {"name": "toy-closed", "kind": "closed", "depth": 32,
               "resident_bound": 16, "pool_pods_per_s": 200,
               "warmup": {"min_s": 0.2, "quiet_s": 0.5, "quiet_binds": 16,
                          "pool_s": 2.0, "max_s": 300.0}}

TOY_READER = '''"""toy per-layer metric: cycles the flight recorder saw."""


def read(ctx):
    return float(len(ctx.cycles)) or None
'''


def make_root(tmp: str) -> str:
    """A checkout-like directory: the program linked in, the benchmark
    copied, the toy cell added."""
    root = os.path.join(tmp, "checkout")
    os.makedirs(os.path.join(root, "perfbench"))
    for d in ("kubetpu", "config"):
        os.symlink(os.path.join(REPO, d), os.path.join(root, d))
    for d in ("lib", "configs", "traffic", "metrics", "kernels",
              "reference"):
        shutil.copytree(os.path.join(REPO, "perfbench", d),
                        os.path.join(root, "perfbench", d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    for f in ("__init__.py", "run.py"):
        shutil.copy(os.path.join(REPO, "perfbench", f),
                    os.path.join(root, "perfbench", f))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    traffic = TOY_TRAFFIC
    e2e = "pods_bound_per_s"
    cells = []
    for config in (TOY_CONFIG, TOY_BASIC):
        cell = config["name"] + ".closed"
        cells.append(cell)
        bench["configs"].append({
            "name": config["name"], "source": config["source"],
            "file": f"perfbench/configs/{config['name']}.json",
            "reduced": [], "why": "toy"})
        bench["workloads"].append({
            "name": cell, "config": config["name"],
            "traffic": traffic["name"], "chips": 1, "why": "toy"})
        next(m for m in bench["end_to_end"]
             if m["name"] == e2e)["workloads"].append(cell)
        with open(os.path.join(root, "perfbench", "configs",
                               config["name"] + ".json"), "w") as f:
            json.dump(config, f)
    bench["per_layer"].append({
        "name": "toy_cycles", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "toy", "moves": e2e,
        "workloads": cells})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with open(os.path.join(root, "perfbench", "traffic",
                           traffic["name"] + ".json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "perfbench", "metrics",
                           "toy_cycles.py"), "w") as f:
        f.write(TOY_READER)
    return root
