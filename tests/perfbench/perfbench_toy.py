"""Three toy cells added as DATA to a temporary copy of the benchmark: a
BENCHMARK.json entry and a configuration file each, one traffic file,
one per-layer metric reader -- and no edit to a file that was there.
What a later PR does to add a cell; the tests run them on the CPU at toy
sizes.  ``toy-mixed-96`` is the worked example of what a configuration
file may state since PR 28 (``perfbench/lib/world.py``): literal pod
templates, an ``init_pods`` list and node labels, here upstream's
MixedSchedulingBasePod row in small."""

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
    # 24 init + 16 resident + the pods in flight (one job on the binder
    # lane, one batch assumed) straddle the 64-row edge of the program's
    # pod axis: the surge crosses it in warm-up (24 + 16 + 28 = 68 bound,
    # 28 nodes still free), or the crossing compiles _schedule_gang on
    # 128 rows inside the 2 s window and no bind lands in it
    "warmup": {"surge": 28},
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


ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
_POD = {"cpu_milli": 100, "memory_bytes": 524288000}
# upstream's pod templates (v1.19 test/integration/scheduler_perf/config/)
# as ISSUE 28 recalls them, one ``templates`` entry each
UPSTREAM_TEMPLATES = {
    "pod-default": dict(_POD),
    "pod-with-pod-affinity": dict(
        _POD, labels={"color": "blue"},
        pod_affinity=[{"topology_key": ZONE, "required": True,
                       "match_labels": {"color": "blue"}}]),
    "pod-with-pod-anti-affinity": dict(
        _POD, labels={"color": "green"},
        pod_anti_affinity=[{"topology_key": HOSTNAME, "required": True,
                            "match_labels": {"color": "green"}}]),
    "pod-with-preferred-pod-affinity": dict(
        _POD, labels={"color": "red"},
        pod_affinity=[{"topology_key": HOSTNAME, "weight": 1,
                       "match_labels": {"color": "red"}}]),
    "pod-with-preferred-pod-anti-affinity": dict(
        _POD, labels={"color": "yellow"},
        pod_anti_affinity=[{"topology_key": HOSTNAME, "weight": 1,
                            "match_labels": {"color": "yellow"}}]),
    "pod-with-topology-spreading": dict(
        _POD, labels={"color": "blue"},
        topology_spread=[{"max_skew": 5, "topology_key": ZONE,
                          "when_unsatisfiable": "DoNotSchedule",
                          "match_labels": {"color": "blue"}}]),
    "pod-with-preferred-topology-spreading": dict(
        _POD, labels={"color": "blue"},
        topology_spread=[{"max_skew": 5, "topology_key": ZONE,
                          "when_unsatisfiable": "ScheduleAnyway",
                          "match_labels": {"color": "blue"}}]),
    "pod-with-node-affinity": dict(
        _POD, node_affinity_in={"key": ZONE, "values": ["zone1", "zone2"]}),
    "pod-low-priority": {"cpu_milli": 900, "memory_bytes": 524288000,
                         "priority": 0},
    "pod-high-priority": {"cpu_milli": 3000, "memory_bytes": 524288000,
                          "priority": 10},
}
MIXED_INIT = ("pod-default", "pod-with-pod-affinity",
              "pod-with-pod-anti-affinity", "pod-with-preferred-pod-affinity",
              "pod-with-preferred-pod-anti-affinity")
# upstream's MixedSchedulingBasePod row in small: five init templates x
# 24 over 96 nodes labelled with ONE zone value, plain pods to schedule
TOY_MIXED = dict(
    TOY_CONFIG, name="toy-mixed-96",
    cluster={"nodes": 96, "node": NODE, "node_labels": {ZONE: ["zone1"]}},
    init_pods=[{"template": t, "count": 24} for t in MIXED_INIT],
    measured_pods={"template": "pod-default"},
    templates={t: UPSTREAM_TEMPLATES[t] for t in MIXED_INIT},
    warmup={}, precision="as sp-basic-5000",
    guarantees=["as sp-basic-5000"],
    # the only reference there is; it REFUSES this row's existing pods
    # (preferred terms), which is what a row's own reference file is for
    reference="default_plugins", control="bf16-scores",
    assumed={"templates": "labels, keys, weights and maxSkew as ISSUE 28 "
                          "recalls upstream's v1.19 pod-*.yaml; the repo "
                          "holds no copy of them (SURVEY.md / SNIPPETS.md "
                          "name the rows only): unverified"})

# quiet_s is longer than one _schedule_gang compile on this CPU (1.3-2.6
# s cold), and the two dips drive the delta-row buckets either side of
# the steady state's, as saturated-d4096 does
TOY_TRAFFIC = {"name": "toy-closed", "kind": "closed", "depth": 32,
               "resident_bound": 16, "pool_pods_per_s": 200,
               "warmup": {"min_s": 0.2, "quiet_s": 2.5, "quiet_binds": 64,
                          "pool_s": 2.0, "max_s": 300.0, "dips": 2}}

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
              "reference", "controls"):
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
    for config in (TOY_CONFIG, TOY_BASIC, TOY_MIXED):
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
