#!/usr/bin/env python3
"""A rehearsal of the PR that adds the next row: a copy of the benchmark
with a cell of row 3's shape added AS A LATER PR MAY ADD IT -- new
files and entries of BENCHMARK.json, no edit to a file that is there --
so that the benchmark's own tests can be run against it before such a PR
exists.  A test that pins "every configuration" or "every metric" to the
ones of today fails there, in a file that PR could not edit.

    python3 perfbench/tools/later_pr_tree.py --out <new directory>
    cd <new directory> && JAX_PLATFORMS=cpu python -m pytest tests/perfbench

What it adds, under names no real row will take:
``configs/rehearsal-mixed-5000.json`` (upstream's
MixedSchedulingBasePod at 5000 nodes as ISSUE 28 recalls it: five init
templates x 2,000, two pods a node, plain measured pods, ONE zone value;
unverified, see its ``assumed``), a reference, a control and two readers
of its own (copies of files that are there: stand-ins, what matters is
that they are found by name), the cell listed for every per-layer metric
that was there, one per-layer metric for the new cell alone and one that
lists no ``workloads`` and is read in every cell.  It is a rehearsal of
the harness, not row 3: that PR brings a reference that models the terms.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
# names no row of upstream will take
CONFIG, CELL = "rehearsal-mixed-5000", "rehearsal-mixed-5000.saturated"
READERS = ("rehearsal_one_cell_ms.sat", "rehearsal_every_cell_ms.sat")
_POD = {"cpu_milli": 100, "memory_bytes": 524288000}


def _term(key, color, **how):
    return [dict(topology_key=key, match_labels={"color": color}, **how)]


TEMPLATES = {
    "pod-default": dict(_POD),
    "pod-with-pod-affinity": dict(
        _POD, labels={"color": "blue"},
        pod_affinity=_term(ZONE, "blue", required=True)),
    "pod-with-pod-anti-affinity": dict(
        _POD, labels={"color": "green"},
        pod_anti_affinity=_term(HOSTNAME, "green", required=True)),
    "pod-with-preferred-pod-affinity": dict(
        _POD, labels={"color": "red"},
        pod_affinity=_term(HOSTNAME, "red", weight=1)),
    "pod-with-preferred-pod-anti-affinity": dict(
        _POD, labels={"color": "yellow"},
        pod_anti_affinity=_term(HOSTNAME, "yellow", weight=1)),
}


def mixed_config(basic: dict) -> dict:
    """The row's file, from the basic row's (its node shape, scheduler
    section and guarantees) and upstream's templates."""
    return dict(
        basic, name=CONFIG,
        source="kubernetes test/integration/scheduler_perf/config/"
               "performance-config.yaml, MixedSchedulingBasePod with "
               "5000Nodes params",
        cluster={"nodes": 5000, "node": basic["cluster"]["node"],
                 "node_labels": {ZONE: ["zone1"]}},
        init_pods=[{"template": t, "count": 2000} for t in TEMPLATES],
        measured_pods={"template": "pod-default"}, templates=TEMPLATES,
        reference="rehearsal_plugins", control="rehearsal-control",
        assumed=dict(basic["assumed"], templates=(
            "labels, keys and weights as ISSUE 28 recalls upstream's v1.19 "
            "pod-*.yaml; the repo holds no copy of them: unverified")))


def build(out: str, root: str = HERE) -> str:
    """Copy the benchmark of ``root`` to the new directory ``out`` and
    add the row there.  Returns ``out``."""
    os.makedirs(os.path.join(out, "tests"))
    for d in ("kubetpu", "config"):
        os.symlink(os.path.join(root, d), os.path.join(out, d))
    junk = shutil.ignore_patterns("__pycache__", ".scratch")
    for d in ("perfbench", os.path.join("tests", "perfbench")):
        shutil.copytree(os.path.join(root, d), os.path.join(out, d),
                        ignore=junk)
    shutil.copy(os.path.join(root, "tests", "conftest.py"),
                os.path.join(out, "tests", "conftest.py"))
    pb = os.path.join(out, "perfbench")
    with open(os.path.join(pb, "configs", "sp-basic-5000.json")) as f:
        config = mixed_config(json.load(f))
    with open(os.path.join(pb, "configs", CONFIG + ".json"), "w") as f:
        json.dump(config, f, indent=2)
    for sub, was, new in (
            ("reference", "default_plugins.py", "rehearsal_plugins.py"),
            ("controls", "bf16-scores.py", "rehearsal-control.py"),
            ("metrics", "prefilter_ms_per_cycle.sat.py", READERS[0] + ".py"),
            ("metrics", "prefilter_ms_per_cycle.sat.py", READERS[1] + ".py")):
        shutil.copy(os.path.join(pb, sub, was), os.path.join(pb, sub, new))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": CONFIG, "source": config["source"],
        "file": f"perfbench/configs/{CONFIG}.json", "reduced": [],
        "why": "existing pods of five templates, four of them with pod "
               "terms, against plain incoming pods"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "saturated-d4096",
        "chips": 1,
        "why": "the same closed loop over 10,000 init pods, two a node"})
    e2e = "pods_bound_per_s"
    # the cell lists itself wherever cells are listed (an entry without
    # the key already holds for every cell)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and (m["name"] == e2e or m.get("moves") == e2e):
            m["workloads"].append(CELL)
    entry = {"unit": "ms", "better": "lower", "source": "program_span",
             "layer": "prepare", "moves": e2e}
    bench["per_layer"].append(dict(entry, name=READERS[0], workloads=[CELL]))
    bench["per_layer"].append(dict(entry, name=READERS[1]))
    with open(os.path.join(out, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True, help="a directory to create")
    ap.add_argument("--root", default=HERE)
    args = ap.parse_args(argv)
    out = build(os.path.abspath(args.out), args.root)
    print(f"{CELL} added under {out}; now:\n  cd {out} && "
          "JAX_PLATFORMS=cpu python -m pytest tests/perfbench -q")
    return 0


if __name__ == "__main__":
    sys.exit(main())
