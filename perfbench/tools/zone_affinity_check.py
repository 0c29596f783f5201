#!/usr/bin/env python3
"""Check (b) of a required-affinity row over a cluster in which the
filter BITES: the row's nodes in THREE zones, every bound pod in ONE.

    python3 perfbench/tools/zone_affinity_check.py \
        --workload sp-podaffinity-5000.saturated --seeds 1,2

``sp-podaffinity-5000`` labels every node ``zone1`` (upstream does), so
its own check (b) cannot see a node wrongly ADMITTED by the incoming
required-affinity filter.  The cluster is the ONE thing this tool
changes (a ``benchmark`` issue could make it data: PERF.md, section 7):
the row's nodes labelled ``zone1`` / ``zone2`` / ``zone3`` in turn, the
row's init pods bound round-robin over a seeded order of ``zone1``'s
nodes alone, the residents placed among them by the reference's own
auction (its filter keeps them in ``zone1``).  The sample, the gang
cycle of the timed program and the judging are ``lib/check.py``'s.

Two worlds a seed, because InterPodAffinity holds a pod to its peers'
zone TWICE, by the filter and, at ``hardPodAffinityWeight`` 1, by the
bound pods' own required terms as score rows (``scoring.go``
processExistingPod), 100 points against LeastAllocated's ~10:

  owners   the bound pods are the row's template, terms and all.  The
           score alone already sends every pod to ``zone1``, so the
           control ``no-required-affinity`` reads 0 here too: what the
           hard weight hides.
  labels   the bound pods carry the template's LABELS and no term (a
           service's replicas that a client pod must join, themselves
           indifferent): no score row, the filter alone decides, and
           the control sends the batch to the two EMPTY zones, which
           LeastAllocated prefers and the filter refuses.

Per seed and world it prints ``ZONES {...}``: the misses of the program
as it stands and of the reference's own auction in its place (both must
be 0), of both under ``no-required-affinity``, and how many of the
program's placements lie in each zone.  ``--program 0`` reads the
reference's half alone (no jax).
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
from types import SimpleNamespace
from typing import Any, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.tools.mixed_sample_check import TERM_FIELDS  # noqa: E402

CONTROL = "no-required-affinity"
ZONES = ("zone1", "zone2", "zone3")
HOME = ZONES[0]
WORLDS = ("owners", "labels")


def zoned(cell, zones=ZONES):
    """The cell with its nodes labelled by ``zones`` in turn; everything
    else (templates, scheduler, traffic, reference) is the cell's."""
    from perfbench.lib import world
    cluster = dict(cell.config["cluster"], node_labels={
        world.ZONE: list(zones)})
    cluster.pop("zones", None)
    config = dict(cell.config, cluster=cluster)
    world.validate(config)
    return SimpleNamespace(name=cell.name, config=config,
                           traffic=cell.traffic, reference=cell.reference)


def _labels_only(rec):
    return dataclasses.replace(rec, **{f: () for f in TERM_FIELDS})


def zone_world(cell, seed: int, owners: bool = True
               ) -> Tuple[List[Any], List[Tuple[Any, str]]]:
    """(nodes, bound) of the zoned cell: the init pods round-robin over
    a seeded order of the home zone's nodes, then ``check_cluster``'s
    residents placed by the reference's own auction.  Without ``owners``
    every bound pod keeps its labels and loses its terms."""
    import numpy as np
    from perfbench.lib import check, world
    ref = cell.reference()
    nodes = world.node_records(cell.config)
    home = [n.name for n in nodes if n.labels[world.ZONE] == HOME]
    order = np.random.default_rng([int(seed), 0x20E]).permutation(len(home))
    recs = [rec for rec, _ in world.init_records(cell.config, seed)]
    init = [(rec, home[int(order[j % len(home)])])
            for j, rec in enumerate(recs)]
    _, bound = check.check_cluster(cell, ref, seed, nodes, init)
    if not owners:
        bound = [(_labels_only(rec), node) for rec, node in bound]
    return nodes, bound


def _judge(cell, nodes, bound):
    cluster = cell.reference().Cluster(nodes)
    for rec, node in bound:
        cluster.add(rec, node)
    return cluster


def reference_misses(cell, seed: int, nodes, bound, **control) -> int:
    import numpy as np
    from perfbench.lib import check
    ref = cell.reference()
    sample = check.sample_records(cell, seed)
    placed = ref.auction_schedule(
        _judge(cell, nodes, bound), sample,
        np.random.default_rng([seed, 0xC0]), **control)
    return len(ref.gang_misses(_judge(cell, nodes, bound), sample, placed))


def program_misses(cell, seed: int, nodes, bound):
    """(misses as the reference words them, placements by zone)."""
    from perfbench.lib import check, world
    sample = check.sample_records(cell, seed)
    placed = check.program_gang_cycle(cell, seed, nodes, bound, sample)
    zone_of = {n.name: n.labels[world.ZONE] for n in nodes}
    by_zone = collections.Counter(
        zone_of.get(node, "pending") if node else "pending"
        for node in placed.values())
    return (cell.reference().gang_misses(_judge(cell, nodes, bound),
                                         sample, placed), dict(by_zone))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--worlds", default=",".join(WORLDS))
    ap.add_argument("--program", type=int, choices=(0, 1), default=1,
                    help="0: the reference's half only (no jax)")
    args = ap.parse_args(argv)
    from perfbench.lib import spec
    from perfbench.tools.cell_controls import control_module
    cell = zoned(spec.cell(args.workload, ROOT))
    mod = control_module(CONTROL)
    for seed in [int(s) for s in args.seeds.split(",")]:
        for name in args.worlds.split(","):
            nodes, bound = zone_world(cell, seed, owners=(name == "owners"))
            row = {"workload": cell.name, "seed": seed, "world": name,
                   "nodes": len(nodes), "bound": len(bound),
                   "batch": int(cell.config["scheduler"]["batch_size"]),
                   "reference": reference_misses(cell, seed, nodes, bound),
                   "reference:" + CONTROL: reference_misses(
                       cell, seed, nodes, bound, **mod.REFERENCE_KW)}
            if args.program:
                misses, by_zone = program_misses(cell, seed, nodes, bound)
                row.update(program=len(misses), first_misses=misses[:3],
                           program_zones=by_zone)
                with mod.program_control():
                    misses, by_zone = program_misses(cell, seed, nodes,
                                                     bound)
                row["program:" + CONTROL] = len(misses)
                row["control_zones"] = by_zone
            print("ZONES " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
