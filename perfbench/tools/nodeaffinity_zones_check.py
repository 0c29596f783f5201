#!/usr/bin/env python3
"""Check (b) of a node-affinity row over a cluster in which the filter
BITES: the row's nodes in THREE zones, the term listing two of them, the
third EMPTY.

    python3 perfbench/tools/nodeaffinity_zones_check.py \
        --workload sp-nodeaffinity-5000.saturated --seeds 1,2

``sp-nodeaffinity-5000`` labels every node ``zone1`` (upstream does) and
its term lists ``zone1`` and ``zone2``, so NodeAffinity's filter admits
every node and the row's own check (b) cannot see a node wrongly
ADMITTED.  The cluster is the ONE thing this tool changes (no public
source fixes such a cluster's shape: PERF.md, section 7): the row's
nodes labelled ``zone1`` / ``zone2`` / ``zone3`` in turn, the row's init
pods bound round-robin over a seeded order of ``zone1``'s and
``zone2``'s nodes alone, the residents placed among them by the
reference's own auction (its filter keeps them out of ``zone3``).  So
``zone3``'s nodes are EMPTY: LeastAllocated prefers them and the filter
refuses them.  The sample, the gang cycle of the timed program and the
judging are ``lib/check.py``'s.

Per seed it prints ``ZONES {...}``: the misses of the program as it
stands and of the reference's own auction in its place (both must be
0), of both under ``no-node-affinity`` (expected: the whole batch, sent
to ``zone3``), and how many of the program's placements lie in each
zone.  ``--program 0`` reads the reference's half alone (no jax).
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
from typing import Any, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the three-zone cell, the judge's cluster and the program's half are the
# pod-affinity row's tool's, by import
from perfbench.tools.zone_affinity_check import (  # noqa: E402
    ZONES, _judge, program_misses, zoned)

CONTROL = "no-node-affinity"
REFUSED = ZONES[2]


def zone_world(cell, seed: int) -> Tuple[List[Any], List[Tuple[Any, str]]]:
    """(nodes, bound) of the zoned cell: the init pods round-robin over
    a seeded order of the nodes outside the refused zone, then
    ``check_cluster``'s residents placed by the reference's own auction."""
    import numpy as np
    from perfbench.lib import check, world
    ref = cell.reference()
    nodes = world.node_records(cell.config)
    open_ = [n.name for n in nodes if n.labels[world.ZONE] != REFUSED]
    order = np.random.default_rng([int(seed), 0x20E]).permutation(len(open_))
    recs = [rec for rec, _ in world.init_records(cell.config, seed)]
    init = [(rec, open_[int(order[j % len(open_)])])
            for j, rec in enumerate(recs)]
    _, bound = check.check_cluster(cell, ref, seed, nodes, init)
    return nodes, bound


def _by_zone(nodes, placed) -> dict:
    from perfbench.lib import world
    zone_of = {n.name: n.labels[world.ZONE] for n in nodes}
    return dict(collections.Counter(
        zone_of.get(node, "pending") if node else "pending"
        for node in placed.values()))


def reference_misses(cell, seed: int, nodes, bound, **control):
    """(misses, placements by zone) of the reference's own auction."""
    import numpy as np
    from perfbench.lib import check
    ref = cell.reference()
    sample = check.sample_records(cell, seed)
    placed = ref.auction_schedule(
        _judge(cell, nodes, bound), sample,
        np.random.default_rng([seed, 0xC0]), **control)
    return (len(ref.gang_misses(_judge(cell, nodes, bound), sample, placed)),
            _by_zone(nodes, placed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=int, choices=(0, 1), default=1,
                    help="0: the reference's half only (no jax)")
    args = ap.parse_args(argv)
    from perfbench.lib import spec
    from perfbench.tools.cell_controls import control_module
    cell = zoned(spec.cell(args.workload, ROOT))
    mod = control_module(CONTROL)
    for seed in [int(s) for s in args.seeds.split(",")]:
        nodes, bound = zone_world(cell, seed)
        n_ref, ref_zones = reference_misses(cell, seed, nodes, bound)
        n_ctl, ref_ctl_zones = reference_misses(cell, seed, nodes, bound,
                                                **mod.REFERENCE_KW)
        row = {"workload": cell.name, "seed": seed, "nodes": len(nodes),
               "bound": len(bound), "bound_zones": _by_zone(
                   nodes, {rec.name: node for rec, node in bound}),
               "batch": int(cell.config["scheduler"]["batch_size"]),
               "reference": n_ref, "reference_zones": ref_zones,
               "reference:" + CONTROL: n_ctl,
               "reference_control_zones": ref_ctl_zones}
        if args.program:
            misses, by_zone = program_misses(cell, seed, nodes, bound)
            row.update(program=len(misses), first_misses=misses[:3],
                       program_zones=by_zone)
            with mod.program_control():
                misses, by_zone = program_misses(cell, seed, nodes, bound)
            row["program:" + CONTROL] = len(misses)
            row["control_zones"] = by_zone
        print("ZONES " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
