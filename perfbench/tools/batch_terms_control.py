#!/usr/bin/env python3
"""The control ``no-batch-score-terms`` at a cell's own size, and what
check (b)'s sample cycle looked like: rounds, admits a round, proposals
deferred by capacity, tie-set sizes.

    python3 perfbench/tools/batch_terms_control.py \\
        --workload sp-prefaffinity-5000.saturated --seeds 1,2,3

Per seed it prints the misses of check (b), judged by the
configuration's own reference, for

  reference                        the reference's float64 auction
  reference:no-batch-score-terms   the same, the pods it admits scoring
                                   nobody (reference/batch_blind_terms.py)
  program                          the program as it stands
  program:no-batch-score-terms     the program with the splice patched
                                   off (controls/no-batch-score-terms.py)

and ``differs``: of the batch's pods, how many the program places on
another node with the splice off (same seed, same tie-break draws), which
is what the splice moved whether or not check (b) can tell.  ``--program
0`` runs the reference's half alone (no jax).  ``sample`` is the
reference's own auction over the check's cluster, round by round: the
tie set's size at the round's start (every pod of this row is alike, so
one set a round), proposals, admitted, deferred by capacity.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROL = "no-batch-score-terms"


def sample_rounds(cell, seed: int, nodes, init):
    """The reference's auction over check (b)'s cluster, a row a round."""
    import numpy as np
    from perfbench.lib import check
    ref = cell.reference()
    cluster, _ = check.check_cluster(cell, ref, seed, nodes, init)
    left = check.sample_records(cell, seed)
    rng = np.random.default_rng([seed, 0xC0])
    rows = []
    while left:
        ties = cluster.tie_set(left[0])
        props = []
        for pod in left:
            best = cluster.tie_set(pod)
            if len(best):
                props.append((pod, int(best[rng.integers(len(best))])))
        admitted = set()
        for pod, r in props:
            if cluster.fits(pod, r) and cluster.terms_ok(pod, r):
                cluster.add(pod, cluster.names[r])
                admitted.add(pod.name)
        rows.append({"tie_set": int(len(ties)), "proposals": len(props),
                     "admitted": len(admitted),
                     "capacity_deferred": len(props) - len(admitted)})
        if not admitted:
            break
        left = [p for p in left if p.name not in admitted]
    return rows


def reference_misses(cell, seed: int, nodes, init, **control) -> int:
    """``tools/control.py``'s reference row with ``batch_blind_terms`` in
    the reference's place (switch off, it is ``interpod_terms``)."""
    from types import SimpleNamespace
    from perfbench.reference import batch_blind_terms as blind
    from perfbench.tools import control as control_tool
    shim = SimpleNamespace(config=cell.config, traffic=cell.traffic,
                           reference=lambda: blind)
    return control_tool.reference_misses(shim, seed, nodes, init, **control)


def program_placements(cell, seed: int, nodes, init):
    from perfbench.lib import check
    ref = cell.reference()
    cluster, bound = check.check_cluster(cell, ref, seed, nodes, init)
    sample = check.sample_records(cell, seed)
    placed = check.program_gang_cycle(cell, seed, nodes, bound, sample)
    return placed, len(ref.gang_misses(cluster, sample, placed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    from perfbench.lib import spec, world
    cell = spec.cell(args.workload, ROOT)
    control = spec._load_module(
        os.path.join(ROOT, "perfbench", "controls", CONTROL + ".py"),
        "perfbench_control_no_batch_score_terms")
    nodes = world.node_records(cell.config)
    for seed in [int(s) for s in args.seeds.split(",")]:
        init = world.init_records(cell.config, seed)
        row = {"workload": cell.name, "seed": seed, "control": CONTROL,
               "batch": int(cell.config["scheduler"]["batch_size"]),
               "sample": sample_rounds(cell, seed, nodes, init),
               "reference": reference_misses(cell, seed, nodes, init),
               "reference:" + CONTROL: reference_misses(
                   cell, seed, nodes, init, **control.REFERENCE_KW)}
        if args.program:
            placed, row["program"] = program_placements(
                cell, seed, nodes, init)
            with control.program_control():
                blind, row["program:" + CONTROL] = program_placements(
                    cell, seed, nodes, init)
            row["differs"] = sum(1 for k in placed if placed[k] != blind[k])
        print("CONTROL " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
