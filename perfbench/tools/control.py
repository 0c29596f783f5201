#!/usr/bin/env python3
"""The controls of check (b), at a cell's own size.  Each must come out
as NOT correct; the same check on the tree as it stands must pass.

    python3 perfbench/tools/control.py --workload <name> --seeds 1,2,3

Per seed it prints the misses of check (b) for

  reference            the reference's own float64 auction in the
                       program's place (must be 0: the check agrees with
                       its own semantics)
  reference:<control>  the same with the configuration's ``control``
  program              the program as it stands (must be 0)
  program:<control>    the program with the control patched into it

A control is a file found by name, ``perfbench/controls/<control>.py``
(``lib/spec.py``): ``REFERENCE_KW`` is what the reference's
``auction_schedule`` is called with, ``program_control()`` the context
manager that patches the program.  The two that exist:

  bf16-scores  the summed plugin scores held in bfloat16
  blind-batch  the batch's own pods left out of the required-term filter

PERF.md records the readings the limit (0) was set from.
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


def reference_misses(cell, seed: int, nodes, init, **control) -> int:
    import numpy as np
    from perfbench.lib import check
    ref = cell.reference()
    sample = check.sample_records(cell, seed)
    cluster, _ = check.check_cluster(cell, ref, seed, nodes, init)
    placed = ref.auction_schedule(
        cluster, sample, np.random.default_rng([seed, 0xC0]), **control)
    judge, _ = check.check_cluster(cell, ref, seed, nodes, init)
    return len(ref.gang_misses(judge, sample, placed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=int, choices=(0, 1), default=1,
                    help="0: the reference's controls only (no jax)")
    args = ap.parse_args(argv)
    from perfbench.lib import check, spec, world
    cell = spec.cell(args.workload, ROOT)
    control = cell.config["control"]
    control_mod = cell.control()
    nodes = world.node_records(cell.config)
    for seed in [int(s) for s in args.seeds.split(",")]:
        init = world.init_records(cell.config, seed)
        row = {"workload": cell.name, "seed": seed, "control": control,
               "batch": int(cell.config["scheduler"]["batch_size"]),
               "reference": reference_misses(cell, seed, nodes, init),
               "reference:" + control: reference_misses(
                   cell, seed, nodes, init, **control_mod.REFERENCE_KW)}
        if args.program:
            row["program"] = len(check.gang_check(cell, seed, nodes, init))
            with control_mod.program_control():
                row["program:" + control] = len(
                    check.gang_check(cell, seed, nodes, init))
        print("CONTROL " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
