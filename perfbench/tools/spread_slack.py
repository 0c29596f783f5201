#!/usr/bin/env python3
"""What the replay's spread line can see in a whole run of a cell whose
reference is ``topology_spread``: one run of the cell as ``run.py --trace
0`` makes it, with the reference's ``replay`` wrapped so that the numbers
the line compared at every constrained bind are kept:

    python3 perfbench/tools/spread_slack.py --workload <cell> --seed <n> --seconds <s>

Prints ``SLACK {...}``: over the constrained binds of the whole log
(warm-up included) and over the last third of them (the steady state:
the window and the end of the warm-up), the quartiles of
``low_z`` (the bind's zone, counted from below), of ``high_min`` (the
least zone, counted from above) and of the room left,
maxSkew - (low_z + selfMatchNum - high_min); then the run's result line,
last, as run.py prints it.  PERF.md records the readings.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def quartiles(values) -> dict:
    values = sorted(values)
    if len(values) < 2:
        return {"n": len(values)}
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return {"n": len(values), "min": values[0], "q1": q1, "median": q2,
            "q3": q3, "max": values[-1]}


def summary(rows) -> dict:
    """``rows``: (low_z, selfMatchNum, high_min, maxSkew) a bind."""
    return {"low_z": quartiles([r[0] for r in rows]),
            "high_min": quartiles([r[2] for r in rows]),
            "room": quartiles([r[3] - (r[0] + r[1] - r[2]) for r in rows])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--require-tpu", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    from perfbench.lib import drive, spec
    cell = spec.cell(args.workload, ROOT)
    ref = cell.reference()
    kept = {}
    plain_replay = ref.replay

    def replay(nodes, init, pods, log, readback, stuck=()):
        rows = []
        ref.spread_violations(nodes, init, pods, log, slack=rows)
        kept["rows"] = rows
        return plain_replay(nodes, init, pods, log, readback, stuck)
    ref.replay = replay
    cell.reference = lambda: ref
    result = drive.run_cell(cell, args.seed, args.seconds, False,
                            require_tpu=bool(args.require_tpu))
    rows = kept.get("rows", [])
    print("SLACK " + json.dumps({"all": summary(rows),
                                 "last_third": summary(
                                     rows[2 * len(rows) // 3:])}),
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
