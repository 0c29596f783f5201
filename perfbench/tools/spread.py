#!/usr/bin/env python3
"""Median and spread of each metric over the result lines of several
runs of one cell (the last line of each log file given):

    python3 perfbench/tools/spread.py run1.log run2.log ...

The spread is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median: what a
bound in BENCHMARK.json is set from (about five times the widest).
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    from perfbench.lib import stats
    values = {}
    flags = []
    for path in argv[1:]:
        with open(path) as f:
            lines = [x for x in f.read().splitlines() if x.strip()]
        try:
            res = json.loads(lines[-1])
        except (IndexError, ValueError):
            print(f"{path}: no result line")
            continue
        flags.append((os.path.basename(path), res["correct"],
                      res["attempted"], res["failed"]))
        for k, v in res["metrics"].items():
            values.setdefault(k, []).append(v["value"])
    for name, correct, attempted, failed in flags:
        print(f"{name}: correct {correct} attempted {attempted} "
              f"failed {failed}")
    for k, xs in sorted(values.items()):
        line = f"{k}: n {len(xs)} median {statistics.median(xs):.6g}"
        if len(xs) >= 3:
            line += f" iqr/median {stats.iqr_spread(xs):.4%}"
        print(line + "  " + " ".join(f"{x:.6g}" for x in xs))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
