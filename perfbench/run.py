#!/usr/bin/env python3
"""Run one cell of the benchmark once:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses to measure on anything but a TPU with the chips the cell asks
for.  The last line of standard output is the result: one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
traced, ``breakdown``.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics.  Any failure exits nonzero
and prints no result line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "kubetpu")):
        print("perfbench: no kubetpu/ beside perfbench/: nothing to "
              "measure", file=sys.stderr)
        return 2
    from perfbench.lib import drive, spec
    try:
        cell = spec.cell(args.workload, ROOT)
        result = drive.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace))
    except (drive.RunError, spec.SpecError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
