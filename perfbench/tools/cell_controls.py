#!/usr/bin/env python3
"""Check (b) of one cell under SEVERAL named controls, at the cell's own
size: ``tools/control.py`` reads the one control a configuration names,
this reads any of ``perfbench/controls/`` beside it, so that a row can
say which lower precision or missing mechanism its check catches and
which it cannot.

    python3 perfbench/tools/cell_controls.py --workload <name> \\
        --seeds 1,2,3 --controls bf16-scores,f32-product

The configuration's own ``control`` (and its ``precision_control``, where
it names one) are read whether listed or not.  Per seed it prints one
line ``CONTROLS {...}``: the misses of check (b) for the reference's own
auction and the program as it stands (both must be 0), and for each
control the reference with the control's ``REFERENCE_KW`` and the
program under its ``program_control()``.  ``--program 0`` reads the
reference's half alone (no jax)."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def control_module(name: str, root: str = ROOT):
    from perfbench.lib import spec
    path = os.path.join(root, "perfbench", "controls", name + ".py")
    if not os.path.exists(path):
        raise spec.SpecError(f"control {name!r}: no {path}")
    return spec._load_module(path, "perfbench_control_"
                             + name.replace(".", "_").replace("-", "_"))


def controls_of(cell, listed=()) -> list:
    """The configuration's own controls first, then the listed ones."""
    names = [cell.config["control"]]
    if cell.config.get("precision_control"):
        names.append(cell.config["precision_control"])
    return names + [n for n in listed if n and n not in names]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--program", type=int, choices=(0, 1), default=1,
                    help="0: the reference's halves only (no jax)")
    args = ap.parse_args(argv)
    from perfbench.lib import check, spec, world
    from perfbench.tools.control import reference_misses
    cell = spec.cell(args.workload, ROOT)
    names = controls_of(cell, args.controls.split(","))
    mods = {name: control_module(name) for name in names}
    nodes = world.node_records(cell.config)
    for seed in [int(s) for s in args.seeds.split(",")]:
        init = world.init_records(cell.config, seed)
        row = {"workload": cell.name, "seed": seed,
               "batch": int(cell.config["scheduler"]["batch_size"]),
               "reference": reference_misses(cell, seed, nodes, init)}
        for name, mod in mods.items():
            row["reference:" + name] = reference_misses(
                cell, seed, nodes, init, **mod.REFERENCE_KW)
        if args.program:
            row["program"] = len(check.gang_check(cell, seed, nodes, init))
            for name, mod in mods.items():
                with mod.program_control():
                    row["program:" + name] = len(
                        check.gang_check(cell, seed, nodes, init))
        print("CONTROLS " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
