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

Controls (a configuration names the one that must fail it, ``control``):

  bf16-scores  the summed plugin scores held in bfloat16, the nearest
               precision below the float32 the program sums them in: the
               step that would halve the bytes of every [pods, nodes]
               score plane
  blind-batch  the batch's own pods left out of the required-term
               filter (the auction's ``intra_batch_topology`` switched
               off): the exchange between the pods of one batch left out

PERF.md records the readings the limit (0) was set from.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

REFERENCE_CONTROLS = {"bf16-scores": {"lowprec": True},
                      "blind-batch": {"blind_batch": True}}


@contextlib.contextmanager
def program_control(name: str):
    """The program with the named control patched in, for the block."""
    import jax
    import jax.numpy as jnp
    from kubetpu.models import gang
    if name == "bf16-scores":
        attr, real = "run_scores", gang.run_scores

        def patched(*a, **kw):
            total, per_plugin = real(*a, **kw)
            return (total.astype(jnp.bfloat16).astype(jnp.float32),
                    per_plugin)
    elif name == "blind-batch":
        attr, real = "run_auction", gang.run_auction

        def patched(*a, **kw):
            kw["intra_batch_topology"] = False
            return real(*a, **kw)
    else:
        raise ValueError(f"no control {name!r}; known: "
                         f"{sorted(REFERENCE_CONTROLS)}")
    setattr(gang, attr, patched)
    jax.clear_caches()        # the auction is traced anew, patched
    try:
        yield
    finally:
        setattr(gang, attr, real)
        jax.clear_caches()


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
    nodes = world.node_records(cell.config)
    for seed in [int(s) for s in args.seeds.split(",")]:
        init = world.init_records(cell.config, seed)
        row = {"workload": cell.name, "seed": seed, "control": control,
               "batch": int(cell.config["scheduler"]["batch_size"]),
               "reference": reference_misses(cell, seed, nodes, init),
               "reference:" + control: reference_misses(
                   cell, seed, nodes, init, **REFERENCE_CONTROLS[control])}
        if args.program:
            row["program"] = len(check.gang_check(cell, seed, nodes, init))
            with program_control(control):
                row["program:" + control] = len(
                    check.gang_check(cell, seed, nodes, init))
        print("CONTROL " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
