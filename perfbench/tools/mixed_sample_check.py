#!/usr/bin/env python3
"""Check (b) with a sample drawn from EVERY pod template of the
configuration, at the cell's own size.

    python3 perfbench/tools/mixed_sample_check.py --workload <name> \
        --seeds 1,2 [--control no-terms-match]

The harness's check (b) (``lib/check.py``) draws its sample from the
measured template alone.  Where that template is plain, as in
``sp-mixed-5000``, no existing term selects a sample pod, so a term that
fails to select, or selects too much, cannot show there.  The sample is
the ONE thing this tool changes, and it is not data yet (a ``benchmark``
issue: PERF.md, section 7): for the length of a call ``mixed_sample``
stands in ``check.sample_records``; the cluster, the gang cycle of the
timed program and the judging are ``lib/check.py``'s and
``tools/control.py``'s.  The batch cycles through ``shapes``:

  * the measured template and every init template in the file's order
    (here plain pods and upstream's four labelled shapes), so existing
    terms both select and do not select incoming pods, and incoming
    terms count existing pods;
  * every labelled template once more WITH ITS LABELS AND WITHOUT ITS
    TERMS.  Upstream's labelled templates select themselves, so whatever
    an existing pod's term does to such a pod the pod's own term does
    too (a green pod is kept off a green pod's node by either), and a
    fault in the existing-term tables could not move one of them out of
    a tie set.  A pod that only carries the label is placed by the
    existing pods' terms alone.

Per seed it prints ``MIXED {...}``: the misses of the program as it
stands and of the reference's own float64 auction in its place (both
must be 0) and, with ``--control <name>``, of both with that control of
``perfbench/controls/`` patched in.  ``no-terms-match`` is the one this
sample is for; a configuration's own ``control`` has to fail the plain
sample, which that one cannot.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from typing import Any, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


TERM_FIELDS = ("anti_required", "aff_required", "anti_preferred",
               "aff_preferred")


def shapes(config) -> List[tuple]:
    """(template, with its terms?) of every shape the sample cycles
    through: the measured template and every init template, each once,
    in the file's order; then every template that states labels, labels
    only."""
    from perfbench.lib import world
    names = [config["measured_pods"]["template"]]
    names += [t for t, _ in world.init_groups(config)]
    names = list(dict.fromkeys(names))
    labelled = [t for t in names
                if world.pod_record(config, t, "sample", 0).labels]
    return [(t, True) for t in names] + [(t, False) for t in labelled]


def mixed_sample(cell, seed: int) -> List[Any]:
    """One full batch that cycles through ``shapes``, drawn from the
    seed: pod ``i`` is of shape ``i % len(shapes)``."""
    import dataclasses
    import numpy as np
    from perfbench.lib import world
    kinds = shapes(cell.config)
    n = int(cell.config["scheduler"]["batch_size"])
    rng = np.random.default_rng([int(seed), 0x6A3])
    idx = rng.choice(1_000_000, size=n, replace=False)
    out = []
    for i, j in enumerate(idx):
        template, with_terms = kinds[i % len(kinds)]
        rec = world.pod_record(cell.config, template, "sample", int(j))
        if not with_terms:
            rec = dataclasses.replace(
                rec, name=rec.name + "-labels",
                **{field: () for field in TERM_FIELDS})
        out.append(rec)
    return out


@contextlib.contextmanager
def _mixed_for_the_sample():
    from perfbench.lib import check
    real = check.sample_records
    check.sample_records = mixed_sample
    try:
        yield
    finally:
        check.sample_records = real


def program_misses(cell, seed: int, nodes, init) -> List[str]:
    """Check (b) over the mixed sample: the misses of one gang cycle of
    the program, as the reference words them."""
    from perfbench.lib import check
    with _mixed_for_the_sample():
        return check.gang_check(cell, seed, nodes, init)


def reference_misses(cell, seed: int, nodes, init, **control) -> int:
    """The same with the reference's own auction in the program's place:
    ``tools/control.py``'s, counted."""
    from perfbench.tools import control as control_tool
    with _mixed_for_the_sample():
        return control_tool.reference_misses(cell, seed, nodes, init,
                                             **control)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="",
                    help="also with this control of perfbench/controls/ "
                         "(no-terms-match)")
    args = ap.parse_args(argv)
    from perfbench.lib import spec, world
    cell = spec.cell(args.workload, ROOT)
    nodes = world.node_records(cell.config)
    mod = args.control and spec._load_module(
        os.path.join(ROOT, "perfbench", "controls", args.control + ".py"),
        "perfbench_control_mixed")
    for seed in [int(s) for s in args.seeds.split(",")]:
        init = world.init_records(cell.config, seed)
        row = {"workload": cell.name, "seed": seed,
               "batch": int(cell.config["scheduler"]["batch_size"]),
               "shapes": [t + ("" if terms else ":labels-only")
                          for t, terms in shapes(cell.config)],
               "reference": reference_misses(cell, seed, nodes, init)}
        misses = program_misses(cell, seed, nodes, init)
        row["program"] = len(misses)
        row["first_misses"] = misses[:5]
        if mod:
            row["reference:" + args.control] = reference_misses(
                cell, seed, nodes, init, **mod.REFERENCE_KW)
            with mod.program_control():
                row["program:" + args.control] = len(
                    program_misses(cell, seed, nodes, init))
        print("MIXED " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
